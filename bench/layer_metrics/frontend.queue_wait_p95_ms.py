"""Front end (``serve/frontend.py``): 95th percentile of the queries'
admission-to-dispatch waits, from the program's ``queue_wait`` spans."""
import numpy as np


def read(run):
    waits = run.spans.get("queue_wait", [])
    return float(np.percentile(waits, 95)) if waits else None
