"""Front end (``serve/frontend.py``): 95th percentile of every query's
latency in the traced window, scheduled arrival to answer, where the
cell's host stalls leave it too unsteady to stand as an end-to-end
metric. Tracing adds its own cost to each request."""
import numpy as np


def read(run):
    lat = run.query_ms
    return float(np.percentile(lat, 95)) if lat else None
