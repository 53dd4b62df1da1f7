"""Write path (``serve/pipeline.py``): 95th percentile of the
``apply_lag`` spans, each a mutation batch's wait from the engine's
submit to the end of the hand-off that applied it on the primary."""
import numpy as np


def read(run):
    lags = run.spans.get("apply_lag", [])
    return float(np.percentile(lags, 95)) if lags else None
