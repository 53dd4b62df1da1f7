"""Query path (``ann/sharded_index.py``): mean ``sketch`` span, the
CountSketch of one search's query rows and the host's fetch of it."""


def read(run):
    spans = run.spans.get("sketch", [])
    return sum(spans) / len(spans) if spans else None
