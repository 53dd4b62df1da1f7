"""Engine (``serve/engine.py``): mean ``flush`` span, one per query
dispatch (the write path drained before every read)."""


def read(run):
    spans = run.spans.get("flush", [])
    return sum(spans) / len(spans) if spans else None
