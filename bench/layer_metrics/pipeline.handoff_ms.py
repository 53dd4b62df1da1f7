"""Write path (``serve/pipeline.py``): mean ``handoff`` span, the apply,
barrier and graph tick of one fused window."""


def read(run):
    spans = run.spans.get("handoff", [])
    return sum(spans) / len(spans) if spans else None
