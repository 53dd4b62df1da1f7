"""Query path (``core/gus.py``): mean ``score`` span, the candidates'
feature gather, the pair features, the scorer and the fetch of its
weights for one dispatch."""


def read(run):
    spans = run.spans.get("score", [])
    return sum(spans) / len(spans) if spans else None
