"""Query path (``core/gus.py``): mean ``embed`` span, the embedding
generator's call on one dispatch's query rows."""


def read(run):
    spans = run.spans.get("embed", [])
    return sum(spans) / len(spans) if spans else None
