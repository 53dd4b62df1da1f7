"""Query path (``core/gus.py`` over ``ann/sharded_index.py``): mean
``answer_primary`` span, the embed, search and score of one dispatch."""


def read(run):
    spans = run.spans.get("answer_primary", [])
    return sum(spans) / len(spans) if spans else None
