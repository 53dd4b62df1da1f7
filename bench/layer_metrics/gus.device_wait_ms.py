"""Query path (``core/gus.py``, ``ann/sharded_index.py``): time the host
spent blocked on the device per answer, the sum of the ``device_wait``
spans (sketch, query step rows, scorer weights) over the count of
``answer_*`` spans."""

ANSWERS = ("answer_primary", "answer_hedge", "answer_failover")


def read(run):
    waits = run.spans.get("device_wait", [])
    answers = sum(len(run.spans.get(name, [])) for name in ANSWERS)
    return sum(waits) / answers if waits and answers else None
