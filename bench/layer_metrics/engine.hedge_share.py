"""Engine (``serve/engine.py``): share of query dispatches whose answer
was reissued past the hedge deadline, 100 times the count of
``answer_hedge`` spans over the count of ``route`` spans."""


def read(run):
    routes = len(run.spans.get("route", []))
    if not routes:
        return None
    return 100.0 * len(run.spans.get("answer_hedge", [])) / routes
