"""Device: share of the traced window in which no operation ran."""


def read(run):
    d = run.device
    if d["window_s"] <= 0 or not d["devices"]:
        return None
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])
