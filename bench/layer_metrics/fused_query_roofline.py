"""Kernels (``kernels/fused_query.py``): the fused shortlist kernel's
share of its roofline, the least time of its calls (``peaks.py``, counted
from live SOAR copies and the query rows the index searched) over their
device time. Bytes bound it."""


# the kernel's device op: its jitted wrapper or its kernel body, as the
# op's name or its op_name metadata in the trace name them
KERNEL = ("fused_query", ("fused_query_kernel", "_fused_kernel"))


def read(run):
    k = run.device["kernels"].get("fused_query")
    if not k or not k["calls"] or k["device_s"] <= 0:
        return None
    least, _ = run.fused_query_least_s(k["calls"])
    return 100.0 * least / k["device_s"]
