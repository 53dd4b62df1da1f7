"""Kernels (``kernels/fused_query.py``): device time per call of the
fused shortlist kernel, from the profiler trace."""


# the kernel's device op: its jitted wrapper or its kernel body, as the
# op's name or its op_name metadata in the trace name them
KERNEL = ("fused_query", ("fused_query_kernel", "_fused_kernel"))


def read(run):
    k = run.device["kernels"].get("fused_query")
    if not k or not k["calls"]:
        return None
    return k["device_s"] / k["calls"] * 1e3
