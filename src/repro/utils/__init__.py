from repro.utils.timing import Timer, percentiles


def pow2_pad(n: int, cap: int | None = None) -> int:
    """Smallest power of two >= n (optionally clamped to ``cap``) — the
    batch-padding discipline that bounds jit recompiles."""
    p = 1
    while p < n:
        p *= 2
    return p if cap is None else min(p, cap)
