"""Published peaks of one chip, keyed by ``device_kind``, and the least
work of the fused query kernel.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bfloat16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s). JAX reports the
chip as ``TPU v5 lite``. A device that is not in the table is an error.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def fused_query_least_s(live_copies: float, pq_m: int, pq_centers: int,
                        peak: dict, calls: int = 1, rows: int = 1
                        ) -> tuple[float, str]:
    """Least time of ``calls`` shortlist calls over ``live_copies`` SOAR
    copies (every partition probed) that carry ``rows`` query rows in
    all: each call must read each live copy's PQ code (``pq_m`` bytes)
    and point id (4 bytes) once, and each row its lookup table
    (``pq_m * pq_centers`` float32) and add ``pq_m`` table entries per
    copy. The work is counted from live copies and real rows, not slab
    slots or padded rows, so it does not depend on how the kernel lays
    the slabs out. Returns (seconds, the bound: "bytes" or "ops")."""
    data = calls * live_copies * (pq_m + 4) + rows * pq_m * pq_centers * 4
    ops = rows * live_copies * pq_m
    t_bytes = data / peak["hbm_bytes_per_s"]
    t_ops = ops / peak["bf16_flops"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
