"""Counted work of one forward, and the chip's published peaks.

FLOPs and the least bytes a forward must move are reckoned from the
configuration's widths and the graph's real edges after the degree cap
(``min(degree, max_degree)`` per row), never from padded slots or compiled
HLO: the count is the same whatever layout or kernel runs, so removing
padding or fusing a stage raises the share and leaves the yardstick where
it was.  Only work that reaches the logits counts.

Least bytes: each input feature table read once, every weight read once,
each kept edge's int32 source id and each row's int32 offset read once,
the logits written once (float32 throughout).

Each model's FLOPs are counted by the ``work`` function of its module,
``bench/models/<model>.py``, whose docstring gives the formulas.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

# Per-chip peaks keyed by JAX's device_kind.  Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s (bf16) and 819 GB/s of HBM per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to PEAKS with a source")
    return PEAKS[device_kind]


def capped_edges(deg: np.ndarray, cap: int) -> int:
    return int(np.minimum(deg, cap).sum())


def least_time_s(work: Dict[str, float], pk: Dict[str, float]) -> Dict:
    """The larger of FLOPs over peak FLOP/s and bytes over peak bandwidth,
    with the bound that sets it."""
    compute = work["flops"] / pk["flops"]
    memory = work["bytes"] / pk["hbm_bytes_per_s"]
    return {"seconds": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
