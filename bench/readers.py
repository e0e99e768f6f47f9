"""Helpers that the per-layer metric readers in ``bench/metrics/`` share.
Each returns ``None`` where the run has nothing to read."""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from bench import work


def idle_pct(ctx: Dict) -> Optional[float]:
    tr = ctx["trace"]
    return None if tr is None else 100.0 * tr["idle_share"]


def device_ms_per(ctx: Dict, count: int) -> Optional[float]:
    tr = ctx["trace"]
    if tr is None or count == 0:
        return None
    return 1e3 * tr["busy_s"] / count


def forwards(ctx: Dict) -> int:
    return int(ctx["window"].get("forwards", 0))


def steps(ctx: Dict) -> int:
    return len(ctx["window"].get("steps", []))


def step_mean(ctx: Dict, key: str, scale: float) -> Optional[float]:
    vals = [s[key] for s in ctx["window"].get("steps", [])
            if not s.get("failed")]
    return scale * float(np.mean(vals)) if vals else None


def sample_ms(ctx: Dict) -> Optional[float]:
    s = ctx["sample_s"]
    return 1e3 * float(np.mean(s)) if s else None


def roofline_pct(ctx: Dict) -> Optional[float]:
    dev_ms = device_ms_per(ctx, forwards(ctx))
    if dev_ms is None or ctx["work"] is None or ctx["peaks"] is None:
        return None
    least = work.least_time_s(ctx["work"], ctx["peaks"])["seconds"]
    return 100.0 * least / (dev_ms / 1e3)


def mfu_pct(ctx: Dict) -> Optional[float]:
    win, w, pk = ctx["window"], ctx["work"], ctx["peaks"]
    if w is None or pk is None or not forwards(ctx):
        return None
    return 100.0 * w["flops"] * forwards(ctx) / win["window_s"] / pk["flops"]
