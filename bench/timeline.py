"""Helpers that the readers of the program's own spans and counters share:
the serve engine's step records (``step_log``, whose ``*_s`` keys its
``hgnn.*`` spans fill) and its requests' timeline stamps
(``admitted_at``, ``started_at``, ``finished_at``).  Each returns ``None``
where the program records none of it."""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np


def step_mean(ctx: Dict, key: str, scale: float) -> Optional[float]:
    """Mean of ``key`` over the window's steps that did not fail."""
    vals = [s[key] for s in ctx["window"].get("steps", [])
            if key in s and not s.get("failed")]
    return scale * float(np.mean(vals)) if vals else None


def request_mean_ms(ctx: Dict, start: str, end: str) -> Optional[float]:
    """Mean ``end - start`` over the window's OK requests, in ms."""
    vals = [getattr(r, end) - getattr(r, start)
            for r in ctx["window"].get("ok", [])
            if getattr(r, start, None) is not None
            and getattr(r, end, None) is not None]
    return 1e3 * float(np.mean(vals)) if vals else None
