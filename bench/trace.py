"""Reduce a profiler trace (``.xplane.pb``) of one traced window to device
busy time, idle share, the top device operations and the idle gaps by the
harness span the host was in.

Device time comes from the ``XLA Ops`` line of each ``/device:TPU:<n>``
plane (the ``XLA Modules`` line where a plane has no op line): busy is the
union of those intervals inside the window, averaged over the chips that
ran anything.  The window is the host span named ``bench.window``.  Host
spans are the ``bench.*`` annotations the harness writes; a gap is charged
to the innermost one that covers its midpoint (``host`` when none does).

The device clock can run ahead of the host's: on a v5e the recorded
program executions began about 1.3 ms before the host call that launched
them.  On one chip the device timeline is therefore shifted by the least
lead of a program over its launch (``tpu::System::Execute``), pairing the
k-th launch with the k-th program, so that no program starts before its
launch; where the counts differ nothing is shifted.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def op_name(event_name: str) -> str:
    """``%fusion.60 = f32[...] fusion(...)`` -> ``fusion.60``."""
    return event_name.split(" = ")[0].strip().lstrip("%")


def load(path: str) -> Dict:
    """Plain lists of ``(name, start_ns, end_ns)`` from the trace: device ops
    per TPU plane, and the harness's host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    modules: Dict[str, List[float]] = {}
    spans: List[Tuple[str, float, float]] = []
    launches: List[float] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get("XLA Ops") or lines.get("XLA Modules")
            if line is not None:
                devices[plane.name] = [(e.name, e.start_ns, e.end_ns)
                                       for e in line.events]
            if "XLA Modules" in lines:
                modules[plane.name] = sorted(
                    e.start_ns for e in lines["XLA Modules"].events)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns, e.end_ns))
                    elif e.name == "tpu::System::Execute":
                        launches.append(e.start_ns)
    skew = 0.0
    if len(modules) == 1:
        starts = next(iter(modules.values()))
        if starts and len(starts) == len(launches):
            skew = max(0.0, min(h - d for h, d in zip(sorted(launches),
                                                      starts)))
    if skew:
        devices = {k: [(n, s + skew, e + skew) for n, s, e in v]
                   for k, v in devices.items()}
    return {"devices": devices, "spans": spans, "skew_ns": skew}


def reduce(raw: Dict, top: int = 10,
           window: Optional[Interval] = None) -> Optional[Dict]:
    """Busy/idle over the ``bench.window`` span (or ``window``).  ``None``
    where there is no window or no device operation inside it."""
    windows = [(s, e) for n, s, e in raw["spans"] if n == "bench.window"]
    if window is None and not windows:
        return None
    lo, hi = window if window is not None else windows[0]
    window_s = (hi - lo) / 1e9
    busy_by_chip, ops = [], {}
    busy_union: List[Interval] = []
    for evs in raw["devices"].values():
        iv = union(clip([(s, e) for _n, s, e in evs], lo, hi))
        if not iv:
            continue
        busy_by_chip.append(sum(e - s for s, e in iv) / 1e9)
        busy_union = union(busy_union + iv)
        for n, s, e in evs:
            if e > lo and s < hi:
                k = op_name(n)
                ops[k] = ops.get(k, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
    if not busy_by_chip:
        return None
    spans = sorted([(s, e, n) for n, s, e in raw["spans"]
                    if n != "bench.window" and e > lo and s < hi])
    gaps: Dict[str, float] = {}
    prev = lo
    for s, e in busy_union + [(hi, hi)]:
        if s > prev:
            mid = 0.5 * (prev + s)
            inner = [(se - ss, n) for ss, se, n in spans if ss <= mid <= se]
            name = min(inner)[1] if inner else "host"
            gaps[name] = gaps.get(name, 0.0) + (s - prev) / 1e9
        prev = max(prev, e)
    busy_s = sum(busy_by_chip) / len(busy_by_chip)
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "chips": len(busy_by_chip),
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:top],
    }


def reduce_file(path: str, top: int = 10) -> Optional[Dict]:
    return reduce(load(path), top)
