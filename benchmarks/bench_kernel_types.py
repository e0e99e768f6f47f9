"""Paper Fig. 3 — breakdown on kernel TYPES (DM/TB/EW/DR) per stage.

Adaptation (DESIGN.md §2): no per-CUDA-kernel timeline exists on TPU; the
per-class shares come from the compiled HLO via the characterizer —
roofline-predicted time per class (max of compute/memory term using each
class's own FLOPs/bytes).

Paper claims to validate: FP is DM-dominated; NA is TB+EW dominated;
SA mixes DM + EW + DR.
"""
from __future__ import annotations

import jax

from benchmarks.common import Row, emit
from benchmarks.hgnn_setup import build, stage_fns
from repro.core.characterize import analyze_hlo_text, peaks

CASES = [("han", "imdb"), ("han", "dblp"), ("rgcn", "imdb"), ("magnn", "imdb")]
CLASSES = ("DM", "TB", "EW", "DR")


def class_times(rep):
    pk = peaks()  # the device's published peaks (CPU hosts: the v5e model)
    out = {}
    for c in CLASSES:
        fl = rep["flops_by_class"].get(c, 0.0)
        by = rep["hbm_bytes_by_class"].get(c, 0.0)
        out[c] = max(fl / pk["flops"], by / pk["hbm_bw"])
    return out


def run() -> list:
    rows: list = []
    for model, ds in CASES:
        cfg, m, params, batch = build(model, ds)
        fns = stage_fns(m, params, batch)
        for stage in ("FP", "NA", "SA"):
            fn, args = fns[stage]
            comp = fn.lower(*args).compile()
            rep = analyze_hlo_text(comp.as_text())
            ct = class_times(rep)
            tot = sum(ct.values()) or 1.0
            shares = " ".join(f"{c}={100*ct[c]/tot:.0f}%" for c in CLASSES)
            rows.append((f"fig3/{model}/{ds}/{stage}", tot * 1e6, shares))
    return rows


if __name__ == "__main__":
    emit(run())
