"""Paper Fig. 4 + Table 3 — per-stage arithmetic intensity and percent of
peak on the roofline (HAN on DBLP, the paper's featured example).

Paper reference points (T4): FP/sgemm AI=26.8 FLOP/B (compute-bound,
ridge=9.37); NA/SpMMCsr AI=0.49 (3.9% peak); SA uEleWise AI=0.1, Reduce 0.34.
v5e ridge = 197e12/819e9 = 240 FLOP/B — all graph stages stay memory-bound
on TPU, only FP approaches the ridge.
"""
from __future__ import annotations

from benchmarks.common import emit
from benchmarks.hgnn_setup import build, stage_fns
from repro.core.characterize import analyze_hlo_text, peaks


def run() -> list:
    rows: list = []
    pk = peaks()  # the device's published peaks (CPU hosts: the v5e model)
    ridge = pk["flops"] / pk["hbm_bw"]
    cfg, m, params, batch = build("han", "dblp")
    fns = stage_fns(m, params, batch)
    for stage in ("FP", "NA", "SA"):
        fn, args = fns[stage]
        rep = analyze_hlo_text(fn.lower(*args).compile().as_text())
        fl, by = rep["total_flops"], max(rep["total_hbm_bytes"], 1.0)
        ai = fl / by
        # achievable fraction of peak at this AI on the v5e roofline
        frac = min(1.0, ai / ridge)
        t_est = max(fl / pk["flops"], by / pk["hbm_bw"])
        rows.append((f"fig4/han/dblp/{stage}", t_est * 1e6,
                     f"AI={ai:.2f}FLOP/B peak={100*frac:.1f}% "
                     f"bound={'compute' if ai > ridge else 'memory'}"))
    rows.append(("fig4/ridge", 0.0, f"v5e_ridge={ridge:.0f}FLOP/B_paper_T4=9.37"))
    return rows


if __name__ == "__main__":
    emit(run())
