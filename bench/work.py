"""Counted work of one forward, and the chip's published peaks.

FLOPs and the least bytes a forward must move are reckoned from the
configuration's widths and the graph's real edges after the degree cap
(``min(degree, max_degree)`` per row), never from padded slots or compiled
HLO: the count is the same whatever layout or kernel runs, so removing
padding or fusing a stage raises the share and leaves the yardstick where
it was.  Only work that reaches the logits counts: HAN projects the target
type alone, and R-GCN's last layer updates the target type alone.

Least bytes: each input feature table read once, every weight read once,
each kept edge's int32 source id and each row's int32 offset read once,
the logits written once (float32 throughout).

FLOPs, per layer (D hidden, H heads, E kept edges, n rows):
  FP        2 n F D per projected table
  HAN NA    per metapath: 4 n D (both attention scores) + 5 E H (score,
            leaky ReLU, exp, sum, divide) + 2 E D (weighted sum) + n D (ELU)
  HAN SA    2 P n D A + 2 P n A (tanh(zW+b)) + 2 P n A (q) + 2 P n D (mix)
  R-GCN     per relation: E D (sum) + n_d D (divide) + 2 n_d D D (W_r);
            per updated type: 2 n D D (W_0) + n D per relation into it + n D
  head      2 n_target D C
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench.graph import Graph, in_adjacency, metapath_adjacency

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


def _han_weights_bytes(cfg: Dict, g: Graph) -> int:
    d, c, a = cfg["hidden"], cfg["n_classes"], cfg["attn_hidden"]
    n = g.feats[g.target].shape[1] * d + d * c
    n += cfg["layers"] * (2 * len(g.metapaths) * d + d * a + 2 * a)
    n += (cfg["layers"] - 1) * d * d
    return 4 * n


def han(cfg: Dict, g: Graph) -> Dict[str, float]:
    t = g.target
    n, f = g.feats[t].shape
    d, heads, a, c = (cfg["hidden"], cfg["n_heads"], cfg["attn_hidden"],
                      cfg["n_classes"])
    p = len(g.metapaths)
    edges = [capped_edges(np.diff(metapath_adjacency(g, mp).indptr),
                          cfg["max_degree"]) for mp in g.metapaths]
    fp_flops = 2.0 * n * f * d
    flops = fp_flops + 2.0 * n * d * c
    for l in range(cfg["layers"]):
        if l > 0:
            flops += 2.0 * n * d * d
        for e in edges:
            flops += 4.0 * n * d + 5.0 * e * heads + 2.0 * e * d + n * d
        flops += p * (2.0 * n * d * a + 4.0 * n * a + 2.0 * n * d)
    bytes_ = (4.0 * n * f + _han_weights_bytes(cfg, g)
              + sum(4.0 * (e + n + 1) for e in edges) + 4.0 * n * c)
    return {"flops": flops, "bytes": bytes_, "edges": float(sum(edges)),
            "fp_flops": fp_flops, "feature_bytes": 4.0 * n * f}


def rgcn(cfg: Dict, g: Graph) -> Dict[str, float]:
    d, c, cap = cfg["hidden"], cfg["n_classes"], cfg["max_degree"]
    keys = sorted(g.relations)
    edges = {k: capped_edges(np.diff(in_adjacency(g, k).indptr), cap)
             for k in keys}
    # types each layer must update, from the head back to the input
    need = [set() for _ in range(cfg["layers"])]
    need[-1] = {g.target}
    for l in range(cfg["layers"] - 1, 0, -1):
        need[l - 1] = need[l] | {s for s, _, dd in keys if dd in need[l]}
    inputs = need[0] | {s for s, _, dd in keys if dd in need[0]}
    fp_flops = sum(2.0 * g.counts[t] * g.feats[t].shape[1] * d
                   for t in inputs)
    flops = fp_flops
    weights = sum(g.feats[t].shape[1] * d for t in inputs) + d * c
    rels_read = set()
    for l in range(cfg["layers"]):
        for t in need[l]:
            rels = [k for k in keys if k[2] == t]
            rels_read.update(rels)
            weights += (len(rels) + 1) * d * d
            flops += (2.0 * g.counts[t] * d * d
                      + (len(rels) + 1) * g.counts[t] * d)
            for k in rels:
                flops += (edges[k] * d + g.counts[t] * d
                          + 2.0 * g.counts[t] * d * d)
    used_edges = sum(edges[k] for k in rels_read)
    flops += 2.0 * g.counts[g.target] * d * c
    bytes_ = (sum(4.0 * g.feats[t].size for t in inputs) + 4.0 * weights
              + sum(4.0 * (edges[k] + g.counts[k[2]] + 1) for k in rels_read)
              + 4.0 * g.counts[g.target] * c)
    return {"flops": flops, "bytes": bytes_, "edges": float(used_edges),
            "fp_flops": fp_flops,
            "feature_bytes": sum(4.0 * g.feats[t].size for t in inputs)}


def forward(cfg: Dict, g: Graph) -> Dict[str, float]:
    """``{"flops", "bytes", "edges"}`` of one full-graph forward."""
    return han(cfg, g) if cfg["model"] == "han" else rgcn(cfg, g)


def least_time_s(work: Dict[str, float], pk: Dict[str, float]) -> Dict:
    """The larger of FLOPs over peak FLOP/s and bytes over peak bandwidth,
    with the bound that sets it."""
    compute = work["flops"] / pk["flops"]
    memory = work["bytes"] / pk["hbm_bytes_per_s"]
    return {"seconds": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
