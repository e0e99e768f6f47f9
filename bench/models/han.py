"""HAN (arXiv:1903.07293) in the benchmark: node-level GAT attention per
metapath, ELU, semantic attention over the metapaths and a linear head, on
the target type alone.  The functions are those that ``bench.harness.model``
lists for every model module."""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.graph import Graph, metapath_adjacency
from bench.reference import Edges
from bench.work import capped_edges


def program_kwargs(cfg: Dict) -> Dict:
    return {"n_heads": cfg["n_heads"], "attn_hidden": cfg["attn_hidden"]}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def weight_shapes(cfg: Dict) -> Dict:
    g, d = cfg["graph"], cfg["hidden"]
    p, heads = len(g["metapaths"]), cfg["n_heads"]
    out = {}
    for l in range(cfg["layers"]):
        if l > 0:
            out[f"{l}.fp"] = (d, d)
        out[f"{l}.gat_dst"] = (p, heads, d // heads)
        out[f"{l}.gat_src"] = (p, heads, d // heads)
        out[f"{l}.sem_W"] = (d, cfg["attn_hidden"])
        out[f"{l}.sem_b"] = (cfg["attn_hidden"],)
        out[f"{l}.sem_q"] = (cfg["attn_hidden"],)
    return out


def weight_scale(name: str, shape) -> float:
    """N(0, 1/fan_in), the attention vectors' fan-in their last axis; the
    semantic bias N(0, 0.01)."""
    return 0.1 if name.endswith("sem_b") else 1.0 / np.sqrt(
        shape[-1] if name.endswith(("gat_dst", "gat_src", "sem_q"))
        else shape[0])


def program_leaf(flat: Dict, layer: int, keys: List):
    head = keys[0]
    if head == "fp":
        return flat[f"{layer}.fp"]
    if head == "gat":
        if len(keys) == 2:  # stacked [P, H, Dh]
            return flat[f"{layer}.gat_{keys[1][2:]}"]
        return flat[f"{layer}.gat_{keys[2][2:]}"][keys[1]]
    if head == "sem":
        return flat[f"{layer}.sem_{keys[1]}"]
    raise KeyError(keys)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _softmax_rows(be, e, dst, n):
    m = be.seg_max(e, dst, n)
    a = jnp.exp(e - m[dst])
    return a / be.seg_sum(a, dst, n)[dst]


def forward(be, cfg: Dict, w: Dict, x, edges: Sequence[Edges], row_mask):
    """HAN logits ``[n, C]`` of the rows of ``x`` (target-type features),
    over one edge list per metapath; the semantic-attention mean runs over
    the rows where ``row_mask`` is 1."""
    n, d = x.shape[0], cfg["hidden"]
    heads = cfg["n_heads"]
    h = be.mm(x, w["fp"][cfg["graph"]["target"]])
    for l, lw in enumerate(w["layers"]):
        if l > 0:
            h = be.mm(h, lw["fp"])
        hh = h.reshape(n, heads, d // heads)
        zs = []
        for p, (dst, src) in enumerate(edges):
            e_dst = (hh * lw["gat_dst"][p]).sum(-1)  # [n, H]
            e_src = (hh * lw["gat_src"][p]).sum(-1)
            e = e_dst[dst] + e_src[src]
            e = jnp.where(e >= 0, e, 0.2 * e)  # leaky ReLU
            alpha = _softmax_rows(be, e, dst, n)  # [E, H]
            z = be.seg_sum(alpha[..., None] * hh[src], dst, n)
            z = jnp.where(z > 0, z, jnp.exp(jnp.minimum(z, 0)) - 1)  # ELU
            zs.append(z.reshape(n, d))
        z = jnp.stack(zs)  # [P, n, D]
        s = jnp.tanh(be.mm(z.reshape(-1, d), lw["sem_W"]) + lw["sem_b"])
        score = (s * lw["sem_q"]).sum(-1).reshape(len(zs), n)
        wp = (score * row_mask).sum(axis=1) / row_mask.sum()
        beta = jnp.exp(wp - wp.max())
        beta = beta / beta.sum()
        h = (beta[:, None, None] * z).sum(0)
    return be.mm(h, w["cls"])


def reference_args(cfg: Dict, xs: Dict, tables: Dict, n: Dict,
                   edges: List[Edges], cap: int, pad: bool):
    """The target table, one edge list per metapath (each padded to
    ``rows * cap``), and the mask of real rows."""
    t = cfg["graph"]["target"]
    mask = np.zeros(n[t], np.float32)
    mask[: len(xs[t])] = 1.0
    e = [reference.pad_edges(x, n[t], n[t] * cap if pad else len(x[0]))
         for x in edges]
    return tables[t], e, mask


# ---------------------------------------------------------------------------
# the program's neighbor choice, read from its batch and held to the graph
# ---------------------------------------------------------------------------

def batch_edges(batch: Dict) -> List[Edges]:
    if "nbr" in batch:
        return [reference.padded_edges(n, m)
                for n, m in zip(np.asarray(batch["nbr"]),
                                np.asarray(batch["mask"]))]
    if "buckets" in batch:
        return [reference.layout_edges(
                    [tuple(np.asarray(a) for a in b) for b in bks])
                for bks in batch["buckets"]]
    return [reference.layout_edges(e) for e in batch["edges"]]


def inputs(g: Graph, index: Dict, local: Dict, cap: int, full_rows: bool,
           adjacency):
    """The target table and one validated edge list per metapath (local
    ids) of one batch."""
    t = g.target
    n = len(local[t])
    edges = [reference.validate_edges(d, s, n, n, local[t], local[t],
                                      adjacency(metapath_adjacency, mp), cap,
                                      full_rows)
             for (d, s), mp in zip(batch_edges(index), g.metapaths)]
    return {t: g.feats[t][local[t]]}, edges


def row_cap(cfg: Dict, spec: Dict) -> int:
    return min(int(spec["fanout"]), cfg["max_degree"])


# ---------------------------------------------------------------------------
# counted work
# ---------------------------------------------------------------------------

def _weights_bytes(cfg: Dict, g: Graph) -> int:
    d, c, a = cfg["hidden"], cfg["n_classes"], cfg["attn_hidden"]
    n = g.feats[g.target].shape[1] * d + d * c
    n += cfg["layers"] * (2 * len(g.metapaths) * d + d * a + 2 * a)
    n += (cfg["layers"] - 1) * d * d
    return 4 * n


def work(cfg: Dict, g: Graph) -> Dict[str, float]:
    """FLOPs per layer (D hidden, H heads, A attention hidden, P
    metapaths, E kept edges of a metapath, n target rows, F its width):

      FP   2 n F D (the target table alone); 2 n D D at each later layer
      NA   per metapath: 4 n D (both attention scores) + 5 E H (score,
           leaky ReLU, exp, sum, divide) + 2 E D (weighted sum) + n D (ELU)
      SA   2 P n D A + 2 P n A (tanh(zW+b)) + 2 P n A (q) + 2 P n D (mix)
      head 2 n D C
    """
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
    bytes_ = (4.0 * n * f + _weights_bytes(cfg, g)
              + sum(4.0 * (e + n + 1) for e in edges) + 4.0 * n * c)
    return {"flops": flops, "bytes": bytes_, "edges": float(sum(edges)),
            "fp_flops": fp_flops, "feature_bytes": 4.0 * n * f}
