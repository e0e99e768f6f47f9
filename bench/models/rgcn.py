"""R-GCN (arXiv:1703.06103) in the benchmark: a per-type self weight, a
mean over each relation's in-neighbors times that relation's weight, ReLU,
and a linear head on the target type.  The functions are those that
``bench.harness.model`` lists for every model module."""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.graph import Graph, in_adjacency
from bench.reference import Edges
from bench.work import capped_edges

Relation = Tuple[str, str, str]


def program_kwargs(cfg: Dict) -> Dict:
    return {}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _rel_keys(cfg: Dict) -> List[Relation]:
    keys = []
    for s, r, d, _n, rev in cfg["graph"]["relations"]:
        keys += [(s, r, d), (d, rev, s)]
    return sorted(keys)


def weight_shapes(cfg: Dict) -> Dict:
    d = cfg["hidden"]
    out = {}
    for l in range(cfg["layers"]):
        for key in _rel_keys(cfg):
            out[f"{l}.w_rel.{'|'.join(key)}"] = (d, d)
        for t in sorted(cfg["graph"]["counts"]):
            out[f"{l}.w_self.{t}"] = (d, d)
    return out


def weight_scale(name: str, shape) -> float:
    """N(0, 1/fan_in)."""
    return 1.0 / np.sqrt(shape[0])


def program_leaf(flat: Dict, layer: int, keys: List):
    head = keys[0]
    if head == "w_rel":
        return flat[f"{layer}.w_rel.{'|'.join(keys[1])}"]
    if head == "w_self":
        return flat[f"{layer}.w_self.{keys[1]}"]
    raise KeyError(keys)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def forward(be, cfg: Dict, w: Dict, xs: Dict[str, np.ndarray],
            rels: Dict[Relation, Edges]):
    """R-GCN logits ``[n_target, C]`` over per-type feature tables ``xs``
    and one in-edge list per relation ``(s, r, d)``."""
    n = {t: x.shape[0] for t, x in xs.items()}
    h = {t: be.mm(xs[t], w["fp"][t]) for t in xs}
    for lw in w["layers"]:
        acc = {t: 0.0 for t in h}
        for key in sorted(rels):
            s, _, d = key
            dst, src = rels[key]
            cnt = be.seg_sum(jnp.ones((len(dst), 1), h[s].dtype), dst, n[d])
            agg = be.seg_sum(h[s][src], dst, n[d]) / jnp.maximum(cnt, 1.0)
            acc[d] = acc[d] + be.mm(agg, lw["w_rel"]["|".join(key)])
        h = {t: jnp.maximum(be.mm(h[t], lw["w_self"][t]) + acc[t], 0.0)
             for t in h}
    return be.mm(h[cfg["graph"]["target"]], w["cls"])


def reference_args(cfg: Dict, xs: Dict, tables: Dict, n: Dict,
                   edges: Dict[Relation, Edges], cap: int, pad: bool):
    """Every type's table, and each relation's edge list padded to its
    destination's ``rows * cap``."""
    e = {k: reference.pad_edges(x, n[k[2]], n[k[2]] * cap if pad
                                else len(x[0])) for k, x in edges.items()}
    return tables, e


# ---------------------------------------------------------------------------
# the program's neighbor choice, read from its batch and held to the graph
# ---------------------------------------------------------------------------

def batch_edges(batch: Dict) -> Dict[Relation, Edges]:
    out = {}
    for key, entry in batch["rels"].items():
        if isinstance(entry, list):
            entry = [tuple(np.asarray(a) for a in b) for b in entry]
        out[tuple(key)] = reference.layout_edges(entry)
    return out


def inputs(g: Graph, index: Dict, local: Dict, cap: int, full_rows: bool,
           adjacency):
    """Every type's table and one validated in-edge list per relation
    (local ids) of one batch."""
    rels = {}
    for key, (d, s) in batch_edges(index).items():
        sk, _, dk = key
        rels[key] = reference.validate_edges(
            d, s, len(local[dk]), len(local[sk]), local[dk], local[sk],
            adjacency(in_adjacency, key), cap, full_rows)
    return {ty: g.feats[ty][local[ty]] for ty in g.counts}, rels


def row_cap(cfg: Dict, spec: Dict) -> int:
    return min(int(spec["fanout"]), cfg["max_degree"])


# ---------------------------------------------------------------------------
# counted work
# ---------------------------------------------------------------------------

def work(cfg: Dict, g: Graph) -> Dict[str, float]:
    """FLOPs (D hidden, E kept edges of a relation, n rows of a type, F its
    width); only the types whose rows reach the logits count, and the last
    layer updates the target type alone:

      FP        2 n F D per projected table
      relation  E D (sum) + n_d D (divide) + 2 n_d D D (W_r)
      update    per updated type: 2 n D D (W_0) + n D per relation into it
                + n D
      head      2 n_target D C
    """
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
