"""MAGNN (arXiv:2002.01680) in the benchmark: the movie-target layer of
§4 on every metapath instance.  Per type a linear map to D; each instance
``(t_0 = u, ..., t_n = v)`` of a metapath is encoded by relational rotation
(``o_0 = h_u``, ``o_i = h_{t_i} + o_{i-1} ⊙ r_i``, ``h_P = o_n / (n + 1)``,
features read as D/2 complex numbers, ``r_i`` a unit-modulus relation
vector per base relation, its conjugate for the reverse); H attention heads,
each D wide, score ``LeakyReLU(a_k^T [h_v ‖ h_P])`` and softmax it over the
instances of v; ELU; the heads concatenated (H D); semantic attention over
the metapaths; a linear head.  The functions are those that
``bench.harness.model`` lists for every model module.

Departures from the paper, each a choice the paper leaves open:

- the base relations are the configuration's relations as listed
  (``M -> D``, ``M -> A``), so an instance walked ``M -> D -> M`` rotates
  by ``r_MD`` and then by its conjugate;
- a target with no instance of a metapath gets a zero vector from it (the
  softmax of an empty set), and that zero row counts in the semantic mean;
- the bias-free linear head of the other models, and the release's
  Xavier-style draws replaced by the shared N(0, 1/fan_in) weights.

The reference enumerates every instance itself from the benchmark's own
adjacency (:func:`instances`); it takes from the program only the serving
path's sample, and holds the program's instance tables to the graph
(every consecutive pair an edge, position 0 the row's own target) through
the shared ``validate_edges``.  A program that caps, samples or drops
instances on the full-graph path therefore fails ``logit_rel_err``.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from bench import reference
from bench.graph import Graph

Instances = np.ndarray  # [E, L] int, position 0 the target row


def program_kwargs(cfg: Dict) -> Dict:
    return {"n_heads": cfg["n_heads"], "attn_hidden": cfg["attn_hidden"],
            "max_instances": cfg["max_instances"]}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _base_relations(cfg: Dict) -> List[str]:
    return [f"{s}|{d}" for s, _r, d, _n, _rev in cfg["graph"]["relations"]]


def weight_shapes(cfg: Dict) -> Dict:
    g, d = cfg["graph"], cfg["hidden"]
    p, heads = len(g["metapaths"]), cfg["n_heads"]
    out = {"cls": (heads * d, cfg["n_classes"])}
    types = sorted({t for mp in g["metapaths"] for t in mp})
    for l in range(cfg["layers"]):
        if l > 0:
            for t in types:
                out[f"{l}.fp.{t}"] = (heads * d if t == g["target"] else d,
                                      d)
        out[f"{l}.att_dst"] = (p, heads, d)
        out[f"{l}.att_src"] = (p, heads, d)
        for key in _base_relations(cfg):
            out[f"{l}.rot.{key}"] = (d // 2, 2)
        out[f"{l}.sem_W"] = (heads * d, cfg["attn_hidden"])
        out[f"{l}.sem_b"] = (cfg["attn_hidden"],)
        out[f"{l}.sem_q"] = (cfg["attn_hidden"],)
    return out


def weight_scale(name: str, shape) -> float:
    """N(0, 1/fan_in), the attention vectors' fan-in their last axis; the
    semantic bias N(0, 0.01); the relation vectors N(0, 1), whose scale
    the normalization to unit modulus removes."""
    if ".rot." in name:
        return 1.0
    if name.endswith("sem_b"):
        return 0.1
    return 1.0 / np.sqrt(shape[-1] if name.endswith(
        ("att_dst", "att_src", "sem_q")) else shape[0])


def program_leaf(flat: Dict, layer: int, keys: List):
    head = keys[0]
    if head == "fp":
        return flat[f"{layer}.fp.{keys[1]}"]
    if head == "att":
        return flat[f"{layer}.att_{keys[2][2:]}"][keys[1]]
    if head == "rel":
        return flat[f"{layer}.rot.{keys[1]}"]
    if head == "sem":
        return flat[f"{layer}.sem_{keys[1]}"]
    raise KeyError(keys)


# ---------------------------------------------------------------------------
# every instance, from the benchmark's adjacency
# ---------------------------------------------------------------------------

def instances(g: Graph, path: Sequence[str]) -> Instances:
    """Every instance of ``path`` from every target, ``[E, L]``, sorted by
    target: each partial instance extends by every neighbor of its last
    node, hop by hop."""
    rows = np.arange(g.counts[path[0]], dtype=np.int64)[:, None]
    for a, b in zip(path[:-1], path[1:]):
        adj = g.rel(a, b).tocsr()
        ends = rows[:, -1]
        starts = adj.indptr[ends]
        deg = adj.indptr[ends + 1] - starts
        which = np.repeat(np.arange(len(rows)), deg)
        within = np.arange(int(deg.sum())) - np.repeat(np.cumsum(deg) - deg,
                                                       deg)
        nxt = adj.indices[starts[which] + within]
        rows = np.concatenate([rows[which], nxt[:, None]], 1)
    return rows


def instance_counts(g: Graph, path: Sequence[str]) -> np.ndarray:
    """Instances per target, as a product of adjacency matrices."""
    v = np.ones(g.counts[path[-1]])
    for a, b in reversed(list(zip(path[:-1], path[1:]))):
        v = g.rel(a, b) @ v
    return v


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _softmax_rows(be, e, dst, n):
    m = be.seg_max(e, dst, n)
    a = jnp.exp(e - m[dst])
    return a / be.seg_sum(a, dst, n)[dst]


def _rotation(rot: Dict, a: str, b: str):
    """The unit-modulus rotation of the edge ``a -> b``: the base
    relation's vector, or its conjugate for the reverse edge."""
    if f"{a}|{b}" in rot:
        r = rot[f"{a}|{b}"]
    else:
        r = rot[f"{b}|{a}"] * jnp.asarray([1.0, -1.0], jnp.float32)
    return r / jnp.sqrt((r * r).sum(-1, keepdims=True))


def _rotate(x, r):
    c = x.reshape(x.shape[0], -1, 2)
    re, im = c[..., 0], c[..., 1]
    return jnp.stack([re * r[:, 0] - im * r[:, 1],
                      re * r[:, 1] + im * r[:, 0]], -1).reshape(x.shape)


def forward(be, cfg: Dict, w: Dict, xs: Dict, paths: Sequence[Instances],
            row_mask):
    """MAGNN logits ``[n, C]`` of the target rows of ``xs`` (every type's
    table), over one ``[E, L]`` instance table per metapath; the semantic
    mean runs over the rows where ``row_mask`` is 1."""
    g = cfg["graph"]
    t, d, heads = g["target"], cfg["hidden"], cfg["n_heads"]
    n = xs[t].shape[0]
    h = {ty: be.mm(xs[ty], w["fp"][ty]) for ty in xs}
    for l, lw in enumerate(w["layers"]):
        if l > 0:
            h = {ty: be.mm(h[ty], lw["fp"][ty]) for ty in lw["fp"]}
        zs = []
        for p, (path, inst) in enumerate(zip(g["metapaths"], paths)):
            dst = inst[:, 0]
            o = h[path[-1]][inst[:, -1]]
            for j in range(len(path) - 2, -1, -1):
                o = h[path[j]][inst[:, j]] + _rotate(
                    o, _rotation(lw["rot"], path[j + 1], path[j]))
            enc = o / len(path)  # [E, D]
            e = (be.mm(h[t], lw["att_dst"][p].T)[dst]
                 + be.mm(enc, lw["att_src"][p].T))  # [E, H]
            e = jnp.where(e >= 0, e, 0.2 * e)  # leaky ReLU
            alpha = _softmax_rows(be, e, dst, n)
            z = jnp.stack([be.seg_sum(alpha[:, k:k + 1] * enc, dst, n)
                           for k in range(heads)], 1)  # [n, H, D]
            z = jnp.where(z > 0, z, jnp.exp(jnp.minimum(z, 0)) - 1)  # ELU
            zs.append(z.reshape(n, heads * d))
        z = jnp.stack(zs)  # [P, n, H D]
        s = jnp.tanh(be.mm(z.reshape(-1, heads * d), lw["sem_W"])
                     + lw["sem_b"])
        score = (s * lw["sem_q"]).sum(-1).reshape(len(zs), n)
        wp = (score * row_mask).sum(axis=1) / row_mask.sum()
        beta = jnp.exp(wp - wp.max())
        beta = beta / beta.sum()
        h = {**h, t: (beta[:, None, None] * z).sum(0)}
    return be.mm(h[t], w["cls"])


def reference_args(cfg: Dict, xs: Dict, tables: Dict, n: Dict,
                   paths: List[Instances], cap: int, pad: bool):
    """Every type's table, one instance table per metapath (padded to
    ``rows * cap`` instances, or to a power of two where a whole graph's
    instances are more, the extra ones wired into the last target row),
    and the mask of real target rows."""
    t = cfg["graph"]["target"]
    mask = np.zeros(n[t], np.float32)
    mask[: len(xs[t])] = 1.0
    out = []
    for inst in paths:
        inst = np.asarray(inst, np.int32)
        if pad:
            length = n[t] * cap
            if len(inst) > length:
                length = 1 << (len(inst) - 1).bit_length()
            extra = np.zeros((length - len(inst), inst.shape[1]), np.int32)
            extra[:, 0] = n[t] - 1
            inst = np.concatenate([inst, extra])
        out.append(inst)
    return tables, out, mask


# ---------------------------------------------------------------------------
# the program's instance tables, read from its batch and held to the graph
# ---------------------------------------------------------------------------

def batch_instances(batch: Dict) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Per metapath ``(row, nodes [E, L])`` of every kept instance, from
    the ``instances`` or the instance-count ``buckets`` layout."""
    if "buckets" in batch:
        tables = [[tuple(np.asarray(a) for a in b) for b in bks]
                  for bks in batch["buckets"]]
    else:
        tables = [[(None, np.asarray(nd), np.asarray(m))]
                  for nd, m in batch["instances"]]
    out = []
    for bks in tables:
        rows, nodes = [], []
        for row_ids, nd, m in bks:
            r, i = np.nonzero(m > 0)
            rows.append(r if row_ids is None else row_ids[r])
            nodes.append(nd[r, i])
        out.append((np.concatenate(rows).astype(np.int64),
                    np.concatenate(nodes).astype(np.int64)))
    return out


def _rel(g: Graph, key) -> sp.csr_matrix:
    return g.rel(*key)


def inputs(g: Graph, index: Dict, local: Dict, cap: int, full_rows: bool,
           adjacency):
    """Every type's table and one instance table per metapath (local ids)
    of one batch.  The program's instances are held to the graph first; a
    full-graph batch is then compared against every instance of the graph
    (the reference's own), a served batch against the program's sample."""
    t = g.target
    kept = []
    for (row, nodes), path in zip(batch_instances(index), g.metapaths):
        real = row < len(local[t])
        row, nodes = row[real], nodes[real]
        if not np.array_equal(nodes[:, 0], row):
            raise reference.BadEdges("an instance in another target's row")
        for j, (a, b) in enumerate(zip(path[:-1], path[1:])):
            pairs = np.unique(nodes[:, j: j + 2], axis=0)
            reference.validate_edges(
                pairs[:, 0], pairs[:, 1], len(local[a]), len(local[b]),
                local[a], local[b], adjacency(_rel, (a, b)), len(local[b]),
                False)
        kept.append(nodes)
    if full_rows:
        kept = [adjacency(instances, mp) for mp in g.metapaths]
    return {ty: g.feats[ty][local[ty]] for ty in g.counts}, kept


def row_cap(cfg: Dict, spec: Dict) -> int:
    """Instances a served target keeps: the fan-out, under
    ``max_instances`` where that is set."""
    if cfg["max_instances"] >= 1:
        return min(int(spec["fanout"]), cfg["max_instances"])
    return int(spec["fanout"])


# ---------------------------------------------------------------------------
# counted work
# ---------------------------------------------------------------------------

def work(cfg: Dict, g: Graph) -> Dict[str, float]:
    """FLOPs per layer (D hidden, H heads, A attention hidden, P metapaths,
    E real instances of a metapath of L nodes, n target rows, F a type's
    width); padded slots never count:

      FP      2 n_t F_t D per type on a metapath; at later layers
              2 n_t W_t D (W_t = H D for the target, D otherwise)
      encode  per instance (L - 1) rotations of 3 D (complex product) plus
              D (add), and D (divide by L)
      attend  per metapath: 2 n D H (target scores) + 2 E D H (instance
              scores) + 5 E H (add, leaky ReLU, exp, sum, divide)
              + 2 E H D (weighted sum) + n H D (ELU)
      SA      2 P n (H D) A + 4 P n A + 2 P n H D
      head    2 n H D C

    Least bytes as :mod:`bench.work` defines them, with each instance's L
    int32 node ids in place of an edge's source id.
    """
    t = g.target
    n = g.counts[t]
    d, heads, a, c = (cfg["hidden"], cfg["n_heads"], cfg["attn_hidden"],
                      cfg["n_classes"])
    hd, p = heads * d, len(g.metapaths)
    types = sorted({ty for mp in g.metapaths for ty in mp})
    counts = [float(instance_counts(g, mp).sum()) for mp in g.metapaths]
    fp_flops = sum(2.0 * g.counts[ty] * g.feats[ty].shape[1] * d
                   for ty in types)
    flops = fp_flops + 2.0 * n * hd * c
    weights = sum(g.feats[ty].shape[1] * d for ty in types) + hd * c
    for l in range(cfg["layers"]):
        if l > 0:
            for ty in types:
                w_in = hd if ty == t else d
                flops += 2.0 * g.counts[ty] * w_in * d
                weights += w_in * d
        for e, mp in zip(counts, g.metapaths):
            flops += e * ((len(mp) - 1) * 4.0 * d + d)
            flops += (2.0 * n * d * heads + 2.0 * e * d * heads
                      + 5.0 * e * heads + 2.0 * e * heads * d + n * hd)
        flops += p * (2.0 * n * hd * a + 4.0 * n * a + 2.0 * n * hd)
        weights += (2 * p * heads * d + len(cfg["graph"]["relations"]) * d
                    + hd * a + 2 * a)
    feature_bytes = sum(4.0 * g.feats[ty].size for ty in types)
    bytes_ = (feature_bytes + 4.0 * weights
              + sum(4.0 * (e * len(mp) + n + 1)
                    for e, mp in zip(counts, g.metapaths))
              + 4.0 * n * c)
    return {"flops": flops, "bytes": bytes_, "edges": float(sum(counts)),
            "fp_flops": fp_flops, "feature_bytes": feature_bytes}
