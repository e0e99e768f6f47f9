"""Plain reference of the benchmark's models, and the check that decides
``correct``.

It imports nothing of the program.  It takes the benchmark's own graph,
features and weights, and from the program only the choice of neighbors
that a degree cap or a fan-out made (a random subset that the model's
definition leaves to the system).  That choice is first held to the graph
(:func:`validate_edges`): every kept edge is a real edge of the metapath or
relation, no edge is kept twice, and a row keeps ``min(degree, cap)`` of
them where the whole row was asked for.

The forward passes follow the papers: HAN (arXiv:1903.07293) with
node-level GAT attention per metapath, ELU, semantic attention over the
metapaths and a linear head; R-GCN (arXiv:1703.06103) with a per-type
self weight, a mean over each relation's in-neighbors, ReLU, and a linear
head on the target type.  They are plain ``jax.numpy`` in float32 on the
default device, as the configurations state, and one code path runs in
several precisions (:class:`Backend`): every matrix product at ``highest``
(the reference), or lower for the controls.  On a TPU the reference shares the chip's float32 elementwise
arithmetic (exp, tanh, division) with the program, so the comparison
measures what the program computes, not the platform's transcendentals.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

Edges = Tuple[np.ndarray, np.ndarray]  # (dst row, src row), local ids


class Backend:
    """Matrix products at ``precision``: ``"highest"`` (float32 at full
    precision, the reference), ``"high"`` (hi*hi + hi*lo + lo*hi of the
    operands' bf16 halves, XLA's three-pass ``high``) or ``"bf16"`` (one
    pass of bf16-rounded operands, XLA's ``default`` on a TPU).  Products of
    bf16 values are exact in float32, so each pass runs at ``highest``
    with float32 accumulation; ``reduce_precision`` makes the halves, which
    the compiler cannot fold away as it can a pair of casts."""

    def __init__(self, precision: str):
        self.precision = precision

    def mm(self, a, b):
        dot = functools.partial(jnp.matmul,
                                precision=jax.lax.Precision.HIGHEST)
        if self.precision == "highest":
            return dot(a, b)
        bf16 = functools.partial(jax.lax.reduce_precision, exponent_bits=8,
                                 mantissa_bits=7)
        ah, bh = bf16(a), bf16(b)
        if self.precision == "bf16":
            return dot(ah, bh)
        return dot(ah, bh) + (dot(ah, bf16(b - bh)) + dot(bf16(a - ah), bh))

    def seg_sum(self, x, seg, n):
        return jax.ops.segment_sum(x, seg, num_segments=n)

    def seg_max(self, x, seg, n):
        return jax.ops.segment_max(x, seg, num_segments=n)


# ---------------------------------------------------------------------------
# the models
# ---------------------------------------------------------------------------

def _softmax_rows(be, e, dst, n):
    m = be.seg_max(e, dst, n)
    a = jnp.exp(e - m[dst])
    return a / be.seg_sum(a, dst, n)[dst]


def han(be, cfg: Dict, w: Dict, x, edges: Sequence[Edges], row_mask):
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


def rgcn(be, cfg: Dict, w: Dict, xs: Dict[str, np.ndarray],
         rels: Dict[Tuple[str, str, str], Edges]):
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


class Reference:
    """The jitted forward of one configuration in one precision, on inputs
    padded to few shapes: rows to a power of two (at least one pad row),
    each edge list to ``rows * cap`` with the extra edges wired into the
    last pad row, whose output nothing reads."""

    def __init__(self, cfg: Dict, w: Dict, precision: str):
        self.cfg = cfg
        self.w = jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.asarray(a, np.float32)), w)
        be = Backend(precision)
        model = han if cfg["model"] == "han" else rgcn
        self.fn = jax.jit(functools.partial(model, be, cfg))

    @staticmethod
    def _rows(n: int, pad: bool) -> int:
        return 1 << int(n).bit_length() if pad else n

    @staticmethod
    def _edges(e: Edges, n_dst: int, length: int) -> Edges:
        d, s = (np.asarray(a, np.int32) for a in e)
        out_d = np.full(length, n_dst - 1, np.int32)
        out_s = np.zeros(length, np.int32)
        out_d[: len(d)], out_s[: len(s)] = d, s
        return out_d, out_s

    @staticmethod
    def _table(x: np.ndarray, rows: int) -> np.ndarray:
        out = np.zeros((rows, x.shape[1]), np.float32)
        out[: len(x)] = x
        return out

    def __call__(self, xs: Dict[str, np.ndarray], edges, cap: int,
                 pad: bool) -> np.ndarray:
        """Logits of the real target rows.  HAN: ``edges`` is one list per
        metapath; R-GCN: ``{(s, r, d): edges}``."""
        t = self.cfg["graph"]["target"]
        n = {k: self._rows(len(x), pad) for k, x in xs.items()}
        tables = {k: self._table(x, n[k]) for k, x in xs.items()}
        if self.cfg["model"] == "han":
            mask = np.zeros(n[t], np.float32)
            mask[: len(xs[t])] = 1.0
            e = [self._edges(x, n[t], n[t] * cap if pad else len(x[0]))
                 for x in edges]
            out = self.fn(self.w, tables[t], e, mask)
        else:
            e = {k: self._edges(x, n[k[2]], n[k[2]] * cap if pad
                                else len(x[0])) for k, x in edges.items()}
            out = self.fn(self.w, tables, e)
        return np.asarray(out, np.float64)[: len(xs[t])]


# ---------------------------------------------------------------------------
# the program's neighbor choice, read from its batch and held to the graph
# ---------------------------------------------------------------------------

def _padded_edges(nbr, mask, row_ids=None) -> Edges:
    nbr, mask = np.asarray(nbr), np.asarray(mask)
    rows, cols = np.nonzero(mask > 0)
    dst = rows if row_ids is None else np.asarray(row_ids)[rows]
    return dst.astype(np.int64), nbr[rows, cols].astype(np.int64)


def layout_edges(entry) -> Edges:
    """One metapath's or relation's edges from any of the program's NA
    layouts: padded ``(nbr [N, K], mask)``, csr ``(seg [E], idx [E])``, or
    degree buckets ``[(row_ids, nbr, mask), ...]``."""
    if isinstance(entry, list):
        parts = [_padded_edges(n, m, r) for r, n, m in entry]
        return (np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]))
    a, b = entry
    if np.ndim(a) == 1:
        return np.asarray(a, np.int64), np.asarray(b, np.int64)
    return _padded_edges(a, b)


def han_edges(batch: Dict) -> List[Edges]:
    if "nbr" in batch:
        return [_padded_edges(n, m) for n, m in zip(np.asarray(batch["nbr"]),
                                                    np.asarray(batch["mask"]))]
    if "buckets" in batch:
        return [layout_edges([tuple(np.asarray(a) for a in b) for b in bks])
                for bks in batch["buckets"]]
    return [layout_edges(e) for e in batch["edges"]]


def rgcn_edges(batch: Dict) -> Dict[Tuple[str, str, str], Edges]:
    out = {}
    for key, entry in batch["rels"].items():
        if isinstance(entry, list):
            entry = [tuple(np.asarray(a) for a in b) for b in entry]
        out[tuple(key)] = layout_edges(entry)
    return out


class BadEdges(Exception):
    pass


def validate_edges(dst: np.ndarray, src: np.ndarray, n_real_dst: int,
                   n_real_src: int, loc_dst: np.ndarray, loc_src: np.ndarray,
                   adj: sp.csr_matrix, cap: int, full_rows: bool) -> Edges:
    """Hold one edge list, in local ids, to the graph's adjacency ``adj``
    (``[n_dst_global, n_src_global]``).  Edges out of pad rows are dropped
    (the program masks pad rows out); an edge into a pad row, an edge the
    graph lacks, a repeated edge, a row over ``cap``, or (``full_rows``) a
    row with fewer than ``min(degree, cap)`` edges raises."""
    keep = dst < n_real_dst
    dst, src = dst[keep], src[keep]
    if len(src) and (src.min() < 0 or src.max() >= n_real_src):
        raise BadEdges("an edge reads a pad row or an id out of range")
    gd, gs = loc_dst[dst], loc_src[src]
    if len(gd) and not np.all(np.asarray(adj[gd, gs]).ravel() > 0):
        raise BadEdges("an edge that the graph does not have")
    key = gd * adj.shape[1] + gs
    if len(np.unique(key)) != len(key):
        raise BadEdges("an edge kept twice")
    cnt = np.bincount(dst, minlength=n_real_dst)
    if cnt.max(initial=0) > cap:
        raise BadEdges(f"a row keeps more than {cap} neighbors")
    if full_rows:
        deg = np.diff(adj.indptr)[loc_dst[:n_real_dst]]
        if not np.array_equal(cnt, np.minimum(deg, cap)):
            raise BadEdges("a row keeps fewer than min(degree, cap) neighbors")
    return dst, src


def relative_error(got: np.ndarray, want: np.ndarray) -> float:
    """``max |got - want| / max |want|`` over every compared logit."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-30))
