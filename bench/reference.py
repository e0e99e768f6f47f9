"""Plain reference of the benchmark's models, and the check that decides
``correct``.

It imports nothing of the program.  It takes the benchmark's own graph,
features and weights, and from the program only the choice of neighbors
that a degree cap or a fan-out made (a random subset that the model's
definition leaves to the system).  That choice is first held to the graph
(:func:`validate_edges`): every kept edge is a real edge of the metapath or
relation, no edge is kept twice, and a row keeps ``min(degree, cap)`` of
them where the whole row was asked for.

Each model's forward follows its paper and lives in its module,
``bench/models/<model>.py``.  The forwards are plain ``jax.numpy`` in
float32 on the default device, as the configurations state, and one code
path runs in several precisions (:class:`Backend`): every matrix product
at ``highest`` (the reference), or lower for the controls.  On a TPU the
reference shares the chip's float32 elementwise arithmetic (exp, tanh,
division) with the program, so the comparison measures what the program
computes, not the platform's transcendentals.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

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


class Reference:
    """The jitted forward of one configuration in one precision, on inputs
    padded to few shapes: rows to a power of two (at least one pad row),
    each edge list to ``rows * cap`` with the extra edges wired into the
    last pad row, whose output nothing reads."""

    def __init__(self, model, cfg: Dict, w: Dict, precision: str):
        self.model, self.cfg = model, cfg
        self.w = jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.asarray(a, np.float32)), w)
        be = Backend(precision)
        self.fn = jax.jit(functools.partial(model.forward, be, cfg))

    @staticmethod
    def _rows(n: int, pad: bool) -> int:
        return 1 << int(n).bit_length() if pad else n

    @staticmethod
    def _table(x: np.ndarray, rows: int) -> np.ndarray:
        out = np.zeros((rows, x.shape[1]), np.float32)
        out[: len(x)] = x
        return out

    def __call__(self, xs: Dict[str, np.ndarray], edges, cap: int,
                 pad: bool) -> np.ndarray:
        """Logits of the real target rows; ``edges`` as the model's
        ``inputs`` returns them."""
        t = self.cfg["graph"]["target"]
        n = {k: self._rows(len(x), pad) for k, x in xs.items()}
        tables = {k: self._table(x, n[k]) for k, x in xs.items()}
        args = self.model.reference_args(self.cfg, xs, tables, n, edges, cap,
                                         pad)
        out = self.fn(self.w, *args)
        return np.asarray(out, np.float64)[: len(xs[t])]


def pad_edges(e: Edges, n_dst: int, length: int) -> Edges:
    """An edge list padded to ``length``, the extra edges wired into the
    last row ``n_dst - 1``."""
    d, s = (np.asarray(a, np.int32) for a in e)
    out_d = np.full(length, n_dst - 1, np.int32)
    out_s = np.zeros(length, np.int32)
    out_d[: len(d)], out_s[: len(s)] = d, s
    return out_d, out_s


# ---------------------------------------------------------------------------
# the program's neighbor choice, read from its batch and held to the graph
# ---------------------------------------------------------------------------

def padded_edges(nbr, mask, row_ids=None) -> Edges:
    nbr, mask = np.asarray(nbr), np.asarray(mask)
    rows, cols = np.nonzero(mask > 0)
    dst = rows if row_ids is None else np.asarray(row_ids)[rows]
    return dst.astype(np.int64), nbr[rows, cols].astype(np.int64)


def layout_edges(entry) -> Edges:
    """One metapath's or relation's edges from any of the program's NA
    layouts: padded ``(nbr [N, K], mask)``, csr ``(seg [E], idx [E])``, or
    degree buckets ``[(row_ids, nbr, mask), ...]``."""
    if isinstance(entry, list):
        parts = [padded_edges(n, m, r) for r, n, m in entry]
        return (np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]))
    a, b = entry
    if np.ndim(a) == 1:
        return np.asarray(a, np.int64), np.asarray(b, np.int64)
    return padded_edges(a, b)


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
