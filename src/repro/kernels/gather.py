"""In-kernel row selection for the NA kernels, in a form Mosaic lowers.

Mosaic has no vector gather: ``jnp.take`` with a vector of row ids inside a
TPU kernel is refused ("Shape mismatch in input, indices and output").  The
NA kernels (``gat_na``, ``segment_spmm``, ``fused_fp_na``, ``cached_gather``)
therefore select rows on the MXU instead: a ``[BN, BM]`` one-hot (or
edge-weighted) selection tile times a ``[BM, D]`` chunk of the source table.

At ``MATMUL_PRECISION`` ("highest") a one-hot row times an f32 table
reproduces the selected row exactly (each f32 splits into bf16 parts that
the MXU multiplies by 1.0 without rounding), so ``gather_rows`` moves rows bit for bit.  A
weighted tile is the padded SpMM itself: one matmul per chunk instead of K
row gathers.

The work per destination tile grows with the chunk rows ``BM`` times the
number of chunks visited, not with the edge count alone — the price of a
gather-free formulation.  Callers walk the table in ``BM``-row chunks so the
selection tile stays a few hundred KB of VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Features, weights and kernels are f32, and so is every matmul of the HGNN
# forward: on TPU, XLA's default precision runs an f32 dot as a single bf16
# pass, which a float32 reference at f32 tolerance rejects.  The executor
# traces its forward under this precision; the kernels pass it explicitly.
MATMUL_PRECISION = "highest"


def matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """f32 MXU product at full precision.  The TPU default would round f32
    operands to one bf16 pass, which the float32 references would catch."""
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   precision=MATMUL_PRECISION,
                   preferred_element_type=jnp.float32)


def column(x: jax.Array, j) -> jax.Array:
    """Column ``j`` (static or traced) of a ``[BN, K]`` tile as ``[BN, 1]``,
    by a masked lane reduction — loops over neighbor slots stay rolled."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lane == j, x, jnp.zeros_like(x)), axis=1,
                   keepdims=True)


def _hits(loc: jax.Array, bm: int) -> jax.Array:
    """``[BN, 1]`` chunk-local row ids -> ``[BN, BM]`` bool selection; ids
    outside ``[0, BM)`` select nothing."""
    return loc == jax.lax.broadcasted_iota(jnp.int32, (loc.shape[0], bm), 1)


def gather_rows(table: jax.Array, loc: jax.Array) -> jax.Array:
    """``table[loc[:, 0]]`` as ``[BN, D]`` f32; ids outside the table give
    zero rows."""
    return matmul(_hits(loc, table.shape[0]).astype(jnp.float32), table)


def weighted_rows(table: jax.Array, loc: jax.Array, w: jax.Array) -> jax.Array:
    """``sum_j w[:, j] * table[loc[:, j]]`` as ``[BN, D]`` f32 for
    ``loc/w [BN, K]``; ids outside the table add nothing."""
    bm = table.shape[0]
    w = w.astype(jnp.float32)

    def slot(j, sel):
        return sel + jnp.where(_hits(column(loc, j), bm), column(w, j), 0.0)

    sel = jax.lax.fori_loop(0, loc.shape[1], slot,
                            jnp.zeros((loc.shape[0], bm), jnp.float32))
    return matmul(sel, table)


def walk_chunks(table_ref, block_m: int, body, init):
    """Fold ``body(acc, chunk, lo)`` over the ``block_m``-row chunks of a
    VMEM-resident table ref (``chunk`` holds global rows ``[lo, lo+BM)``)."""

    def step(c, acc):
        lo = pl.multiple_of(c * block_m, block_m)
        return body(acc, table_ref[pl.ds(lo, block_m), :], lo)

    return jax.lax.fori_loop(0, table_ref.shape[0] // block_m, step, init)
