"""Pallas TPU kernels: concat-free Semantic Aggregation.

The paper shows SA paying 17.5% of its time in DR-Type concat plus
memory-bound EW kernels (uEleWise 82.4% DRAM BW, Reduce 88.3%).  With the
stacked ``[P, N, D]`` layout the concat disappears; these two kernels fuse the
remaining chain so ``z`` is read from HBM exactly twice (once per pass)
instead of 4-5 times in the unfused chain:

  pass 1: w_p = mean_n( q · tanh(z_p,n W + b) )      (reduction tree -> [P])
  pass 2: out_n = sum_p softmax(w)_p * z_p,n          (weighted reduce)

The softmax over P (a length-P vector) happens on the host side of the two
calls — it is O(P) work.  Inside the kernels every contraction is a 2-D
matmul over one subgraph's ``[BN, D]`` slab (Mosaic lowers no 3-D
contraction), and the ``[P]`` score partials live in a ``[P, 128]``
lane-broadcast block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import gather, streaming


def _slab_scores(z, w, b, q, keep):
    """``sum_n q·tanh(z_n W + b)`` over the rows of one ``[BN, D]`` slab
    where ``keep [BN, 1]`` holds, broadcast over 128 lanes (``[1, 128]``)."""
    s = jnp.tanh(gather.matmul(z, w) + b)  # [BN, Hs]
    part = jnp.sum(s * q, axis=1, keepdims=True)  # [BN, 1]
    part = jnp.sum(jnp.where(keep, part, 0.0), axis=0, keepdims=True)
    return jnp.broadcast_to(part, (1, 128))


def _rows(n: int):
    return jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)


def _score_kernel(z_ref, w_ref, b_ref, q_ref, out_ref, *, block_n: int,
                  n_valid: int):
    """Partial semantic scores for one row tile: out [P, 128] += partials."""
    i = pl.program_id(0)
    w = w_ref[...].astype(jnp.float32)  # [D, Hs]
    b = b_ref[...].astype(jnp.float32)  # [1, Hs]
    q = q_ref[...].astype(jnp.float32)  # [1, Hs]

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)

    # pad rows would contribute tanh(b)·q each — mask them out
    keep = i * block_n + _rows(block_n) < n_valid
    for p in range(z_ref.shape[0]):
        out_ref[p:p + 1, :] += _slab_scores(z_ref[p], w, b, q, keep)


def _combine_kernel(z_ref, beta_ref, out_ref):
    beta = beta_ref[...].astype(jnp.float32)  # [1, P]
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for p in range(z_ref.shape[0]):
        acc = acc + beta[:, p:p + 1] * z_ref[p].astype(jnp.float32)
    out_ref[...] = acc.astype(out_ref.dtype)


def _score_stream_kernel(z_ref, w_ref, b_ref, q_ref, out_ref, buf, sem,
                         *, block_n: int, n: int, n_chunks: int):
    """Pass 1 over an HBM-resident ``z``: double-buffered chunk walk.

    Chunks are consecutive ``[P, block_n, D]`` row slices; the tail chunk is
    aligned to the array end (``off = n - block_n``) so no padded copy of
    ``z`` ever exists — rows a previous chunk already counted are masked out.
    """
    w = w_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    q = q_ref[...].astype(jnp.float32)
    d = w.shape[0]

    def off(s):
        return jnp.minimum(s * block_n, n - block_n)

    def dma(slot, s):
        return pltpu.make_async_copy(
            z_ref.at[:, pl.ds(off(s), block_n), :], buf.at[slot], sem.at[slot])

    dma(0, 0).start()
    out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)

    def body(s, carry):
        slot = jax.lax.rem(s, 2)

        @pl.when(s + 1 < n_chunks)  # next chunk in flight
        def _():
            dma(jax.lax.rem(s + 1, 2), s + 1).start()

        dma(slot, s).wait()
        # tail-overlap dedup: only rows at/after this chunk's logical start
        keep = _rows(block_n) >= s * block_n - off(s)
        for p in range(out_ref.shape[0]):
            zc = buf[slot, p][:, :d]  # [block_n, D] (lane pad dropped)
            out_ref[p:p + 1, :] += _slab_scores(zc, w, b, q, keep)
        return carry

    jax.lax.fori_loop(0, n_chunks, body, 0)


def semantic_scores(
    z: jax.Array, w: jax.Array, b: jax.Array, q: jax.Array,
    block_n: int = 512, interpret: bool = False,
    vmem_budget: int = streaming.VMEM_TABLE_BUDGET,
) -> jax.Array:
    p, n, d = z.shape
    hs = w.shape[1]
    block_n = min(block_n, n)
    oversized = n * p * d * z.dtype.itemsize > vmem_budget
    if oversized and n > block_n:
        # streaming split (as in the NA kernels): z stays in HBM, chunks ride
        # double-buffered DMAs, and — unlike the resident path — no
        # row-padded copy of the [P, N, D] stack is ever materialized (a
        # lane pad only where D is not a multiple of 128)
        n_chunks = -(-n // block_n)
        z = streaming.pad_lanes(z)
        out = pl.pallas_call(
            functools.partial(_score_stream_kernel, block_n=block_n, n=n,
                              n_chunks=n_chunks),
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),  # z stays in HBM
                pl.BlockSpec((d, hs), lambda i: (0, 0)),
                pl.BlockSpec((1, hs), lambda i: (0, 0)),
                pl.BlockSpec((1, hs), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((p, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((p, 128), jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((2, p, block_n, z.shape[2]), z.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
            interpret=interpret,
        )(z, w, b[None, :], q[None, :])
        return out[:, 0] / n
    n_pad = (-n) % block_n
    if n_pad:  # resident path: pad cost bounded by one tile
        z = jnp.pad(z, ((0, 0), (0, n_pad), (0, 0)))
    grid = ((n + n_pad) // block_n,)
    out = pl.pallas_call(
        functools.partial(_score_kernel, block_n=block_n, n_valid=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((p, block_n, d), lambda i: (0, i, 0)),
            pl.BlockSpec((d, hs), lambda i: (0, 0)),
            pl.BlockSpec((1, hs), lambda i: (0, 0)),
            pl.BlockSpec((1, hs), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((p, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((p, 128), jnp.float32),
        interpret=interpret,
    )(z, w, b[None, :], q[None, :])
    return out[:, 0] / n  # mean over nodes


def semantic_combine(
    z: jax.Array, beta: jax.Array, block_n: int = 512, interpret: bool = False
) -> jax.Array:
    p, n, d = z.shape
    n_pad = (-n) % block_n
    if n_pad:
        z = jnp.pad(z, ((0, 0), (0, n_pad), (0, 0)))
    grid = ((n + n_pad) // block_n,)
    out = pl.pallas_call(
        _combine_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((p, block_n, d), lambda i: (0, i, 0)),
            pl.BlockSpec((1, p), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + n_pad, d), z.dtype),
        interpret=interpret,
    )(z, beta[None, :].astype(jnp.float32))
    return out[:n]


def semantic_attention(
    z: jax.Array, w: jax.Array, b: jax.Array, q: jax.Array,
    block_n: int = 512, interpret: bool = False,
) -> jax.Array:
    wp = semantic_scores(z, w, b, q, block_n=block_n, interpret=interpret)
    beta = jax.nn.softmax(wp)
    return semantic_combine(z, beta, block_n=block_n, interpret=interpret)
