"""Pallas TPU kernel: fused Feature Projection + Neighbor Aggregation.

Paper guideline (b): "a subgraph-level kernel fusion technique can be used to
fuse the execution of feature projection and neighbor aggregation for each
subgraph".  On GPU (fuseGNN) this keeps projected features in shared memory;
the TPU adaptation exploits aggregator linearity — aggregate *raw* features
(memory-bound gather/reduce on the VPU) and project the aggregate (compute-
bound MXU matmul) inside one kernel, so the projected table never round-trips
HBM and the memory-bound and compute-bound phases share one VMEM residency
(the paper's "kernel mixing" realized as fusion).

Blocking: grid over (row tile, feature tile).  Raw HGNN tables run big
(~5k x 3066 ~ 60 MB > VMEM), so the raw table has two paths like the other
NA kernels: **resident** (per-F-tile ``[M, BF]`` column slabs via BlockSpec)
when a slab fits VMEM, and **streaming** (table in HBM, scalar-prefetched
chunk schedule + double-buffered DMA of ``[BM, BF]`` sub-blocks) when it
does not — see ``kernels/streaming.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import gather, streaming
from repro.kernels.segment_spmm import _accumulate, _mean


def _write_partial(out_ref, part, fi):
    # accumulate partial products across feature-dim tiles
    @pl.when(fi == 0)
    def _init():
        out_ref[...] = part.astype(out_ref.dtype)

    @pl.when(fi != 0)
    def _acc():
        out_ref[...] = (out_ref[...] + part).astype(out_ref.dtype)


def _kernel(nbr_ref, mask_ref, x_ref, w_ref, out_ref, *, mean: bool,
            block_m: int):
    fi = pl.program_id(1)  # feature-dim tile index
    nbr = nbr_ref[...]
    mask = mask_ref[...]
    w = w_ref[...]  # [BF, D]
    acc = gather.walk_chunks(
        x_ref, block_m,
        lambda acc, chunk, lo: _accumulate(acc, nbr, mask, chunk, lo),
        jnp.zeros((nbr.shape[0], x_ref.shape[1]), jnp.float32))
    # MXU: fused projection of the aggregate
    part = gather.matmul(_mean(acc, mask, mean).astype(w.dtype), w)
    _write_partial(out_ref, part, fi)


def _stream_kernel(sched_ref, count_ref, nbr_ref, mask_ref, x_ref, w_ref,
                   out_ref, buf, sem, *, mean: bool, block_m: int,
                   block_f: int):
    t, fi = pl.program_id(0), pl.program_id(1)
    nc = count_ref[t]
    nbr = nbr_ref[...]
    mask = mask_ref[...]

    def get_dma(slot, s):
        c = sched_ref[t, s]
        return pltpu.make_async_copy(
            x_ref.at[pl.ds(c * block_m, block_m),
                     pl.ds(fi * block_f, block_f)],
            buf.at[slot], sem.at[slot])

    @pl.when(nc > 0)
    def _warmup():
        get_dma(0, 0).start()

    def body(s, acc):
        slot = jax.lax.rem(s, 2)

        @pl.when(s + 1 < nc)  # double buffer: next chunk in flight
        def _():
            get_dma(jax.lax.rem(s + 1, 2), s + 1).start()

        get_dma(slot, s).wait()
        lo = sched_ref[t, s] * block_m
        return _accumulate(acc, nbr, mask, buf[slot], lo)

    acc0 = jnp.zeros((nbr.shape[0], block_f), jnp.float32)
    acc = _mean(jax.lax.fori_loop(0, nc, body, acc0), mask, mean)
    w = w_ref[...]
    part = gather.matmul(acc.astype(w.dtype), w)
    _write_partial(out_ref, part, fi)


def fused_fp_na(
    x_src: jax.Array,  # [M, F]
    w: jax.Array,  # [F, D]
    nbr: jax.Array,  # [N, K]
    mask: jax.Array,  # [N, K]
    mean: bool = True,
    block_n: int = 128,
    block_f: int = 512,
    block_m: int = 0,  # 0 = auto (resident if an [M, BF] slab fits, else 512)
    vmem_budget: int = streaming.VMEM_TABLE_BUDGET,
    interpret: bool = False,
) -> jax.Array:
    n, k = nbr.shape
    m, f = x_src.shape
    d = w.shape[1]
    n_pad = (-n) % block_n
    f_pad = (-f) % block_f
    if n_pad:
        nbr = jnp.pad(nbr, ((0, n_pad), (0, 0)))
        mask = jnp.pad(mask, ((0, n_pad), (0, 0)))
    if f_pad:
        x_src = jnp.pad(x_src, ((0, 0), (0, f_pad)))
        w = jnp.pad(w, ((0, f_pad), (0, 0)))
    nbr = nbr.astype(jnp.int32)
    nf_blocks = (f + f_pad) // block_f
    grid = ((n + n_pad) // block_n, nf_blocks)
    out_shape = jax.ShapeDtypeStruct((n + n_pad, d), w.dtype)

    resident = block_m == 0 and streaming.table_fits_vmem(
        m, block_f, x_src.dtype.itemsize, vmem_budget)
    if resident:
        block_m = streaming.chunk_rows(m)
        x_src = streaming.pad_rows(x_src, block_m)
        m_pad = x_src.shape[0]
        out = pl.pallas_call(
            functools.partial(_kernel, mean=mean, block_m=block_m),
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_n, k), lambda i, fi: (i, 0)),
                pl.BlockSpec((block_n, k), lambda i, fi: (i, 0)),
                pl.BlockSpec((m_pad, block_f), lambda i, fi: (0, fi)),
                pl.BlockSpec((block_f, d), lambda i, fi: (fi, 0)),
            ],
            out_specs=pl.BlockSpec((block_n, d), lambda i, fi: (i, 0)),
            out_shape=out_shape,
            compiler_params=streaming.resident_params(
                streaming.table_vmem_bytes(m_pad, block_f,
                                           x_src.dtype.itemsize)),
            interpret=interpret,
        )(nbr, mask, x_src, w)
        return out[:n]

    if block_m == 0:
        block_m = streaming.CHUNK_ROWS
    block_m = min(block_m, max(m, 1))
    x_src = streaming.pad_rows(x_src, block_m)
    n_chunks = x_src.shape[0] // block_m
    sched, count = streaming.chunk_schedule(nbr, mask, block_n, n_chunks,
                                            block_m)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, k), lambda i, fi, *_: (i, 0)),
            pl.BlockSpec((block_n, k), lambda i, fi, *_: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # raw table stays in HBM
            pl.BlockSpec((block_f, d), lambda i, fi, *_: (fi, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i, fi, *_: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block_m, block_f), x_src.dtype),  # double buffer
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_stream_kernel, mean=mean, block_m=block_m,
                          block_f=block_f),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(sched, count, nbr, mask, x_src, w)
    return out[:n]
