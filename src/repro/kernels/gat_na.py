"""Pallas TPU kernel: fused multi-head GAT Neighbor Aggregation.

The paper's NA stage is the dominant cost (74% of HGNN inference) and on GPU
decomposes into three kernels — SDDMM edge scores, segment-softmax, SpMM
weighted gather — re-reading the edge list and re-gathering source rows in
each.  The seed code mirrored that split: edge scores in XLA (one gather of
the source table), then one ``segment_spmm`` launch *per attention head*
(H more gathers).  This kernel collapses the whole stage into a single
launch per metapath stack:

  per ``[block_n, K]`` destination tile, for ALL heads at once:
    1. SDDMM   — ``e[n,k,h] = leaky_relu(a_dst·h_dst[n,h] + a_src·h_src[nbr])``
    2. softmax — masked segment-softmax over the K neighbor slots
    3. reduce  — K-step weighted reduction tree into ``[BN, H, Dh]``

Each gathered source row feeds both its edge score and its weighted
contribution.  The softmax is *online* (flash-attention style: running max /
denominator / rescaled accumulator, updated once per neighbor slot), so the
source table is consumed in chunks without a second pass.

Two execution paths share the same tile update:

* **resident** — the source table fits VMEM (one BlockSpec, kept across
  tiles by the Pallas pipeline).  This is the common case for HGNN latent
  tables (4k x 64 ~ 1 MB).
* **streaming** — the table stays in HBM; a scalar-prefetched chunk schedule
  (``pltpu.PrefetchScalarGridSpec``) drives double-buffered
  ``pltpu.make_async_copy`` DMAs, overlapping the fetch of chunk ``s+1``
  with the reduction over chunk ``s`` (see ``kernels/streaming.py``).

An optional leading stack dim ``S`` (HAN's per-metapath subgraphs, stacked
``[P, N, K]``) rides the grid, so the *entire* metapath stack is one
``pallas_call`` — no per-head and no per-metapath Python loop.

Layout note: features travel as 2-D ``[rows, H*Dh]`` tiles (lane-friendly)
and every per-head step stays 2-D, since Mosaic lowers 2-D contractions
only: the per-head dots ``a·h`` are one matmul against a block-diagonal
``[H*Dh, H]`` matrix, and per-head weights broadcast over their ``Dh``
lanes through a 0/1 ``[H, H*Dh]`` expansion matmul.  Source rows are
selected with the one-hot MXU gather of ``kernels/gather.py``, one
``block_m``-row chunk of the table at a time (resident tables are walked in
chunks too); ``mask`` is {0,1}-valued (GAT edge presence), matching
``ref.gat_na``.

**Fused NA→SA epilogue** (``sem=...``): the paper's inter-stage-reuse
guideline.  Semantic Aggregation's pass 1 (``w_p = mean_n q·tanh(z_p W + b)``,
see kernels/semantic_attn.py) re-reads the whole ``[P, N, D]`` NA output from
HBM.  With ``sem`` given, each output tile is activated (elu) and folded into
the per-subgraph score partial *while still in VMEM* — the kernel returns
``(z, w)`` and SA shrinks to a length-P softmax plus the weighted combine,
eliminating one full HBM pass over the stack.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import gather, streaming

_NEG = -1e9


def _expand(n_heads: int, hdh: int) -> jax.Array:
    """``[H, H*Dh]`` 0/1: ``x @ E`` repeats head ``h``'s value over its
    ``Dh`` lanes."""
    dh = hdh // n_heads
    col = jax.lax.broadcasted_iota(jnp.int32, (n_heads, hdh), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (n_heads, hdh), 0) * dh
    return ((col >= head) & (col < head + dh)).astype(jnp.float32)


def _tile_update(carry, nbr, mask, e_dst, a_src, hbuf, lo):
    """Online-softmax update of one destination tile against one source chunk.

    carry: (acc [BN, H*Dh], denom [BN, H], m_run [BN, H]) f32
    e_dst: [BN, H] destination half of the edge score
    a_src: [H*Dh, H] block-diagonal source attention vectors
    hbuf:  [BM, >=H*Dh] chunk of the source table whose global rows are
           ``[lo, lo+BM)``; each selected row feeds its score and its
           weighted contribution.
    """
    hdh = a_src.shape[0]
    hbuf = hbuf[:, :hdh]  # streamed chunks carry lane-pad columns
    bm = hbuf.shape[0]
    expand = _expand(e_dst.shape[1], hdh)
    sel = ((nbr >= lo) & (nbr < lo + bm) & (mask != 0)).astype(jnp.int32)
    loc = nbr - lo

    def slot(j, carry):  # one online-softmax step per neighbor slot
        acc, denom, m_run = carry
        rows = gather.gather_rows(hbuf, gather.column(loc, j))  # [BN, H*Dh]
        e = e_dst + gather.matmul(rows, a_src)  # [BN, H]
        e = jnp.where(e >= 0, e, 0.2 * e)  # leaky relu
        s = gather.column(sel, j) != 0
        e = jnp.where(s, e, _NEG)
        m_new = jnp.maximum(m_run, e)
        scale = jnp.exp(m_run - m_new)
        p_w = jnp.where(s, jnp.exp(e - m_new), 0.0)
        denom = denom * scale + p_w
        acc = (acc * gather.matmul(scale, expand)
               + gather.matmul(p_w, expand) * rows)
        return acc, denom, m_new

    return jax.lax.fori_loop(0, nbr.shape[1], slot, carry)


def _init_carry(bn: int, n_heads: int, hdh: int):
    return (jnp.zeros((bn, hdh), jnp.float32),
            jnp.zeros((bn, n_heads), jnp.float32),
            jnp.full((bn, n_heads), _NEG, jnp.float32))


def _finish(carry):
    acc, denom, _ = carry
    expand = _expand(denom.shape[1], acc.shape[1])
    return acc / gather.matmul(jnp.maximum(denom, 1e-9), expand)


def _write(out2d, out_ref):
    out_ref[...] = out2d.astype(out_ref.dtype)[None]


def _sa_epilogue(out2d, w_ref, b_ref, q_ref, out_ref, scores_ref,
                 block_n: int, n_valid: int):
    """Activate the tile and fold SA pass 1 into it while it sits in VMEM.

    Writes ``z = elu(out)`` and accumulates ``sum_n q·tanh(z W + b)`` for
    this subgraph into ``scores_ref`` (an ``[8, 128]`` broadcast of the
    partial, one tile-aligned block per subgraph) across the row-tile grid
    dim; rows past ``n_valid`` (the block_n pad) contribute nothing.
    """
    t = pl.program_id(1)
    # elu (NA activation); Mosaic has no expm1
    z = jnp.where(out2d > 0, out2d, jnp.exp(jnp.minimum(out2d, 0.0)) - 1.0)
    _write(z, out_ref)
    s = jnp.tanh(gather.matmul(z, w_ref[...])
                 + b_ref[...].astype(jnp.float32))  # [BN, Hs]
    part = jnp.sum(s * q_ref[...].astype(jnp.float32), axis=1,
                   keepdims=True)  # [BN, 1]
    rows = t * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (z.shape[0], 1), 0)
    part = jnp.sum(jnp.where(rows < n_valid, part, 0.0), axis=0,
                   keepdims=True)  # [1, 1]
    part = jnp.broadcast_to(part, scores_ref.shape)

    @pl.when(t == 0)
    def _init():
        scores_ref[...] = part

    @pl.when(t != 0)
    def _acc():
        scores_ref[...] = scores_ref[...] + part


def _resident_kernel(nbr_ref, mask_ref, hdst_ref, adst_ref, asrc_ref,
                     hsrc_ref, *rest, n_heads: int, block_m: int,
                     block_n: int = 0, n_valid: int = 0,
                     fuse_sa: bool = False):
    if fuse_sa:
        w_ref, b_ref, q_ref, out_ref, scores_ref = rest
    else:
        (out_ref,) = rest
    nbr = nbr_ref[0]
    mask = mask_ref[0]
    a_src = asrc_ref[0]
    e_dst = gather.matmul(hdst_ref[...], adst_ref[0])  # [BN, H]
    hdh = hdst_ref.shape[1]

    carry = gather.walk_chunks(
        hsrc_ref, block_m,
        lambda carry, chunk, lo: _tile_update(carry, nbr, mask, e_dst, a_src,
                                              chunk, lo),
        _init_carry(nbr.shape[0], n_heads, hdh))
    out2d = _finish(carry)
    if fuse_sa:
        _sa_epilogue(out2d, w_ref, b_ref, q_ref, out_ref, scores_ref,
                     block_n, n_valid)
    else:
        _write(out2d, out_ref)


def _streaming_kernel(sched_ref, count_ref, nbr_ref, mask_ref, hdst_ref,
                      adst_ref, asrc_ref, hsrc_ref, *rest,
                      n_heads: int, block_m: int, block_n: int = 0,
                      n_valid: int = 0, fuse_sa: bool = False):
    if fuse_sa:
        w_ref, b_ref, q_ref, out_ref, scores_ref, buf, sem = rest
    else:
        out_ref, buf, sem = rest
    st = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
    nc = count_ref[st]
    nbr = nbr_ref[0]
    mask = mask_ref[0]
    a_src = asrc_ref[0]
    e_dst = gather.matmul(hdst_ref[...], adst_ref[0])  # [BN, H]
    hdh = hdst_ref.shape[1]

    def get_dma(slot, s):
        c = sched_ref[st, s]
        return pltpu.make_async_copy(
            hsrc_ref.at[pl.ds(c * block_m, block_m), :], buf.at[slot],
            sem.at[slot])

    @pl.when(nc > 0)
    def _warmup():
        get_dma(0, 0).start()

    def body(s, carry):
        slot = jax.lax.rem(s, 2)

        @pl.when(s + 1 < nc)  # double buffer: next chunk in flight
        def _():
            get_dma(jax.lax.rem(s + 1, 2), s + 1).start()

        get_dma(slot, s).wait()
        lo = sched_ref[st, s] * block_m
        return _tile_update(carry, nbr, mask, e_dst, a_src, buf[slot], lo)

    carry = jax.lax.fori_loop(0, nc, body,
                              _init_carry(nbr.shape[0], n_heads, hdh))
    out2d = _finish(carry)
    if fuse_sa:
        _sa_epilogue(out2d, w_ref, b_ref, q_ref, out_ref, scores_ref,
                     block_n, n_valid)
    else:
        _write(out2d, out_ref)


def _normalize(p: Dict, h_dst, h_src, nbr, mask) -> Tuple:
    """Lift the unstacked call form to the stacked one (S=1)."""
    if nbr.ndim == 2:
        return ({k: v[None] for k, v in p.items()}, h_dst, h_src,
                nbr[None], mask[None], False)
    return p, h_dst, h_src, nbr, mask, True


def _attn_matrix(a: jax.Array) -> jax.Array:
    """``[S, H, Dh]`` attention vectors -> ``[S, H*Dh, H]`` block-diagonal
    matrices, so ``h2d @ A[s]`` is the per-head dot ``(h3 * a[s]).sum(-1)``."""
    s_dim, n_heads, dh = a.shape
    eye = jnp.eye(n_heads, dtype=jnp.float32)
    blocks = a.astype(jnp.float32)[:, :, :, None] * eye[None, :, None, :]
    return blocks.reshape(s_dim, n_heads * dh, n_heads)


def gat_na(
    p: Dict[str, jax.Array],  # a_dst/a_src [H, Dh] (or [S, H, Dh] stacked)
    h_dst: jax.Array,  # [N, H, Dh]
    h_src: jax.Array,  # [M, H, Dh]
    nbr: jax.Array,  # [N, K] int32 (or [S, N, K] stacked)
    mask: jax.Array,  # [N, K] {0,1} float (or [S, N, K])
    block_n: int = 128,
    block_m: int = 0,  # 0 = auto (resident if the table fits, else 512)
    vmem_budget: int = streaming.VMEM_TABLE_BUDGET,
    interpret: bool = False,
    sem=None,  # {"W" [H*Dh, Hs], "b" [Hs], "q" [Hs]}: fused NA→SA epilogue
) -> jax.Array:
    """Fused multi-head GAT NA; one launch per (stacked) subgraph batch.

    Returns ``[N, H, Dh]`` (``[S, N, H, Dh]`` for the stacked form).  With
    ``sem`` the output is elu-activated and the SA pass-1 score partial is
    accumulated in the same launch; returns ``(z, w [S])`` (``(z, w)``
    scalars for the unstacked form) where ``w_s = mean_n q·tanh(z_s W + b)``.
    """
    p, h_dst, h_src, nbr, mask, stacked = _normalize(p, h_dst, h_src, nbr, mask)
    s_dim, n, k = nbr.shape
    m, n_heads, dh = h_src.shape
    hdh = n_heads * dh
    h_dst2 = streaming.pad_rows(h_dst.reshape(-1, hdh), block_n)
    h_src2 = h_src.reshape(m, hdh)
    n_pad = (-n) % block_n
    if n_pad:
        nbr = jnp.pad(nbr, ((0, 0), (0, n_pad), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, n_pad), (0, 0)))
    nbr = nbr.astype(jnp.int32)
    n_tiles = (n + n_pad) // block_n
    a_dst = _attn_matrix(p["a_dst"])
    a_src = _attn_matrix(p["a_src"])

    itemsize = h_src2.dtype.itemsize
    resident = block_m == 0 and streaming.table_fits_vmem(
        m, hdh, itemsize, vmem_budget)
    fuse_sa = sem is not None
    extra_in: list = []
    if fuse_sa:
        hs = sem["W"].shape[1]
        extra_in = [sem["W"].astype(jnp.float32),
                    sem["b"].astype(jnp.float32)[None, :],
                    sem["q"].astype(jnp.float32)[None, :]]
    out_shape = jax.ShapeDtypeStruct((s_dim, n + n_pad, hdh), h_dst.dtype)
    out_spec = pl.BlockSpec((1, block_n, hdh), lambda s, t: (s, t, 0))
    if fuse_sa:
        # one (8, 128) tile per subgraph holds its broadcast score partial
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((s_dim * 8, 128), jnp.float32)]
        out_spec = [out_spec, pl.BlockSpec((8, 128), lambda s, t: (s, 0))]
    row_specs = [
        pl.BlockSpec((1, block_n, k), lambda s, t: (s, t, 0)),  # nbr
        pl.BlockSpec((1, block_n, k), lambda s, t: (s, t, 0)),  # mask
        pl.BlockSpec((block_n, hdh), lambda s, t: (t, 0)),      # h_dst
        pl.BlockSpec((1, hdh, n_heads), lambda s, t: (s, 0, 0)),  # a_dst
        pl.BlockSpec((1, hdh, n_heads), lambda s, t: (s, 0, 0)),  # a_src
    ]
    sem_specs = [
        pl.BlockSpec((hdh, hs), lambda s, t: (0, 0)),  # W
        pl.BlockSpec((1, hs), lambda s, t: (0, 0)),    # b
        pl.BlockSpec((1, hs), lambda s, t: (0, 0)),    # q
    ] if fuse_sa else []
    kern_kw = dict(n_heads=n_heads, fuse_sa=fuse_sa, block_n=block_n,
                   n_valid=n)

    if resident:
        block_m = streaming.chunk_rows(m)
        h_src2 = streaming.pad_rows(h_src2, block_m)
        m_pad = h_src2.shape[0]
        out = pl.pallas_call(
            functools.partial(_resident_kernel, block_m=block_m, **kern_kw),
            grid=(s_dim, n_tiles),
            in_specs=(row_specs
                      + [pl.BlockSpec((m_pad, hdh), lambda s, t: (0, 0))]
                      + sem_specs),
            out_specs=out_spec,
            out_shape=out_shape,
            compiler_params=streaming.resident_params(
                streaming.table_vmem_bytes(m_pad, hdh, itemsize)),
            interpret=interpret,
        )(nbr, mask, h_dst2, a_dst, a_src, h_src2, *extra_in)
    else:
        if block_m == 0:
            block_m = streaming.CHUNK_ROWS
        block_m = min(block_m, max(m, 1))
        h_src2 = streaming.pad_lanes(streaming.pad_rows(h_src2, block_m))
        n_chunks = h_src2.shape[0] // block_m
        sched, count = streaming.chunk_schedule(
            nbr.reshape(-1, k), mask.reshape(-1, k), block_n, n_chunks, block_m)

        def drop_sched(spec):
            """Lift a (s, t) index map over the scalar-prefetch operands."""
            return pl.BlockSpec(spec.block_shape,
                                lambda s, t, *_: spec.index_map(s, t))

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s_dim, n_tiles),
            in_specs=([drop_sched(sp) for sp in row_specs]
                      + [pl.BlockSpec(memory_space=pl.ANY)]  # h_src in HBM
                      + [drop_sched(sp) for sp in sem_specs]),
            out_specs=([drop_sched(sp) for sp in out_spec] if fuse_sa
                       else drop_sched(out_spec)),
            scratch_shapes=[
                pltpu.VMEM((2, block_m, h_src2.shape[1]), h_src2.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        )
        out = pl.pallas_call(
            functools.partial(_streaming_kernel, block_m=block_m, **kern_kw),
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(sched, count, nbr, mask, h_dst2, a_dst, a_src, h_src2, *extra_in)

    if fuse_sa:
        out, scores = out
        out = out[:, :n].reshape(s_dim, n, n_heads, dh)
        wp = scores[::8, 0] / n  # mean over (valid) nodes
        return (out, wp) if stacked else (out[0], wp[0])
    out = out[:, :n].reshape(s_dim, n, n_heads, dh)
    return out if stacked else out[0]
