"""Pallas TPU kernel: flash attention (GQA, causal, optional sliding window).

VMEM-tiled online-softmax attention for the LM substrate's prefill/train
path.  Grid = (batch, q_heads, q_blocks, kv_blocks); the kv axis is the
innermost ("arbitrary") dimension and accumulates into VMEM scratch
(m, l, acc), writing the output tile on the last kv step — the canonical
TPU flash structure.  GQA is folded into the BlockSpec index maps
(kv head = q head // group).

Block sizes default to (128, 512): q tile 128×Dh and kv tile 512×Dh keep the
working set (q + k + v + acc + scores ≈ 128·128·4·3 + 512·128·4·2 + 128·512·4
≈ 1 MB) well under the 16 MB v5e VMEM, and all matmul dims are 128-aligned
for the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, block_q: int, block_k: int, causal: bool, window: int, scale: float,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # skip fully-masked tiles (causal: kv entirely in the future;
    # window: kv entirely out of the sliding window)
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if window:
        run = jnp.logical_and(run, k_start + block_k - 1 >= q_start - window + 1)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # [BQ, Dh]
        k = k_ref[0, 0].astype(jnp.float32)  # [BK, Dh]
        v = v_ref[0, 0].astype(jnp.float32)  # [BK, Dh]
        s = q @ k.T  # [BQ, BK]
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, rows >= cols)
        if window:
            mask = jnp.logical_and(mask, rows - cols < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]  # [BQ, 1]
        m_cur = jnp.maximum(m_prev[:, 0], s.max(axis=-1))[:, None]
        alpha = jnp.exp(m_prev - m_cur)  # [BQ, 1]
        p = jnp.exp(s - m_cur)
        p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1)[:, None]
        acc_scr[...] = acc_scr[...] * alpha + p @ v
        m_scr[...] = m_cur

    @pl.when(ki == nk - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # [B, S, H, Dh]
    k: jax.Array,  # [B, S, KVH, Dh]
    v: jax.Array,  # [B, S, KVH, Dh]
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, s, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    # layout: [B, H, S, Dh] tiles
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    grid = (b, h, s // block_q, s // block_k)
    scale = 1.0 / (dh ** 0.5)
    out = pl.pallas_call(
        functools.partial(
            _kernel, block_q=block_q, block_k=block_k,
            causal=causal, window=window, scale=scale,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, dh), lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dh), lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
