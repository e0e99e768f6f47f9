"""Pallas TPU kernel: single-token decode attention over a long KV cache.

Flash-decoding structure: grid = (batch, kv_blocks); each step loads a KV
tile into VMEM, computes partial online-softmax statistics for ALL query
heads at once (GQA: [KVH, G] head layout so the einsum hits the MXU), and
accumulates in scratch.  The kv axis is "arbitrary" so scratch carries across
steps; output is written on the last step.

This is the serve_step hot kernel for decode_32k / long_500k shapes; the
sharded variant splits the kv axis over the 'model' mesh axis outside the
kernel (see serve/decode.py) and combines partials with the same online rule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, block_k: int, scale: float,
):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[0]
    k_start = ki * block_k

    @pl.when(k_start < kv_len)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale  # [H, Dh]
        k = k_ref[0].astype(jnp.float32)  # [BK, KVH, Dh]
        v = v_ref[0].astype(jnp.float32)  # [BK, KVH, Dh]
        h, dh = q.shape
        kvh = k.shape[1]
        g = h // kvh
        qg = q.reshape(kvh, g, dh)
        s = jnp.einsum("kgd,tkd->kgt", qg, k)  # [KVH, G, BK]
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(cols < kv_len, s, NEG_INF)
        m_prev = m_scr[...]  # [H, 1]
        m_cur = jnp.maximum(m_prev[:, 0], s.max(axis=-1).reshape(h))[:, None]
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur.reshape(kvh, g, 1))
        p = jnp.where(cols < kv_len, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1).reshape(h, 1)
        pv = jnp.einsum("kgt,tkd->kgd", p, v).reshape(h, dh)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_cur

    @pl.when(ki == nk - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,  # [B, H, Dh]
    k: jax.Array,  # [B, S, KVH, Dh]
    v: jax.Array,  # [B, S, KVH, Dh]
    kv_len,  # [B] int32 valid lengths (or scalar)
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, h, dh = q.shape
    s, kvh = k.shape[1], k.shape[2]
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))
    block_k = min(block_k, s)
    assert s % block_k == 0
    grid = (b, s // block_k)
    scale = 1.0 / (dh ** 0.5)
    out = pl.pallas_call(
        functools.partial(_kernel, block_k=block_k, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda bi, ki: (bi,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, h, dh), lambda bi, ki: (bi, 0, 0)),
            pl.BlockSpec((1, block_k, kvh, dh), lambda bi, ki: (bi, ki, 0, 0)),
            pl.BlockSpec((1, block_k, kvh, dh), lambda bi, ki: (bi, ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, dh), lambda bi, ki: (bi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(kv_len, q, k, v)
    return out
