"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy:
  * ``use_pallas=False`` runs the pure-jnp reference (``kernels/ref.py``) —
    the XLA path, on any backend.
  * ``use_pallas=True`` runs the Pallas kernel: compiled on a TPU backend,
    in the interpreter with ``interpret=True`` (CPU tests).  Anywhere else
    it raises — a run that asked for the kernels never gets the reference
    in their place.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from repro.kernels import (
    decode_attention as _dec,
    feature_cache as _fc,
    flash_attention as _fa,
    fused_fp_na as _ffn,
    gat_na as _gat,
    ref,
    segment_spmm as _spmm,
    semantic_attn as _sem,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pallas(use_pallas: bool, interpret: bool) -> bool:
    """Does a wrapper run its Pallas kernel?  Raises for ``use_pallas=True``
    on a backend that cannot compile it without ``interpret=True``."""
    if not use_pallas:
        return False
    if interpret or _on_tpu():
        return True
    raise RuntimeError(
        "use_pallas=True needs a TPU backend or interpret=True; the "
        f"{jax.default_backend()!r} backend cannot compile Pallas TPU kernels")


@functools.partial(jax.jit, static_argnames=("mean", "use_pallas", "interpret"))
def segment_spmm(h_src, nbr, mask, mean: bool = True, use_pallas: bool = False,
                 interpret: bool = False):
    if _pallas(use_pallas, interpret):
        return _spmm.segment_spmm(h_src, nbr, mask, mean=mean, interpret=interpret)
    return ref.segment_spmm(h_src, nbr, mask, mean=mean)


@functools.partial(jax.jit, static_argnames=("mean", "use_pallas", "interpret"))
def fused_fp_na(x_src, w, nbr, mask, mean: bool = True, use_pallas: bool = False,
                interpret: bool = False):
    if _pallas(use_pallas, interpret):
        return _ffn.fused_fp_na(x_src, w, nbr, mask, mean=mean, interpret=interpret)
    return ref.fused_fp_na(x_src, w, nbr, mask, mean=mean)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def semantic_attention(z, w, b, q, use_pallas: bool = False, interpret: bool = False):
    if _pallas(use_pallas, interpret):
        return _sem.semantic_attention(z, w, b, q, interpret=interpret)
    return ref.semantic_attention(z, w, b, q)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "use_pallas", "interpret")
)
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    use_pallas: bool = False, interpret: bool = False):
    if _pallas(use_pallas, interpret):
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   interpret=interpret)
    return ref.mha_attention(q, k, v, causal=causal, window=window)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def decode_attention(q, k, v, kv_len, use_pallas: bool = False,
                     interpret: bool = False):
    if _pallas(use_pallas, interpret):
        return _dec.decode_attention(q, k, v, kv_len, interpret=interpret)
    return ref.decode_attention(q, k, v, kv_len)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def cached_gather(table, hot, idx, use_pallas: bool = False,
                  interpret: bool = False):
    """Hot-row cache gather (``repro.core.residency``): reads from the
    extended pool ``concat(table, table[hot])`` with the cache section
    VMEM-resident on the Pallas path (kernels/feature_cache.py).  Indices
    ``>= len(table)`` hit the cache; the rest gather from HBM."""
    if _pallas(use_pallas, interpret):
        return _fc.cached_gather(table, hot, idx, interpret=interpret)
    return ref.cached_gather(table, hot, idx)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def gat_aggregate(p: Dict, h_dst, h_src, nbr, mask, use_pallas: bool = False,
                  interpret: bool = False):
    """Fused multi-head GAT NA: SDDMM + segment-softmax + weighted reduce in
    ONE kernel launch for all heads (kernels/gat_na.py).

    Replaces the seed's split execution (edge scores in XLA re-gathering
    ``h_src[nbr]``, then one ``segment_spmm`` launch per head): the neighbor
    tile is gathered exactly once and every head rides the same gather.
    Large source tables stream from HBM instead of falling back to the ref.
    """
    if _pallas(use_pallas, interpret):
        return _gat.gat_na(p, h_dst, h_src, nbr, mask, interpret=interpret)
    return ref.gat_na(p, h_dst, h_src, nbr, mask)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def gat_aggregate_stacked(p_stacked: Dict, h_dst, h_src, nbr, mask,
                          use_pallas: bool = False, interpret: bool = False):
    """Stacked form: ``nbr/mask [P, N, K]``, params ``[P, H, Dh]`` — the whole
    metapath stack (HAN's inter-subgraph parallelism) is ONE kernel launch
    (the stack dim rides the Pallas grid), not P launches of H kernels."""
    if _pallas(use_pallas, interpret):
        return _gat.gat_na(p_stacked, h_dst, h_src, nbr, mask,
                           interpret=interpret)
    return ref.gat_na(p_stacked, h_dst, h_src, nbr, mask)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def gat_aggregate_stacked_fused_sa(p_stacked: Dict, h_dst, h_src, nbr, mask,
                                   sem: Dict, use_pallas: bool = False,
                                   interpret: bool = False):
    """Stacked GAT NA with the fused NA→SA epilogue (inter-stage reuse):
    the semantic-score pass-1 partial accumulates inside the NA kernel while
    each ``z`` tile is still in VMEM, so SA never re-reads the ``[P, N, D]``
    stack for its scores.  Returns ``(z [P, N, H, Dh] elu-activated, w [P])``.
    """
    if _pallas(use_pallas, interpret):
        return _gat.gat_na(p_stacked, h_dst, h_src, nbr, mask,
                           interpret=interpret, sem=sem)
    return ref.gat_na_fused_sa(p_stacked, h_dst, h_src, nbr, mask,
                               sem["W"], sem["b"], sem["q"])


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def semantic_combine(z, beta, use_pallas: bool = False,
                     interpret: bool = False):
    """SA pass 2 only (the fused-epilogue path's remaining work): weighted
    combine ``sum_p beta_p z_p`` — exactly one read of the stack."""
    if _pallas(use_pallas, interpret):
        return _sem.semantic_combine(z, beta, interpret=interpret)
    return jnp.einsum("p,pnd->nd", beta, z)
