"""Pallas TPU kernel: hot-row feature-cache gather (residency fast path).

``repro.core.residency`` remaps every NA index table so references to hot
rows address a contiguous cache section appended to the source pool
(``pool = concat(table, table[hot])``, indices ``>= N``).  This kernel
serves those remapped gathers with the cache section pinned in VMEM:

* the ``[C, D]`` cache block has a constant index map, so the Pallas
  pipeline keeps it resident across every index tile (the same
  whole-table-resident idiom as ``segment_spmm``'s small-table path) —
  a hot reference never touches HBM again;
* cold references fall through to a plain XLA gather of the HBM table.

Bit-exactness: the cache rows are bitwise copies of table rows, the
in-kernel one-hot selection (``kernels/gather.py``, full f32 precision)
moves them unscaled and adds only zeros for the chunks a slot misses, and
the hot/cold merge is a ``where`` — so the result equals
``concat(table, table[hot])[idx]`` bit for bit (``ref.cached_gather``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import gather, streaming


def _kernel(slot_ref, cache_ref, out_ref, *, block_m: int):
    slot = slot_ref[...]  # [BN, 1] cache slot per index (-1 = cold)
    rows = gather.walk_chunks(  # [C, D] cache — VMEM-resident across tiles
        cache_ref, block_m,
        lambda acc, chunk, lo: acc + gather.gather_rows(chunk, slot - lo),
        jnp.zeros(out_ref.shape, jnp.float32))
    out_ref[...] = rows.astype(out_ref.dtype)  # cold rows zero; merged outside


def cached_gather(
    table: jax.Array,  # [N, D] source feature table (HBM)
    hot: jax.Array,  # [C] int32 hot row ids (the cache section's contents)
    idx: jax.Array,  # [...] int32 indices into the extended pool [0, N+C)
    block_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Gather from ``concat(table, table[hot])`` with the cache in VMEM."""
    n, d = table.shape
    c = hot.shape[0]
    block_m = streaming.chunk_rows(c)
    cache = streaming.pad_rows(
        jnp.take(table, hot.astype(jnp.int32), axis=0), block_m)  # fill
    c_pad = cache.shape[0]
    flat = idx.reshape(-1).astype(jnp.int32)
    m = flat.shape[0]
    pad = (-m) % block_n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    slot = jnp.where(flat >= n, flat - n, -1).reshape(-1, 1)
    hot_rows = pl.pallas_call(
        functools.partial(_kernel, block_m=block_m),
        grid=((m + pad) // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((c_pad, d), lambda i: (0, 0)),  # resident cache
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m + pad, d), table.dtype),
        compiler_params=streaming.resident_params(
            streaming.table_vmem_bytes(c_pad, d, table.dtype.itemsize)),
        interpret=interpret,
    )(slot, cache)[:m]
    flat = flat[:m]
    cold_rows = jnp.take(table, jnp.where(flat < n, flat, 0), axis=0)
    out = jnp.where((flat >= n)[:, None], hot_rows, cold_rows)
    return out.reshape(idx.shape + (d,))
