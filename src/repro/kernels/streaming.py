"""HBM-streaming support for the Neighbor Aggregation kernels.

The NA kernels (``gat_na``, ``segment_spmm``, ``fused_fp_na``) gather rows of
a source feature table with data-dependent indices.  Small tables live whole
in VMEM (one BlockSpec, the pipeline keeps them resident across row tiles);
large tables cannot, and the seed code silently fell back to the XLA ref.

The streaming path lifts that limit.  The source table stays in HBM
(``memory_space=ANY``); the wrapper pre-computes, per destination row tile,
*which* ``block_m``-row chunks of the table its neighbor ids touch — the
**chunk schedule** — and passes it as a scalar-prefetch operand
(``pltpu.PrefetchScalarGridSpec``).  Inside the kernel a double-buffered
``pltpu.make_async_copy`` loop walks the schedule: the DMA for chunk ``s+1``
is in flight while chunk ``s`` is gathered/reduced, so HBM latency hides
behind the VPU reduction tree.  Chunks no neighbor touches are never fetched
— for power-law graphs most tiles touch a small fraction of the table.

Everything here is jit-traceable (static shapes only): the schedule is built
with one ``segment_max`` scatter + one sort, no host round-trip.

Scaling envelope: the schedule is ``[n_tiles, n_chunks]`` int32 and rides the
scalar-prefetch operand whole, so its footprint grows as
``(N / block_n) * (M / block_m)``.  That is fine for the HGNN working set
this repo targets (thousands of tiles x tens of chunks); for web-scale
tables the schedule itself outgrows SMEM and wants per-tile blocking
(``BlockSpec(..., memory_space=SMEM)`` rows instead of one prefetched
array) — tracked in ROADMAP.md under the real-TPU validation item.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

# Source tables whose VMEM footprint is at most this many bytes stay whole in
# VMEM (resident BlockSpec path); larger ones stream.  The footprint counts
# the lane padding of narrow rows (a 64-wide f32 row occupies 128 lanes).
# 16 MiB is v5e's default *scoped* VMEM limit, not its VMEM size (128 MiB
# per core): the pipeline double-buffers the resident block, so resident
# kernels raise the scoped limit to ``2 * budget + TILE_VMEM``
# (``resident_params``).
VMEM_TABLE_BUDGET = 8 * 1024 * 1024
# Scoped VMEM for everything a kernel holds besides its resident table:
# double-buffered row tiles, one-hot selection tiles, accumulators.
TILE_VMEM = 16 * 1024 * 1024
# Rows per one-hot selection chunk (kernels/gather.py): at block_n=128 the
# f32 selection tile is 256 KB.
CHUNK_ROWS = 512


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def table_vmem_bytes(m: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of an ``[m, cols]`` block: rows pad to 8 sublanes,
    columns to 128 lanes."""
    return _round_up(max(m, 1), 8) * _round_up(cols, 128) * itemsize


def table_fits_vmem(m: int, cols: int, itemsize: int,
                    budget: int = VMEM_TABLE_BUDGET) -> bool:
    """Static (trace-time) residency decision for an ``[m, cols]`` table."""
    return table_vmem_bytes(m, cols, itemsize) <= budget


def resident_params(table_bytes: int) -> pltpu.CompilerParams:
    """Scoped-VMEM limit for a kernel holding a double-buffered resident
    block of ``table_bytes`` (``table_vmem_bytes``) plus its tiles."""
    return pltpu.CompilerParams(vmem_limit_bytes=2 * table_bytes + TILE_VMEM)


def chunk_rows(m: int) -> int:
    """Chunk size for walking a resident ``m``-row table: ``CHUNK_ROWS``, or
    the whole table rounded up to a sublane multiple when it is smaller."""
    return min(CHUNK_ROWS, _round_up(max(m, 1), 8))


def chunk_schedule(
    nbr: jax.Array,  # [N, K] int32 (row-padded to a tile multiple)
    mask: jax.Array,  # [N, K] float; 0 = padded / absent edge
    block_n: int,
    n_chunks: int,
    block_m: int,
) -> Tuple[jax.Array, jax.Array]:
    """Per-tile chunk schedule: which source chunks each row tile touches.

    Returns ``(sched [T, C] int32, count [T] int32)`` where for tile ``t``
    the first ``count[t]`` entries of ``sched[t]`` are the touched chunk ids
    in ascending order (remaining entries are 0 and must not be read).
    """
    n = nbr.shape[0]
    n_tiles = n // block_n
    chunk = nbr.astype(jnp.int32) // block_m  # [N, K]
    valid = (mask != 0).astype(jnp.int32)
    tile = (jnp.arange(n, dtype=jnp.int32) // block_n)[:, None]  # [N, 1]
    flat = (tile * n_chunks + chunk).reshape(-1)
    touched = jax.ops.segment_max(
        valid.reshape(-1), flat, num_segments=n_tiles * n_chunks
    ).reshape(n_tiles, n_chunks) > 0
    # touched ids ascending, untouched pushed past the end via a sentinel
    key = jnp.where(touched, jnp.arange(n_chunks, dtype=jnp.int32)[None, :],
                    jnp.int32(n_chunks))
    sched = jnp.sort(key, axis=1)
    count = touched.sum(axis=1).astype(jnp.int32)
    sched = jnp.where(sched >= n_chunks, 0, sched).astype(jnp.int32)
    return sched, count


def pad_lanes(x: jax.Array) -> jax.Array:
    """Zero-pad the minor dim of an HBM array to a multiple of 128 lanes:
    Mosaic slices an HBM ref for a DMA only along whole lane tiles, so a
    64-wide table cannot be chunked as it is.  Kernels drop the pad columns
    after the copy."""
    pad = (-x.shape[-1]) % 128
    if pad:
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))
    return x


def pad_rows(x: jax.Array, multiple: int) -> jax.Array:
    """Zero-pad the leading dim of ``x`` up to a multiple (DMA chunks must be
    full-size; padded rows are never selected by the in-chunk mask)."""
    pad = (-x.shape[0]) % multiple
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x


class InflightWindow:
    """Bounded async-dispatch window for the stage-graph schedule.

    The host-side analogue of this module's double-buffered DMA loop: the
    kernel keeps the DMA for chunk ``s+1`` in flight while chunk ``s``
    reduces; the :meth:`~repro.core.pipeline.StageGraphExecutor.
    forward_overlapped` driver keeps up to ``depth`` *stage results* in
    flight on JAX's async dispatch stream while the host races ahead to
    issue dependents.  Admitting one more stage past the window blocks on
    the oldest (the DMA wait of slot ``s - depth``); ``depth <= 1`` is the
    serial schedule — every admit blocks immediately, which is the bit-exact
    parity baseline the tests pin.
    """

    def __init__(self, depth: int):
        self.depth = max(int(depth), 1)
        self._live: list = []
        self.admitted: list = []
        self.max_inflight = 0

    def admit(self, name: str, value):
        """Record ``value`` (a dispatched stage's output pytree) as in
        flight; blocks until the window has room for it."""
        self.admitted.append(name)
        if self.depth <= 1:
            jax.block_until_ready(value)
            self.max_inflight = max(self.max_inflight, 1)
            return value
        self._live.append(value)
        self.max_inflight = max(self.max_inflight, len(self._live))
        while len(self._live) > self.depth:
            jax.block_until_ready(self._live.pop(0))
        return value

    def drain(self) -> None:
        while self._live:
            jax.block_until_ready(self._live.pop(0))
