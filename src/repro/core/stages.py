"""The paper's HGNN execution stages as composable JAX modules.

Stage 2 — Feature Projection (FP):   type-specific dense matmul (DM-Type).
Stage 3 — Neighbor Aggregation (NA): graph-topology gather + reduce (TB-Type)
                                     with element-wise attention math (EW-Type).
Stage 4 — Semantic Aggregation (SA): lives in :mod:`repro.core.semantics`.

Two NA execution paths:

* ``*_csr``  — DGL-faithful baseline: flat gather + ``segment_sum`` /
  ``segment_max`` over edge lists.  Lowers to gather/scatter HLO — the
  TB-Type irregular pattern the paper profiles (SpMMCsr / SDDMMCoo).
* ``*_padded`` — TPU-adapted optimized path: degree-capped dense ``[N, K]``
  neighbor tiles; the reduction tree becomes a masked dense reduction that
  feeds the MXU/VPU and tiles into VMEM (see kernels/segment_spmm.py for the
  Pallas version).

All functions are pure and jit-able; parameters are plain dict pytrees.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import BATCH, MODEL, shard

# Stage-aware partitioning strategy (the paper's taxonomy drives the specs):
#   FP  (DM-Type dense matmul)      -> hidden dim over MODEL, nodes over BATCH
#   NA  (TB-Type irregular gather)  -> destination nodes over BATCH; the
#                                      source pool replicated (arbitrary
#                                      gathers cannot stay sharded)
#   SA  (EW-Type elementwise+reduce)-> rides the NA layout, nodes over BATCH
# Every entry is a logical per-dim spec resolved by repro.dist.resolve_spec.
HGNN_STAGE_SPECS: Dict[str, Tuple] = {
    "fp_weight": (None, MODEL),          # [F_t, hidden]
    "fp_out": (BATCH, MODEL),            # [N_t, hidden]
    "na_dst": (BATCH, None, None),       # [N, H, Dh]
    "na_src": (None, None, None),        # [M, H, Dh] replicated gather pool
    "na_nbr": (BATCH, None),             # [N, K]  (also [N, I] instance masks)
    "na_out": (BATCH, None, None),       # [N, H, Dh]
    "na_inst_nodes": (BATCH, None, None),  # [N, I, L] MAGNN instance tables
    "na_flat_out": (BATCH, None),        # [N, D] flattened NA output
    "sa_stacked": (None, BATCH, None),   # [P, N, D]
}


# ---------------------------------------------------------------------------
# Stage 2: Feature Projection
# ---------------------------------------------------------------------------


def init_feature_projection(
    rng: jax.Array, feat_dims: Dict[str, int], hidden: int
) -> Dict[str, jax.Array]:
    keys = jax.random.split(rng, len(feat_dims))
    return {
        t: jax.random.normal(k, (d, hidden), jnp.float32) / np.sqrt(d)
        for k, (t, d) in zip(keys, sorted(feat_dims.items()))
    }


def feature_projection(
    params: Dict[str, jax.Array], feats: Dict[str, jax.Array]
) -> Dict[str, jax.Array]:
    """Project per-type raw features into the shared latent space (DM-Type)."""
    return {t: feats[t] @ params[t] for t in feats}


def feature_projection_sharded(
    params: Dict[str, jax.Array], feats: Dict[str, jax.Array]
) -> Dict[str, jax.Array]:
    """FP with the stage-aware partitioning: the dense DM-Type matmul is the
    one HGNN stage that shards like an LM layer — weights column-sharded over
    'model', per-type node rows over the batch axes.  No-op off-mesh."""
    return {
        t: shard(feats[t] @ shard(params[t], *HGNN_STAGE_SPECS["fp_weight"]),
                 *HGNN_STAGE_SPECS["fp_out"])
        for t in feats
    }


# ---------------------------------------------------------------------------
# Stage 3: Neighbor Aggregation
# ---------------------------------------------------------------------------


def init_gat(rng: jax.Array, n_heads: int, head_dim: int) -> Dict[str, jax.Array]:
    k1, k2 = jax.random.split(rng)
    s = 1.0 / np.sqrt(head_dim)
    return {
        "a_dst": jax.random.normal(k1, (n_heads, head_dim), jnp.float32) * s,
        "a_src": jax.random.normal(k2, (n_heads, head_dim), jnp.float32) * s,
    }


def _leaky_relu(x, slope=0.2):
    return jnp.where(x >= 0, x, slope * x)


def gat_aggregate_padded(
    p: Dict[str, jax.Array],
    h_dst: jax.Array,  # [N, H, Dh] projected features of target nodes
    h_src: Optional[jax.Array],  # [M, H, Dh] projected neighbor pool
    nbr: jax.Array,  # [N, K] int32 (may be None when hn/e_nbr pre-gathered)
    mask: jax.Array,  # [N, K] float
    hn: Optional[jax.Array] = None,  # [N, K, H, Dh] pre-gathered neighbors
    e_nbr: Optional[jax.Array] = None,  # [N, K, H] pre-gathered src scores
) -> jax.Array:
    """GAT neighbor aggregation over a padded subgraph. Returns [N, H, Dh].

    ``hn`` / ``e_nbr`` let the async schedule's split stages supply the two
    TB gathers pre-merged (owned rows gathered while the halo exchange was
    still in flight, where-selected against the halo rows afterwards) —
    pure row selections, so the attention math below is bitwise identical
    to the gather-from-``h_src`` default.
    """
    e_dst = (h_dst * p["a_dst"]).sum(-1)  # [N, H]   EW
    if hn is None:
        hn = h_src[nbr]  # [N, K, H, Dh]  TB gather
    if e_nbr is None:
        e_nbr = (h_src * p["a_src"]).sum(-1)[nbr]  # [M, H] EW -> [N, K, H]
    e = _leaky_relu(e_dst[:, None, :] + e_nbr)  # [N, K, H]
    e = jnp.where(mask[..., None] > 0, e, -1e9)
    e = e - jax.lax.stop_gradient(e.max(axis=1, keepdims=True))
    w = jnp.exp(e) * mask[..., None]
    alpha = w / jnp.maximum(w.sum(axis=1, keepdims=True), 1e-9)  # [N, K, H]
    out = jnp.einsum("nkh,nkhd->nhd", alpha, hn)  # reduction tree
    return out


def gat_aggregate_csr(
    p: Dict[str, jax.Array],
    h_dst: jax.Array,  # [N, H, Dh]
    h_src: jax.Array,  # [M, H, Dh]
    seg: jax.Array,  # [E] int32 destination (segment) id per edge
    idx: jax.Array,  # [E] int32 source id per edge
    n_nodes: int,
) -> jax.Array:
    """DGL-faithful GAT: SDDMM (edge scores) + segment-softmax + SpMM."""
    e_dst = (h_dst * p["a_dst"]).sum(-1)  # [N, H]
    e_src = (h_src * p["a_src"]).sum(-1)  # [M, H]
    e = _leaky_relu(e_dst[seg] + e_src[idx])  # [E, H]  SDDMM-like
    m = jax.ops.segment_max(e, seg, num_segments=n_nodes)  # scatter-max
    w = jnp.exp(e - jax.lax.stop_gradient(m[seg]))
    denom = jax.ops.segment_sum(w, seg, num_segments=n_nodes)
    alpha = w / jnp.maximum(denom[seg], 1e-9)  # [E, H]
    msg = h_src[idx] * alpha[..., None]  # [E, H, Dh]
    return jax.ops.segment_sum(msg, seg, num_segments=n_nodes)  # SpMM


def gat_aggregate_padded_sharded(
    p: Dict[str, jax.Array],
    h_dst: jax.Array,
    h_src: jax.Array,
    nbr: jax.Array,
    mask: jax.Array,
) -> jax.Array:
    """Padded NA with the stage-aware partitioning: destination nodes (and
    their neighbor lists) shard over BATCH; the source pool is replicated so
    the TB-Type gather stays local.  No-op off-mesh."""
    h_dst = shard(h_dst, *HGNN_STAGE_SPECS["na_dst"])
    h_src = shard(h_src, *HGNN_STAGE_SPECS["na_src"])
    nbr = shard(nbr, *HGNN_STAGE_SPECS["na_nbr"])
    mask = shard(mask, *HGNN_STAGE_SPECS["na_nbr"])
    out = gat_aggregate_padded(p, h_dst, h_src, nbr, mask)
    return shard(out, *HGNN_STAGE_SPECS["na_out"])


def gat_aggregate_padded_stacked(
    p_stacked: Dict[str, jax.Array],
    h: jax.Array,
    nbr: jax.Array,  # [P, N, K] stacked per-metapath subgraphs
    mask: jax.Array,
    agg_fn: Optional[Callable] = None,
    stacked_fn: Optional[Callable] = None,
    h_src: Optional[jax.Array] = None,
) -> jax.Array:
    """Inter-subgraph-parallel NA over stacked padded subgraphs with the
    stage-aware sharding applied at the stacked level (constraints sit
    outside the vmap): destination nodes over BATCH, source pool replicated,
    metapath dim unsharded.  ``agg_fn`` swaps the per-subgraph body (vmapped
    over the stack); ``stacked_fn`` consumes the whole ``[P, N, K]`` stack in
    one call — the fused Pallas GAT-NA kernel path, ONE launch per stack.
    ``h_src`` swaps the gather pool (default: the destination table itself;
    the residency arm passes the cache-extended pool)."""
    h_src = shard(h if h_src is None else h_src,
                  *HGNN_STAGE_SPECS["na_src"])
    nbr = shard(nbr, None, *HGNN_STAGE_SPECS["na_nbr"])
    mask = shard(mask, None, *HGNN_STAGE_SPECS["na_nbr"])
    if stacked_fn is not None:
        z = stacked_fn(p_stacked, h, h_src, nbr, mask)
    else:
        base = agg_fn or gat_aggregate_padded
        z = jax.vmap(lambda pp, nn, mm: base(pp, h, h_src, nn, mm),
                     in_axes=(0, 0, 0))(p_stacked, nbr, mask)
    return shard(z, None, *HGNN_STAGE_SPECS["na_out"])


def gat_aggregate_bucketed(
    p: Dict[str, jax.Array],
    h_dst: jax.Array,  # [N, H, Dh]
    h_src: jax.Array,  # [M, H, Dh]
    buckets,  # sequence of (row_ids [n_b], nbr [n_b, K_b], mask) device arrays
    agg_fn: Optional[Callable] = None,
) -> jax.Array:
    """GAT NA over a degree-bucketed layout (``core.metapath.bucket_padded``).

    Each bucket runs the padded aggregation at its own degree cap ``K_b``
    (2-3 dense launches instead of one ``K=max_degree`` launch whose
    reduction tree is mostly padding); outputs scatter back to node order
    through ``row_ids``.  ``agg_fn`` swaps in the fused Pallas kernel."""
    base = agg_fn or gat_aggregate_padded
    h_src = shard(h_src, *HGNN_STAGE_SPECS["na_src"])
    out = jnp.zeros(h_dst.shape, h_dst.dtype)
    for row_ids, nbr, mask in buckets:
        z = base(p, jnp.take(h_dst, row_ids, axis=0), h_src,
                 shard(nbr, *HGNN_STAGE_SPECS["na_nbr"]),
                 shard(mask, *HGNN_STAGE_SPECS["na_nbr"]))
        out = out.at[row_ids].set(z.astype(out.dtype))
    return shard(out, *HGNN_STAGE_SPECS["na_out"])


def mean_aggregate_padded(
    h_src: Optional[jax.Array], nbr: jax.Array, mask: jax.Array,
    hn: Optional[jax.Array] = None,
) -> jax.Array:
    """Mean NA (RGCN). h_src [M, D] -> [N, D].  ``hn`` supplies the gather
    pre-merged (async schedule's own/halo split) — same rows, same sum."""
    if hn is None:
        hn = h_src[nbr]  # [N, K, D]
    s = (hn * mask[..., None]).sum(axis=1)
    d = jnp.maximum(mask.sum(axis=1, keepdims=True), 1.0)
    return s / d


def gather_own(own: jax.Array, idx: jax.Array) -> jax.Array:
    """Owned-side half of a split own/halo gather (see :func:`gather_merge`).

    Indices pointing past the owned table clip to its last row; the merge
    discards those lanes in favour of the halo side, so the clip value
    never reaches the output.  Depends only on the owned table — the async
    schedule dispatches it while the halo exchange is still in flight.
    """
    return own[jnp.clip(idx, 0, own.shape[0] - 1)]


def gather_merge(
    own_sel: jax.Array,  # gather_own(own, idx)
    halo: jax.Array,  # [h_max, ...] exchanged halo rows (h_max may be 0)
    idx: jax.Array,  # indices into the virtual concat([own, halo]) table
    n_own: int,
) -> jax.Array:
    """Merge the split gather: ``concat([own, halo])[idx]`` as a where-select
    of the two clipped row selections.  Pure row copies — bitwise equal to
    gathering from the materialized concatenation."""
    if halo.shape[0] == 0:
        return own_sel
    halo_sel = halo[jnp.clip(idx - n_own, 0, halo.shape[0] - 1)]
    cond = (idx < n_own).reshape(idx.shape + (1,) * (halo.ndim - 1))
    return jnp.where(cond, own_sel, halo_sel)


def mean_aggregate_padded_sharded(
    h_src: jax.Array, nbr: jax.Array, mask: jax.Array,
    agg_fn: Optional[Callable] = None,
) -> jax.Array:
    """Mean NA (RGCN) with stage-aware sharding: destinations over BATCH,
    source pool replicated (``HGNN_STAGE_SPECS["na_src"]``; spec entries past
    ``h_src.ndim`` are ignored by ``resolve_spec``).  No-op off-mesh.
    ``agg_fn`` swaps in the Pallas ``segment_spmm`` kernel."""
    h_src = shard(h_src, *HGNN_STAGE_SPECS["na_src"])
    nbr = shard(nbr, *HGNN_STAGE_SPECS["na_nbr"])
    mask = shard(mask, *HGNN_STAGE_SPECS["na_nbr"])
    base = agg_fn or mean_aggregate_padded
    return shard(base(h_src, nbr, mask), BATCH, None)


def mean_aggregate_bucketed(
    h_src: jax.Array,  # [M, D]
    buckets,  # sequence of (row_ids [n_b], nbr [n_b, K_b], mask) device arrays
    n_rows: int,
    agg_fn: Optional[Callable] = None,
) -> jax.Array:
    """Mean NA over a degree-bucketed layout — `gat_aggregate_bucketed`'s
    dispatch with ``agg_fn=mean`` for RGCN's per-relation tables.

    Each bucket runs the padded mean at its own degree cap ``K_b`` and
    scatters back through ``row_ids``; ``agg_fn`` swaps in the Pallas
    ``segment_spmm`` kernel.  Stage-aware sharding as in the padded path:
    destinations over BATCH, source pool replicated (no-op off-mesh)."""
    base = agg_fn or mean_aggregate_padded
    h_src = shard(h_src, *HGNN_STAGE_SPECS["na_src"])
    out = jnp.zeros((n_rows, h_src.shape[-1]), h_src.dtype)
    for row_ids, nbr, mask in buckets:
        z = base(h_src,
                 shard(nbr, *HGNN_STAGE_SPECS["na_nbr"]),
                 shard(mask, *HGNN_STAGE_SPECS["na_nbr"]))
        out = out.at[row_ids].set(z.astype(out.dtype))
    return shard(out, *HGNN_STAGE_SPECS["na_flat_out"])


def mean_aggregate_csr(
    h_src: jax.Array, seg: jax.Array, idx: jax.Array, n_nodes: int
) -> jax.Array:
    s = jax.ops.segment_sum(h_src[idx], seg, num_segments=n_nodes)
    d = jax.ops.segment_sum(jnp.ones_like(seg, h_src.dtype), seg, num_segments=n_nodes)
    return s / jnp.maximum(d[:, None], 1.0)


def csr_to_edges(indptr: np.ndarray, indices: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side: CSR -> (segment_ids, indices) flat edge list."""
    degrees = np.diff(indptr)
    seg = np.repeat(np.arange(len(degrees), dtype=np.int32), degrees)
    return seg, indices.astype(np.int32)


# ---------------------------------------------------------------------------
# Instance aggregation (MAGNN intra-metapath)
# ---------------------------------------------------------------------------


def init_instance_attention(rng: jax.Array, n_heads: int, width: int):
    """MAGNN's intra-metapath attention: ``n_heads`` heads, each scoring
    the full-width target row and instance encoding (``a_dst``/``a_src``
    ``[H, width]``, the two halves of the paper's ``a_k``)."""
    return init_gat(rng, n_heads, width)


def instance_relations(metapaths) -> Tuple[Tuple[str, str], ...]:
    """The base relations of MAGNN's rotation encoder, in first-seen order.

    Walking each metapath from its far end toward the target, an edge type
    ``a -> b`` whose pair has not been seen becomes a base relation with a
    vector of its own; ``b -> a`` later rotates by that vector's conjugate.
    On IMDB's MDM and MAM the base relations are ``M -> D`` and ``M -> A``.
    """
    base: List[Tuple[str, str]] = []
    for path in metapaths:
        for j in range(len(path) - 2, -1, -1):
            a, b = path[j + 1], path[j]
            if (a, b) not in base and (b, a) not in base:
                base.append((a, b))
    return tuple(base)


def init_relations(rng: jax.Array, metapaths, width: int) -> Dict[str, jax.Array]:
    """One relation vector per base relation, ``[width/2, 2]`` (real and
    imaginary parts), keyed ``"a|b"``; the encoder normalizes each complex
    entry to unit modulus."""
    keys = [f"{a}|{b}" for a, b in instance_relations(metapaths)]
    ks = jax.random.split(rng, len(keys))
    return {key: jax.random.normal(k, (width // 2, 2), jnp.float32)
            for key, k in zip(keys, ks)}


def edge_rotation(rel: Dict[str, jax.Array], a: str, b: str) -> jax.Array:
    """``[D/2, 2]`` unit-modulus rotation of the edge ``a -> b``: the base
    relation's vector, or its conjugate for the reverse edge."""
    if f"{a}|{b}" in rel:
        r = rel[f"{a}|{b}"]
    else:
        r = rel[f"{b}|{a}"] * jnp.asarray([1.0, -1.0], jnp.float32)
    return r / jnp.sqrt((r * r).sum(-1, keepdims=True))


def rotate(x: jax.Array, r: jax.Array) -> jax.Array:
    """Complex product ``x ⊙ r``: ``x [..., D]`` read as ``D/2`` pairs
    (real, imaginary), ``r [D/2, 2]``."""
    c = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    re, im = c[..., 0], c[..., 1]
    rr, ri = r[..., 0], r[..., 1]
    return jnp.stack([re * rr - im * ri, re * ri + im * rr],
                     axis=-1).reshape(x.shape)


def position_rotations(rel: Dict[str, jax.Array], types) -> jax.Array:
    """``[L, D/2, 2]``: entry ``p`` turns path position ``p`` (``p`` edges
    from the target) into the target's frame, the product of the
    rotations of the edges between them (entry 0 is 1).  With the
    paper's indexing ``t_0 = u, ..., t_n = v`` this is ``prod_{j > i}
    r_j``, the closed form of the recursion ``o_i = h_{t_i} + o_{i-1} ⊙
    r_i``."""
    r0 = next(iter(rel.values()))
    out = [jnp.zeros_like(r0).at[:, 0].set(1.0)]
    for p in range(1, len(types)):
        r = edge_rotation(rel, types[p], types[p - 1])
        out.append(rotate(out[-1].reshape(-1), r).reshape(r.shape))
    return jnp.stack(out)


def position_tables(tables: Dict[str, jax.Array], rel: Dict[str, jax.Array],
                    types) -> List[jax.Array]:
    """Per path position, its type's projected table (``tables[type]``,
    rows on the second-to-last axis) rotated into the target's frame by
    :func:`position_rotations`; position 0, the target, as it is."""
    rot = position_rotations(rel, types)
    return [tables[types[0]]] + [rotate(tables[ty], r)
                                 for ty, r in zip(types[1:], rot[1:])]


def rotation_encoder(rotated: List[jax.Array]) -> jax.Array:
    """MAGNN's relational-rotation instance encoding (Fu et al. §4.2):
    ``h_P = (1/L) sum_p h_{t_p} ⊙ c_p`` from each position's rows already
    rotated by :func:`position_rotations` (``rotated[p]``, ``[..., D]``).
    Rotation is per row, so rotating a projected table before the gather
    rotates every row it serves: one pass over each small table instead of
    a complex product per instance.  Every attention head reads this one
    ``[..., D]`` encoding."""
    out = rotated[0]
    for x in rotated[1:]:
        out = out + x
    return out / len(rotated)


def instance_aggregate(
    p: Dict[str, jax.Array],
    h_tgt: jax.Array,  # [N, D] projected target rows
    enc: jax.Array,  # [N, I, D] encoded instances, shared by every head
    mask: jax.Array,  # [N, I]
) -> jax.Array:
    """Per-head attention over each target's instances -> ``[N, H, D]``:
    ``e_k = LeakyReLU(a_dst_k · h_v + a_src_k · h_P)`` softmaxed over the
    instances of v, each head a weighted sum of the full-width encodings.
    A row with no instance aggregates to zero."""
    # scores laid out [N, H, I]: the softmax runs along the instance axis
    e_t = h_tgt @ p["a_dst"].T  # [N, H]
    e_i = jnp.einsum("nid,hd->nhi", enc, p["a_src"])  # [N, H, I]
    e = _leaky_relu(e_t[:, :, None] + e_i)
    valid = mask[:, None, :] > 0
    e = jnp.where(valid, e, -1e9)
    e = e - jax.lax.stop_gradient(e.max(axis=2, keepdims=True))
    w = jnp.where(valid, jnp.exp(e), 0.0)
    alpha = w / jnp.maximum(w.sum(axis=2, keepdims=True), 1e-9)
    return jnp.einsum("nhi,nid->nhd", alpha, enc)
