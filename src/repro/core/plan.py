"""Declarative stage plans — each HGNN model's execution as *data*.

The paper's central observation is that every HGNN is the same four-stage
pipeline (Subgraph Build → FP → NA → SA) with per-stage execution patterns
(DM / TB / EW / DR).  Before this module, each model class re-implemented
the same dispatch ladder (baseline CSR vs fused resident vs streaming vs
bucketed vs sharded vs pallas-vs-ref) inside its ``fp``/``na``/``sa``
methods.  A :class:`StagePlan` lifts all of those choices into a frozen
dataclass; one executor (:mod:`repro.core.pipeline`) interprets it.

Real HGNN deployments stack 2–3 of those FP→NA→SA rounds (the follow-up
training characterization, arXiv:2407.11790, measures how the stage mix
shifts with depth), so a :class:`StagePlan` is an *L-layer container*: a
tuple of :class:`LayerPlan`\\ s, each carrying its own FP/NA/SA specs plus
the **inter-layer handoff** — which per-type feature tables layer *l* must
materialize for layer *l+1*'s gathers.  The graph-side index tables
(padded/stacked/bucketed neighbor maps, degree buckets, instance LUTs,
partition halo maps) are layer-invariant and built once in ``prepare()``;
only features flow between layers.

Plan fields double as the sharding contract: ``batch_specs`` /
``param_specs`` are declarative (leaf-name, ndim) → logical-spec tables that
``launch/serve.py`` resolves into :class:`NamedSharding`s — no model-specific
branches in the serving layer either.

Layout / kind vocabulary (the executor's dispatch table):

====== =========== ==================================================
field  value       meaning
====== =========== ==================================================
na.kind   gat        multi-head GAT attention (HAN)
          mean       per-relation mean (RGCN)
          instance   metapath-instance attention (MAGNN)
          gcn        homogeneous 2-layer mean aggregation (GCN)
na.layout csr        DGL-faithful flat edge lists (baseline)
          stacked    padded ``[P, N, K]`` stack, one launch / metapath stack
          bucketed   degree-bucketed padded tiles (per metapath / relation;
                     MAGNN: instance tables binned by instance count)
          padded     padded ``[N, K]`` per relation (RGCN fused)
          instances  one ``[N, I, L]`` instance table per metapath (MAGNN)
sa.kind   attention  HAN-style semantic attention over the stack
          rel_sum    RGCN sum across relations + self loop
          none       single semantic — identity
====== =========== ==================================================
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

from repro.dist.sharding import BATCH, MODEL

# (leaf-or-container key, ndim, logical per-dim spec): a batch/param pytree
# leaf whose dict path contains `key` and whose rank is `ndim` gets the spec;
# everything else replicates.  Resolved by `launch/serve.py:hgnn_shardings`.
ShardRule = Tuple[str, int, Tuple]


@dataclass(frozen=True)
class FPSpec:
    """Stage 2 — Feature Projection (DM-Type dense matmul).

    ``kind`` values:
      per_type  dict of per-type projections (layer 0: raw feats → hidden;
                hidden layers: square re-projections of the carried tables)
      dense     single W on the target table (GCN's combination matmul;
                HAN hidden layers re-projecting the previous SA output)
      identity  no projection — hidden RGCN layers, where the per-relation
                ``w_rel`` / ``w_self`` matmuls inside NA/SA *are* the layer's
                linear transform
    """

    kind: str = "per_type"  # per_type | dense | identity
    sharded: bool = True  # stage-aware shard constraints (no-op off-mesh)
    heads: bool = False  # reshape the target type to [N, H, Dh]


@dataclass(frozen=True)
class NASpec:
    """Stage 3 — Neighbor Aggregation (TB-Type gather + EW attention math)."""

    kind: str  # gat | mean | instance | gcn
    layout: str  # csr | stacked | bucketed | padded | instances
    activation: Optional[str] = None  # elu | relu | None (post-aggregation)
    use_pallas: bool = False  # fused Pallas kernels on the hot loop


@dataclass(frozen=True)
class SASpec:
    """Stage 4 — Semantic Aggregation (EW/Reduce; DR concat in the baseline)."""

    kind: str  # attention | rel_sum | none
    stacked: bool = True  # concat-free [P, N, D] input vs per-metapath list
    # Fused NA→SA epilogue (paper guideline: inter-stage data reuse): the
    # semantic-score pass-1 partial accumulates inside the NA kernel while
    # each z tile is still in VMEM, eliminating one full [P, N, D] HBM read.
    # The executor honours it only on the stacked layout.
    fuse_epilogue: bool = False


@dataclass(frozen=True)
class HeadSpec:
    """Classifier head."""

    kind: str = "linear"  # linear (z @ W) | select_linear (z[target] @ W)
    target: Optional[str] = None  # node type for select_linear
    param: str = "cls"  # which parameter leaf holds the classifier matrix


@dataclass(frozen=True)
class PartitionSpec:
    """Graph-partitioned multi-host execution (``repro.dist.partition``).

    The vertex/feature tables are split into ``k`` edge-cut partitions
    (metapath-aware target assignment, reference-majority source assignment);
    FP and NA run per-partition on local shards, and the only communication
    is the explicit halo feature exchange (``gather_halo`` stage) between
    them.  ``k`` rides the leading array dim of every partitioned batch
    table and shards over the BATCH axes (``PARTITION_BATCH_SPECS``).
    """

    k: int  # number of graph partitions (>= 1; 1 = trivial, empty halos)
    # halo exchange implementation: "auto" = shard_map all-gather when the
    # mesh's BATCH axes divide k, flat gather otherwise; "xla" forces the
    # flat gather (GSPMD resolves the cross-shard traffic from constraints).
    halo: str = "auto"
    # Pad the per-type partition tables to assignment-independent capacities
    # (n_max = ceil(n_type / k) rows per partition, h_max = n_type halo
    # rows) instead of the data-dependent maxima.  The pad rows carry
    # own_mask = 0 and zero features, so they contribute nothing — outputs
    # stay bit-exact — but every sampled batch of the same ladder rung now
    # partitions to identical shapes, so the jitted serve forward never
    # re-traces.  Off by default: full-batch partitioned runs keep the
    # tight data-dependent shapes (and their committed bench records).
    static_shapes: bool = False


@dataclass(frozen=True)
class SampleSpec:
    """Request-path neighbor sampling (``repro.serve.sampler``).

    Serving traffic arrives as requests — "classify these target vertices" —
    not as a full-graph forward.  A plan that carries a ``SampleSpec``
    declares that its batches may be *sampled minibatches*: for a set of
    target vertices the sampler extracts the k-hop / per-metapath
    neighborhood, relabels it into the plan's own NA layout (stacked /
    bucketed / per-relation padded / instance tables), and pads the result
    to a rung of the shape ``ladder`` so the jitted executor never
    recompiles past warmup.

    ``fanout``    per-hop neighbor cap (per metapath / relation); the
                  effective padded width is ``min(fanout, cfg.max_degree)``
                  (``cfg.max_instances`` for MAGNN) and is shape-static.
    ``ladder``    tuple of ``(t_cap, f_cap)`` rungs, small→large: ``t_cap``
                  bounds the targets per batch (engine-side chunking),
                  ``f_cap`` the per-type local vertex tables (clamped to
                  the type's population at sample time).  A batch is padded
                  to the smallest rung that fits; overflow truncates the
                  frontier (farthest-first, counted), never the targets.
    ``seed``      sampler RNG seed — kept equal to ``cfg.seed`` so the
                  sampler's precomputed tables replay ``prepare()``'s exact
                  RNG stream (full fan-out ⇒ bit-exact vs full graph).
    """

    fanout: int
    ladder: Tuple[Tuple[int, int], ...]
    seed: int = 0


@dataclass(frozen=True)
class ResidencySpec:
    """Hot-feature residency (``repro.core.residency``).

    A plan that carries a ``ResidencySpec`` declares that every gather
    path consults a degree-ordered hot-row cache of ``cache_rows`` rows
    per node type: ``prepare()``'s finalize hook selects each type's
    top-``cache_rows`` rows by reference count (degree under the plan's
    own index tables — stacked/bucketed/padded/instances/edge-lists),
    materializes them as a contiguous cache section appended to the
    source pool, and remaps the neighbor tables so hot references read
    the cache section instead of re-gathering the scattered HBM rows.
    The partitioned arm additionally overlays hot halo rows from a
    partition-local cache so they skip the halo exchange, and the
    serving engine runs its per-step sampled frontier against a live
    :class:`~repro.core.residency.HotRowCache` with the in-flight
    targets pinned.

    HiHGNN-style inter-layer reuse falls out of the layer-invariant
    index tables: the hot set and remap are computed once, so layer
    *l*'s carried target table keeps the same rows resident and layer
    *l+1*'s NA gathers them from the cache section, never HBM.

    Bit-exact by construction — the cache holds bitwise row copies and
    the remap is a pure index substitution.
    """

    cache_rows: int  # hot rows kept resident per node type (>= 1)
    # serving: rows addressed by the in-flight slot batch are pinned and
    # never evicted while the step is outstanding
    pin_targets: bool = True


def default_sample_ladder(
    fanout: int, width: int, hops: int = 1,
    t_rungs: Tuple[int, ...] = (8, 32, 128),
) -> Tuple[Tuple[int, int], ...]:
    """Small automatic ``(t_cap, f_cap)`` ladder for a :class:`SampleSpec`.

    ``width`` is the model's nominal per-target per-hop frontier width
    (metapaths × padded degree for HAN, relations × degree for RGCN, ...);
    ``hops`` the expansion depth.  The ``f_cap`` sizing is a heuristic —
    the sampler clamps it to each type's population and truncates (counted)
    on overflow — while the *rung count* is what matters: one jit
    compilation per rung at warmup, zero after.
    """
    return tuple((t, t * (1 + max(width, 1) * max(hops, 1)))
                 for t in t_rungs)


@dataclass(frozen=True)
class LayerPlan:
    """One FP→NA→SA round of an L-layer stack.

    ``handoff`` names the inter-layer contract — which per-type feature
    tables this layer materializes for the next layer's gathers:

    ============== =======================================================
    handoff        carried state after this layer
    ============== =======================================================
    target         ``{target: z}`` — the metapath graphs are target→target
                   (HAN's stacked subgraphs, GCN's homogeneous graph), so
                   only the target table is ever gathered again
    all            the SA stage already returns every node type's updated
                   table (RGCN's rel_sum updates the whole graph)
    target+carry   the target row is updated from SA; the ``carry`` types
                   (MAGNN's non-target metapath positions) pass through
                   from this layer's FP output and are re-projected by the
                   next layer's FP
    ============== =======================================================
    """

    fp: FPSpec
    na: NASpec
    sa: SASpec
    handoff: str = "target"  # target | all | target+carry
    carry: Tuple[str, ...] = ()  # non-target types forwarded (target+carry)
    # Hot-feature residency for this layer's gathers (None = every gather
    # re-reads HBM).  Layer-uniform — the hot set is computed once from
    # the layer-invariant index tables (see StagePlan.__post_init__).
    residency: Optional[ResidencySpec] = None


@dataclass(frozen=True)
class StagePlan:
    """One model's whole execution, declared as data.

    ``layers`` is the L-layer stack (one :class:`LayerPlan` per FP→NA→SA
    round); the single-layer accessors ``plan.fp`` / ``plan.na`` /
    ``plan.sa`` read layer 0, which is exact for every layer-invariant
    field — NA kind/layout and the SA kind must be uniform across the
    stack (the host-side index tables are built once), and only FP varies
    per layer.  ``metapaths`` carries the static per-metapath node-type
    paths (HAN's subgraph count, MAGNN's per-position gather types) so the
    device batch holds arrays only.
    """

    model: str
    target: str  # target node type (classification rows)
    layers: Tuple[LayerPlan, ...]
    head: HeadSpec
    metapaths: Tuple[Tuple[str, ...], ...] = ()
    batch_specs: Tuple[ShardRule, ...] = ()
    param_specs: Tuple[ShardRule, ...] = (("fp", 2, (None, MODEL)),)
    # Graph-partitioned execution mode (None = single-table execution).
    partition: Optional[PartitionSpec] = None
    # Request-path sampled-minibatch mode (None = full-graph batches only).
    sample: Optional[SampleSpec] = None

    def __post_init__(self):
        if not self.layers:
            raise ValueError("a StagePlan needs at least one LayerPlan")
        lp0 = self.layers[0]
        for i, lp in enumerate(self.layers[1:], start=1):
            # full-spec equality, not just kind/layout: the executor
            # dispatches every layer on layer 0's NASpec/SASpec (activation,
            # use_pallas, fuse_epilogue, ...) and inits hidden FP dicts from
            # layer 0's carry, so a differing hidden spec would be silently
            # ignored rather than honoured
            if (lp.na != lp0.na or lp.sa != lp0.sa
                    or lp.residency != lp0.residency
                    or (lp.handoff, lp.carry) != (lp0.handoff, lp0.carry)):
                raise ValueError(
                    "NA/SA/residency specs and the handoff/carry contract "
                    "must be layer-uniform (the host-side index tables are "
                    "built once and the executor dispatches every layer on "
                    f"layer 0's specs); layer {i} declares "
                    f"{(lp.na, lp.sa, lp.residency, lp.handoff, lp.carry)} "
                    f"vs layer 0's "
                    f"{(lp0.na, lp0.sa, lp0.residency, lp0.handoff, lp0.carry)}")

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    # Layer-0 accessors: every pre-multi-layer read site (`plan.na.layout`,
    # `plan.sa.fuse_epilogue`, ...) keeps working, and stays correct for the
    # layer-invariant fields enforced by __post_init__.
    @property
    def fp(self) -> FPSpec:
        return self.layers[0].fp

    @property
    def na(self) -> NASpec:
        return self.layers[0].na

    @property
    def sa(self) -> SASpec:
        return self.layers[0].sa

    @property
    def residency(self) -> Optional[ResidencySpec]:
        return self.layers[0].residency

    @property
    def shards_on_mesh(self) -> bool:
        """CSR gather/scatter cannot shard; every padded layout can."""
        return self.na.layout != "csr"


# Shared batch-sharding rule sets (destination nodes over BATCH, source pools
# replicated — the stage-aware strategy of `stages.HGNN_STAGE_SPECS`).
STACKED_BATCH_SPECS: Tuple[ShardRule, ...] = (
    ("nbr", 3, (None, BATCH, None)),  # HAN [P, N, K]
    ("mask", 3, (None, BATCH, None)),
)
BUCKETED_BATCH_SPECS: Tuple[ShardRule, ...] = (
    ("buckets", 2, (BATCH, None)),  # per-bucket nbr / mask [n_b, K_b]
    ("buckets", 1, (BATCH,)),  # per-bucket row_ids
)
RELATION_BATCH_SPECS: Tuple[ShardRule, ...] = (
    ("rels", 2, (BATCH, None)),  # per-relation nbr / mask [N_d, K]
    ("rels", 1, (BATCH,)),  # per-relation bucket row_ids
)
INSTANCE_BATCH_SPECS: Tuple[ShardRule, ...] = (
    ("instances", 3, (BATCH, None, None)),  # [N, I, L] instance node tables
    ("instances", 2, (BATCH, None)),  # [N, I] instance masks
)
INSTANCE_BUCKET_BATCH_SPECS: Tuple[ShardRule, ...] = (
    ("buckets", 3, (BATCH, None, None)),  # per-bucket nodes [n_b, I_b, L]
) + BUCKETED_BATCH_SPECS  # masks [n_b, I_b], row_ids [n_b]
# Partitioned batches: every table under batch["part"] leads with the
# partition dim K, which shards over the BATCH axes (one partition — or a
# contiguous block of partitions — per data-parallel shard).  The 1-d
# leaves (the output inverse permutation) stay replicated.
PARTITION_BATCH_SPECS: Tuple[ShardRule, ...] = (
    ("part", 4, (BATCH, None, None, None)),  # [K, P, n, Kd] / [K, n, I, L]
    ("part", 3, (BATCH, None, None)),  # [K, n, F] feats / [K, n, Kd] rels
    ("part", 2, (BATCH, None)),  # [K, n] masks / [K, H] halo maps
)
