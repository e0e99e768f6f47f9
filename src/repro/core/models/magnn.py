"""MAGNN — Metapath Aggregated GNN (Fu et al., WWW'20).

Stages (paper Table 1): Metapath Walk | Linear | GAT | Attention Sum.
Unlike HAN, Neighbor Aggregation operates on metapath *instances*: every
instance is encoded from the projected features of ALL nodes along the path
by the relational-rotation encoder (one learned relation vector per base
relation, its conjugate for the reverse), then ``n_heads`` attention heads,
each ``hidden`` wide, aggregate the instances per target; the heads are
concatenated (``n_heads * hidden``) into semantic attention and the head.

Instance enumeration on the full-graph path keeps every instance with
``max_instances=0`` (as published), or a per-target sample of
``max_instances`` (the reservoir of core/metapath.py: full enumeration
explodes through hub nodes such as DBLP's 20 venues).  Every instance is
binned by instance count (``bucket_padded``, :func:`instance_buckets`)
instead of one ``[N, I_max, L]`` pad, whose slots are mostly padding on a
heavy-tailed graph.  Sampled serving keeps its per-target cap whatever
the full-graph path does.

Execution is declared as a :class:`StagePlan` with NA layout ``instances``
(or ``bucketed``); the per-position node types are static and ride the
plan (``metapaths``), so the device batch holds arrays only and the
instance tables shard over the stage-aware destination-node BATCH axes.
"""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from repro.configs.base import HGNNConfig
from repro.core import metapath as mp
from repro.core.hgraph import HeteroGraph
from repro.core.pipeline import PlannedModel
from repro.core.plan import (INSTANCE_BATCH_SPECS,
                             INSTANCE_BUCKET_BATCH_SPECS,
                             PARTITION_BATCH_SPECS, FPSpec, HeadSpec,
                             LayerPlan, NASpec, PartitionSpec, ResidencySpec,
                             SampleSpec, SASpec, ScheduleSpec, StagePlan,
                             default_sample_ladder)
from repro.data.synthetic import DATASET_METAPATHS, DATASET_TARGET
from repro.serve.spans import span


class MAGNN(PlannedModel):
    def __init__(self, cfg: HGNNConfig):
        super().__init__(cfg)
        self.metapaths = DATASET_METAPATHS[cfg.dataset]
        self.target = DATASET_TARGET[cfg.dataset]

    def plan(self) -> StagePlan:
        cfg = self.cfg
        layout = "bucketed" if instance_buckets(cfg) > 1 else "instances"
        part = None
        if cfg.partitions >= 1:
            if layout != "instances":
                raise ValueError(
                    "partitioned MAGNN execution needs the instances layout "
                    f"(no degree buckets); got layout={layout!r}")
            part = PartitionSpec(k=cfg.partitions)
        na = NASpec(kind="instance", layout=layout, activation="elu",
                    use_pallas=cfg.use_pallas)
        sa = SASpec(kind="attention", stacked=False)
        # instance gathers touch every metapath position's type, so hidden
        # layers carry the non-target positions forward from this layer's FP
        # (handoff="target+carry") and re-project all of them ([D, D] per
        # type) before the next round of gathers
        carry = tuple(sorted({ty for p in self.metapaths for ty in p}
                             - {self.target}))
        sample = None
        if cfg.fanout >= 1:
            # instances per target are the MAGNN fan-out knob; every kept
            # instance pulls its full node path into the frontier
            k = sample_cap(cfg)
            width = (len(self.metapaths) * k
                     * max(len(p) for p in self.metapaths))
            sample = SampleSpec(
                fanout=cfg.fanout,
                ladder=(cfg.sample_ladder
                        or default_sample_ladder(cfg.fanout, width,
                                                 cfg.layers)),
                seed=cfg.seed)
        residency = (ResidencySpec(cache_rows=cfg.cache_rows)
                     if cfg.cache_rows >= 1 else None)
        return StagePlan(
            model="magnn",
            target=self.target,
            layers=tuple(
                LayerPlan(fp=FPSpec(kind="per_type", sharded=False),
                          na=na, sa=sa, handoff="target+carry", carry=carry,
                          residency=residency)
                for l in range(cfg.layers)),
            head=HeadSpec(kind="linear"),
            metapaths=tuple(tuple(p) for p in self.metapaths),
            batch_specs=(PARTITION_BATCH_SPECS if part is not None
                         else INSTANCE_BUCKET_BATCH_SPECS
                         if layout == "bucketed"
                         else INSTANCE_BATCH_SPECS),
            partition=part,
            sample=sample,
            schedule=(ScheduleSpec(depth=cfg.overlap)
                      if cfg.overlap >= 1 else None),
        )

    # ---------------- Stage 1: Subgraph Build (host, metapath instances) ----
    def prepare(self, hg: HeteroGraph) -> Dict:
        cfg = self.cfg
        rng = np.random.default_rng(cfg.seed)
        timing: Dict = {}
        with span("hgnn.magnn.enumerate", timing, "enumerate_s"):
            insts = [mp.enumerate_instances(hg, p, cfg.max_instances, rng=rng)
                     for p in self.metapaths]
        batch: Dict = {
            "feats": {t: jnp.asarray(f) for t, f in hg.features.items()},
            "feat_dims": {t: hg.feat_dim(t) for t in hg.features},
            "n_nodes": hg.node_counts[self.target],
        }
        if instance_buckets(cfg) > 1:
            # rows binned by instance count: per metapath a few
            # [n_b, I_b, L] tables instead of one [N, I_max, L] pad
            bks = [mp.bucket_padded(ib, instance_buckets(cfg))
                   for ib in insts]
            batch["buckets"] = [
                [(jnp.asarray(b.row_ids[i]), jnp.asarray(b.nbr[i]),
                  jnp.asarray(b.mask[i])) for i in range(b.n_buckets)]
                for b in bks]
            slots = [sum(m.size for m in b.mask) for b in bks]
        else:
            # node types per path position are static (plan.metapaths)
            batch["instances"] = [
                (jnp.asarray(ib.nodes), jnp.asarray(ib.mask)) for ib in insts]
            slots = [ib.mask.size for ib in insts]
        batch = self._maybe_partition(batch)
        batch["record"] = mp.InstanceRecord(
            instances=tuple(ib.n_instances for ib in insts),
            instance_slots=tuple(int(x) for x in slots),
            enumerate_s=timing["enumerate_s"])
        return batch


# Quantile buckets for every instance of a heavy-tailed graph: on the
# benchmark's IMDB (628,722 MDM/MAM instances, up to 1,107 per movie) one
# [N, I_max, L] pad holds 12.4x the real instances, 8 buckets 2.15x, 16
# 1.53x and 32 1.29x.  Each slot's gathered 64-wide rows are re-laid on a
# v5e, so the forward follows the slots more than the bucket count: 19.6,
# 10.0, 9.6 and 8.6 ms a forward at 8, 16, 24 and 32 buckets.
EXACT_BUCKETS = 32


def instance_buckets(cfg: HGNNConfig) -> int:
    """Instance-count buckets of the full-graph layout: ``degree_buckets``
    where set (1: one table), else :data:`EXACT_BUCKETS` when every
    instance is kept; the partitioned flow needs the one-table layout."""
    if cfg.degree_buckets >= 1:
        return cfg.degree_buckets
    if cfg.max_instances <= 0 and cfg.partitions < 1:
        return EXACT_BUCKETS
    return 0


def sample_cap(cfg: HGNNConfig) -> int:
    """Instances a sampled target keeps: the fan-out, under
    ``max_instances`` when that caps the full-graph path too."""
    if cfg.max_instances >= 1:
        return min(cfg.fanout, cfg.max_instances)
    return cfg.fanout
