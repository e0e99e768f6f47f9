"""HAN — Heterogeneous Graph Attention Network (Wang et al., WWW'19).

Stages (paper Table 1): Metapath Walk | Linear Transformation | GAT | Attention Sum.

Execution is declared as a :class:`StagePlan` and run by the stage-graph
executor (:mod:`repro.core.pipeline`); this module only owns the host-side
Subgraph Build and the plan:

  * baseline (``cfg.fused=False``): NA layout ``csr`` — one CSR subgraph per
    metapath, separate kernels, SA pays the DR-Type concat.
  * optimized (``cfg.fused=True``): layout ``stacked`` ``[P, N, K]``
    (inter-subgraph parallelism, concat-free SA) or ``bucketed`` when
    ``cfg.degree_buckets > 1``.  ``cfg.use_pallas`` runs the fused GAT-NA
    kernel; ``cfg.fuse_na_sa`` additionally fuses the SA pass-1 epilogue
    into the NA kernel (stacked layout only).
"""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from repro.configs.base import HGNNConfig
from repro.core import metapath as mp
from repro.core import stages
from repro.core.hgraph import HeteroGraph
from repro.core.pipeline import PlannedModel
from repro.core.plan import (BUCKETED_BATCH_SPECS, PARTITION_BATCH_SPECS,
                             STACKED_BATCH_SPECS, FPSpec, HeadSpec, LayerPlan,
                             NASpec, PartitionSpec, ResidencySpec, SampleSpec,
                             SASpec, StagePlan,
                             default_sample_ladder)
from repro.data.synthetic import DATASET_METAPATHS, DATASET_TARGET


class HAN(PlannedModel):
    def __init__(self, cfg: HGNNConfig):
        super().__init__(cfg)
        self.metapaths = DATASET_METAPATHS[cfg.dataset]
        self.target = DATASET_TARGET[cfg.dataset]

    def plan(self) -> StagePlan:
        cfg = self.cfg
        if not cfg.fused:
            layout = "csr"
        elif cfg.degree_buckets > 1:
            layout = "bucketed"
        else:
            layout = "stacked"
        part = None
        if cfg.partitions >= 1:
            if layout != "stacked":
                raise ValueError(
                    "partitioned HAN execution needs the stacked layout "
                    "(fused=True, no degree buckets); got "
                    f"layout={layout!r}")
            part = PartitionSpec(k=cfg.partitions)
        na = NASpec(kind="gat", layout=layout, activation="elu",
                    use_pallas=cfg.use_pallas)
        sa = SASpec(kind="attention", stacked=cfg.fused,
                    fuse_epilogue=(cfg.fuse_na_sa and layout == "stacked"
                                   and part is None))
        sample = None
        if cfg.fanout >= 1:
            # per-hop width: every metapath contributes up to the padded
            # table's effective fan-out per target row
            k = min(cfg.fanout, cfg.max_degree)
            sample = SampleSpec(
                fanout=cfg.fanout,
                ladder=(cfg.sample_ladder or default_sample_ladder(
                    cfg.fanout, len(self.metapaths) * k, cfg.layers)),
                seed=cfg.seed)
        residency = (ResidencySpec(cache_rows=cfg.cache_rows)
                     if cfg.cache_rows >= 1 else None)
        # layer 0 projects the raw per-type features; the metapath graphs
        # are target->target, so every hidden layer re-projects only the
        # previous SA output (a dense [D, D] matmul, reshaped to heads)
        return StagePlan(
            model="han",
            target=self.target,
            layers=tuple(
                LayerPlan(
                    fp=(FPSpec(kind="per_type", sharded=True, heads=True)
                        if l == 0 else
                        FPSpec(kind="dense", sharded=True, heads=True)),
                    na=na, sa=sa, handoff="target", residency=residency)
                for l in range(cfg.layers)),
            head=HeadSpec(kind="linear"),
            metapaths=tuple(tuple(p) for p in self.metapaths),
            batch_specs=(PARTITION_BATCH_SPECS if part is not None
                         else BUCKETED_BATCH_SPECS if layout == "bucketed"
                         else STACKED_BATCH_SPECS),
            partition=part,
            sample=sample,
        )

    # ---------------- Stage 1: Subgraph Build (host) ----------------
    def prepare(self, hg: HeteroGraph) -> Dict:
        cfg = self.cfg
        rng = np.random.default_rng(cfg.seed)
        batch: Dict = {
            "feats": {t: jnp.asarray(f) for t, f in hg.features.items()},
            "n_nodes": hg.node_counts[self.target],
        }
        if cfg.fused:
            subs = [
                mp.build_padded(hg, p, cfg.max_degree, rng) for p in self.metapaths
            ]
            if cfg.degree_buckets > 1:
                # degree-bucketed layout: per metapath, rows binned into a
                # few K-caps (executor dispatches gat_aggregate_bucketed)
                batch["buckets"] = [
                    [(jnp.asarray(b.row_ids[i]), jnp.asarray(b.nbr[i]),
                      jnp.asarray(b.mask[i])) for i in range(b.n_buckets)]
                    for b in (mp.bucket_padded(s, cfg.degree_buckets)
                              for s in subs)
                ]
            else:
                nbr, mask = mp.stack_padded(subs)
                batch["nbr"] = jnp.asarray(nbr)  # [P, N, K]
                batch["mask"] = jnp.asarray(mask)
        else:
            edges = []
            for p in self.metapaths:
                csr = mp.build_csr(hg, p)
                seg, idx = stages.csr_to_edges(csr.indptr, csr.indices)
                edges.append((jnp.asarray(seg), jnp.asarray(idx)))
            batch["edges"] = edges
        batch["feat_dims"] = {t: hg.feat_dim(t) for t in hg.features}
        return self._maybe_partition(batch)
