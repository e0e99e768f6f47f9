"""GCN (Kipf & Welling) — the paper's GNN comparison baseline (§4.5, Fig. 5a).

Two stages only: Aggregation (normalized mean over neighbors) + Combination
(dense matmul). Used on the Reddit-like graph to contrast with HAN's
metapath-scaled Neighbor Aggregation.

As a :class:`StagePlan`: FP is the first Combination (``x @ w1`` — mean
aggregation and the dense matmul commute), NA covers both aggregation
layers (GCN has no semantic stage: ``sa.kind="none"``), the head is the
second Combination.
"""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from repro.configs.base import HGNNConfig
from repro.core import metapath as mp
from repro.core import stages
from repro.core.hgraph import HeteroGraph
from repro.core.pipeline import PlannedModel
from repro.core.plan import (FPSpec, HeadSpec, LayerPlan, NASpec,
                             ResidencySpec, SampleSpec, SASpec,
                             StagePlan, default_sample_ladder)
from repro.data.synthetic import DATASET_TARGET


class GCN(PlannedModel):
    def __init__(self, cfg: HGNNConfig):
        super().__init__(cfg)
        self.target = DATASET_TARGET[cfg.dataset]

    def plan(self) -> StagePlan:
        if self.cfg.partitions >= 1:
            raise ValueError("gcn runs the homogeneous CSR baseline; it has "
                             "no partitioned execution layout")
        cfg = self.cfg
        sample = None
        if cfg.fanout >= 1:
            # each LayerPlan runs TWO csr aggregations -> 2 hops per layer
            sample = SampleSpec(
                fanout=cfg.fanout,
                ladder=(cfg.sample_ladder or default_sample_ladder(
                    cfg.fanout, cfg.fanout, 2 * cfg.layers)),
                seed=cfg.seed)
        residency = (ResidencySpec(cache_rows=cfg.cache_rows)
                     if cfg.cache_rows >= 1 else None)
        # one LayerPlan = one agg(relu(agg(h @ w))) block (the paper's
        # 2-conv GCN); extra layers stack that block with fresh [D, D]
        # combination weights before the classifier head
        return StagePlan(
            model="gcn",
            target=self.target,
            layers=tuple(
                LayerPlan(fp=FPSpec(kind="dense", sharded=False),
                          na=NASpec(kind="gcn", layout="csr",
                                    activation="relu"),
                          sa=SASpec(kind="none"), handoff="target",
                          residency=residency)
                for l in range(self.cfg.layers)),
            head=HeadSpec(kind="linear", param="w2"),
            sample=sample,
        )

    def prepare(self, hg: HeteroGraph) -> Dict:
        t = self.target
        csr = mp.build_csr(hg, [t, t])
        seg, idx = stages.csr_to_edges(csr.indptr, csr.indices)
        return self._maybe_partition({
            "x": jnp.asarray(hg.features[t]),
            "seg": jnp.asarray(seg),
            "idx": jnp.asarray(idx),
            "n_nodes": hg.node_counts[t],
            "feat_dim": hg.feat_dim(t),
        })
