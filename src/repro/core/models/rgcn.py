"""R-GCN — Relational GCN (Schlichtkrull et al., ESWC'18).

Stages (paper Table 1): Relation Walk | per-relation Linear | Mean | Sum.
The early-stage HGNN: Semantic Aggregation is a plain sum (Reduce kernel,
memory-bound only — §4.4 of the paper).

Updates every node type: h'_d = act(W_0 h_d + Σ_{r: s->d} mean_{N_r}(h_s) W_r).

Execution is declared as a :class:`StagePlan`: NA layout ``csr`` (baseline),
``padded`` (``cfg.fused``), or ``bucketed`` (``cfg.degree_buckets > 1`` —
the per-relation tables ride the same degree-bucket dispatch as HAN, with
``agg_fn=mean``).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np

from repro.configs.base import HGNNConfig
from repro.core import metapath as mp
from repro.core import stages
from repro.core.hgraph import HeteroGraph
from repro.core.pipeline import PlannedModel
from repro.core.plan import (PARTITION_BATCH_SPECS, RELATION_BATCH_SPECS,
                             FPSpec, HeadSpec, LayerPlan, NASpec,
                             PartitionSpec, ResidencySpec, SampleSpec, SASpec,
                             StagePlan, default_sample_ladder)
from repro.data.synthetic import DATASET_TARGET


class RGCN(PlannedModel):
    def __init__(self, cfg: HGNNConfig):
        super().__init__(cfg)
        self.target = DATASET_TARGET[cfg.dataset]
        self.rel_keys: List[Tuple[str, str, str]] = []

    def plan(self) -> StagePlan:
        cfg = self.cfg
        if not cfg.fused:
            layout = "csr"
        elif cfg.degree_buckets > 1:
            layout = "bucketed"
        else:
            layout = "padded"
        part = None
        if cfg.partitions >= 1:
            if layout != "padded":
                raise ValueError(
                    "partitioned RGCN execution needs the padded per-relation "
                    f"layout (fused=True, no degree buckets); got {layout!r}")
            part = PartitionSpec(k=cfg.partitions)
        na = NASpec(kind="mean", layout=layout, use_pallas=cfg.use_pallas)
        sample = None
        if cfg.fanout >= 1:
            # the relation count is graph-side (plan() has no hg); size the
            # auto ladder for a nominal 4 relations — the sampler clamps
            # per-type and counts any truncation
            k = min(cfg.fanout, cfg.max_degree)
            sample = SampleSpec(
                fanout=cfg.fanout,
                ladder=(cfg.sample_ladder or default_sample_ladder(
                    cfg.fanout, 4 * k, cfg.layers)),
                seed=cfg.seed)
        residency = (ResidencySpec(cache_rows=cfg.cache_rows)
                     if cfg.cache_rows >= 1 else None)
        # rel_sum SA updates EVERY node type (handoff="all"); hidden layers
        # need no FP — the per-layer w_rel / w_self matmuls inside NA/SA are
        # the layer's linear transform (h' = relu(W_0 h + sum mean(h_s) W_r))
        return StagePlan(
            model="rgcn",
            target=self.target,
            layers=tuple(
                LayerPlan(
                    fp=(FPSpec(kind="per_type", sharded=True) if l == 0
                        else FPSpec(kind="identity")),
                    na=na, sa=SASpec(kind="rel_sum"), handoff="all",
                    residency=residency)
                for l in range(cfg.layers)),
            head=HeadSpec(kind="select_linear", target=self.target),
            batch_specs=(PARTITION_BATCH_SPECS if part is not None
                         else RELATION_BATCH_SPECS),
            partition=part,
            sample=sample,
        )

    # ---------------- Stage 1: Relation Walk (host) ----------------
    def prepare(self, hg: HeteroGraph) -> Dict:
        cfg = self.cfg
        rng = np.random.default_rng(cfg.seed)
        self.rel_keys = sorted(hg.relations.keys())
        batch: Dict = {
            "feats": {t: jnp.asarray(f) for t, f in hg.features.items()},
            "counts": dict(hg.node_counts),
            "feat_dims": {t: hg.feat_dim(t) for t in hg.features},
            "rels": {},
        }
        for key in self.rel_keys:
            s, r, d = key
            # incoming edges to type d from type s
            adj_in = hg.relations[key].T.tocsr()
            if cfg.fused:
                nbr = np.zeros((adj_in.shape[0], cfg.max_degree), np.int32)
                mask = np.zeros((adj_in.shape[0], cfg.max_degree), np.float32)
                indptr, indices = adj_in.indptr, adj_in.indices
                for u in range(adj_in.shape[0]):
                    nbrs = indices[indptr[u] : indptr[u + 1]]
                    if len(nbrs) > cfg.max_degree:
                        nbrs = rng.choice(nbrs, cfg.max_degree, replace=False)
                    nbr[u, : len(nbrs)] = nbrs
                    mask[u, : len(nbrs)] = 1.0
                if cfg.degree_buckets > 1:
                    # degree-bucketed per-relation tables (open ROADMAP item):
                    # same quantile K-caps as HAN, scattered back via row_ids
                    bk = mp.bucket_padded(
                        mp.PaddedSubgraph(nbr, mask, [s, d]),
                        cfg.degree_buckets)
                    batch["rels"][key] = [
                        (jnp.asarray(bk.row_ids[i]), jnp.asarray(bk.nbr[i]),
                         jnp.asarray(bk.mask[i]))
                        for i in range(bk.n_buckets)
                    ]
                else:
                    batch["rels"][key] = (jnp.asarray(nbr), jnp.asarray(mask))
            else:
                seg, idx = stages.csr_to_edges(adj_in.indptr, adj_in.indices)
                batch["rels"][key] = (jnp.asarray(seg), jnp.asarray(idx))
        return self._maybe_partition(batch)
