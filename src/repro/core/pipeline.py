"""The stage-graph executor: one interpreter for every :class:`StagePlan`.

Model classes used to own the dispatch ladder (baseline CSR vs fused
resident vs streaming vs bucketed vs sharded vs pallas-vs-ref) — three
copies of it, one per HGNN.  Here it lives once: the executor resolves
layout, kernel dispatch, sharding constraints and interpret/pallas mode from
the plan, and the models shrink to host-side ``prepare()`` plus a plan
builder (:class:`PlannedModel`).

A plan is an **L-layer stack** (:class:`repro.core.plan.LayerPlan`): the
executor loops FP→NA→SA per layer with the per-type intermediate feature
tables as the carried state, reusing the layer-invariant host-side layouts
(padded/stacked/bucketed index maps, degree buckets, instance LUTs, halo
maps) built once in ``prepare()``.  Layer 0's parameters live at the pytree
root — ``cfg.layers=1`` is bit-exact with the pre-multi-layer path — and
hidden layers ride ``params["layers"][l-1]`` with the same leaf names, so
the declarative sharding rule tables cover them for free.

The executor also owns the paper's two structural optimizations:

* **Graph-partitioned execution** (``plan.partition``): the vertex/feature
  tables are split into K edge-cut partitions (``repro.dist.partition``);
  FP and NA run per-partition on local shards and the halo feature exchange
  between them is an explicit ``gather_halo`` stage (shard_map over the
  BATCH axes when the mesh divides K).  SA runs unchanged on the
  partition-local stacks — its score pass reduces per-partition partials,
  so the only other communication is a [K, P]-sized reduce.  The halo
  *maps* are graph-invariant, so an L-layer stack re-runs ``gather_halo``
  per layer on the *updated* features (total exchanged traffic =
  halo-bytes × L; ``characterize.partition_traffic`` reports it).

* **Fused NA→SA epilogue** (``plan.sa.fuse_epilogue``): on the stacked
  layout the semantic-score pass-1 partial (``mean_n q·tanh(z W + b)``)
  accumulates inside the NA kernel while each ``z`` tile is in VMEM —
  one full ``[P, N, D]`` HBM read disappears, and SA degenerates to a
  softmax over ``P`` plus the weighted combine (exactly one ``z`` read).
* **Per-stage characterization records** (:meth:`stage_records`): every
  stage function is lowered and walked by ``core/characterize.py``, so
  benchmarks report the paper's Fig. 3-style breakdown from the same code
  path that serves traffic.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import semantics, stages
from repro.core.plan import StagePlan
from repro.dist.sharding import BATCH, MODEL
from repro.kernels.gather import MATMUL_PRECISION

_ACT = {None: lambda x: x, "elu": jax.nn.elu, "relu": jax.nn.relu}


def _kops():
    """Kernel dispatch goes through the module attribute so tests can
    monkeypatch wrappers into interpret mode."""
    from repro.kernels import ops

    return ops


class StageGraphExecutor:
    """Executes a :class:`StagePlan` over a prepared device batch."""

    def __init__(self, plan: StagePlan, cfg):
        self.plan = plan
        self.cfg = cfg
        # per-stage jit cache for the async schedule driver: one traced
        # callable per stage name, reused across forward_overlapped calls
        # (shapes key jax.jit's own cache below it)
        self._ov_jit: Dict = {}
        # last forward_overlapped dispatch trace (tests / accounting)
        self.last_dispatch: Dict = {}

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------
    def init(self, rng: jax.Array, batch: Dict) -> Dict:
        params = self._init_layer0(rng, batch)
        if self.plan.n_layers > 1:
            # hidden-layer params mirror the root leaf names under
            # params["layers"][l-1] (the sharding rule tables match on leaf
            # name + rank, so they cover the stack for free); fold_in keeps
            # the layer-0 RNG stream untouched -> layers=1 stays bit-exact
            params["layers"] = [
                self._init_hidden_layer(jax.random.fold_in(rng, l), batch)
                for l in range(1, self.plan.n_layers)
            ]
        return params

    def _init_layer0(self, rng: jax.Array, batch: Dict) -> Dict:
        cfg, plan = self.cfg, self.plan
        d = cfg.hidden
        if plan.na.kind == "gcn":
            k1, k2 = jax.random.split(rng)
            d_in = batch["feat_dim"]
            return {
                "w1": jax.random.normal(k1, (d_in, d), jnp.float32) / np.sqrt(d_in),
                "w2": jax.random.normal(k2, (d, cfg.n_classes), jnp.float32)
                / np.sqrt(d),
            }
        k_fp, k_na, k_sem, k_cls = jax.random.split(rng, 4)
        params: Dict = {
            "fp": stages.init_feature_projection(k_fp, batch["feat_dims"], d),
            "cls": jax.random.normal(k_cls, (d, cfg.n_classes), jnp.float32)
            / np.sqrt(d),
        }
        params.update(self._init_na_sa(k_na, k_sem, batch))
        return params

    def _init_na_sa(self, k_na: jax.Array, k_sem: jax.Array,
                    batch: Dict) -> Dict:
        """The NA/SA parameter block shared by layer 0 and every hidden
        layer: gat stacks / instance attention + semantic attention, or
        per-relation ``w_rel`` + per-type ``w_self``.  RNG consumption is
        identical to the pre-multi-layer init, so layer 0 stays bit-exact."""
        cfg, plan = self.cfg, self.plan
        d = cfg.hidden
        head_dim = d // cfg.n_heads
        p: Dict = {}
        if plan.na.kind == "gat":
            keys = jax.random.split(k_na, len(plan.metapaths))
            gat = [stages.init_gat(k, cfg.n_heads, head_dim) for k in keys]
            if plan.na.layout == "stacked":
                # one stacked param set -> ONE kernel launch for the stack
                # (bucketed keeps the per-metapath list: no uniform K)
                gat = jax.tree.map(lambda *xs: jnp.stack(xs), *gat)
            p["gat"] = gat
            p["sem"] = semantics.init_semantic_attention(
                k_sem, d, cfg.attn_hidden)
        elif plan.na.kind == "instance":
            keys = jax.random.split(k_na, len(plan.metapaths))
            p["att"] = [
                stages.init_instance_attention(k, cfg.n_heads, head_dim)
                for k in keys
            ]
            p["sem"] = semantics.init_semantic_attention(
                k_sem, d, cfg.attn_hidden)
        elif plan.na.kind == "mean":
            rel_keys = sorted(batch["rels"])
            rel_ks = jax.random.split(k_na, max(len(rel_keys), 1))
            self_ks = jax.random.split(k_sem, len(batch["counts"]))
            p["w_rel"] = {
                key: jax.random.normal(k, (d, d), jnp.float32) / np.sqrt(d)
                for key, k in zip(rel_keys, rel_ks)
            }
            p["w_self"] = {
                t: jax.random.normal(k, (d, d), jnp.float32) / np.sqrt(d)
                for t, k in zip(sorted(batch["counts"]), self_ks)
            }
        return p

    def _init_hidden_layer(self, rng: jax.Array, batch: Dict) -> Dict:
        """Params for one layer >= 1: the hidden FP (square [D, D]
        re-projections of the carried tables, or nothing for ``identity``)
        plus a fresh copy of the layer's NA/SA attention/relation weights."""
        cfg, plan = self.cfg, self.plan
        d = cfg.hidden
        if plan.na.kind == "gcn":
            return {"fp": jax.random.normal(rng, (d, d), jnp.float32)
                    / np.sqrt(d)}
        k_fp, k_na, k_sem = jax.random.split(rng, 3)
        p: Dict = {}
        if plan.na.kind == "gat":
            p["fp"] = jax.random.normal(k_fp, (d, d), jnp.float32) / np.sqrt(d)
        elif plan.na.kind == "instance":
            # carry is layer-uniform (StagePlan.__post_init__)
            types = tuple(sorted(set(plan.layers[0].carry) | {plan.target}))
            fp_ks = jax.random.split(k_fp, len(types))
            p["fp"] = {
                t: jax.random.normal(k, (d, d), jnp.float32) / np.sqrt(d)
                for t, k in zip(types, fp_ks)
            }
        p.update(self._init_na_sa(k_na, k_sem, batch))
        return p

    def _layer_params(self, params: Dict, l: int) -> Dict:
        """Layer ``l``'s parameter dict: layer 0 lives at the pytree root
        (bit-exact with the single-layer layout), hidden layers under
        ``params["layers"][l-1]`` with the same leaf names."""
        return params if l == 0 else params["layers"][l - 1]

    # ------------------------------------------------------------------
    # Stage 2: Feature Projection
    # ------------------------------------------------------------------
    def fp(self, params: Dict, batch: Dict):
        plan = self.plan
        if plan.partition is not None:
            return self._fp_partitioned(params, batch)
        if plan.fp.kind == "dense":
            return batch["x"] @ params["w1"]
        project = (stages.feature_projection_sharded if plan.fp.sharded
                   else stages.feature_projection)
        h = project(params["fp"], batch["feats"])
        if plan.fp.heads:
            ht = h[plan.target]
            return ht.reshape(ht.shape[0], self.cfg.n_heads, -1)  # [N, H, Dh]
        return h

    def _fp_partitioned(self, params: Dict, batch: Dict) -> Dict:
        """FP over the per-partition owned feature shards: [K, n_t, F_t] @
        W_t per type — pure data parallelism over the partition dim."""
        plan = self.plan
        out: Dict = {}
        for t, f in batch["part"]["feats"].items():
            w = params["fp"][t]
            if plan.fp.sharded:
                w = stages.shard(w, *stages.HGNN_STAGE_SPECS["fp_weight"])
            out[t] = stages.shard(f @ w, BATCH, None, MODEL)
        return out

    def _fp_hidden(self, lp, p_l: Dict, state):
        """FP for layers >= 1: project the carried per-type feature tables
        (``[N_t, D]`` single-table, ``[K, n_t, D]`` partitioned — the matmul
        broadcasts over the partition dim).  ``identity`` passes the state
        through (RGCN: the relation weights are the layer's transform)."""
        plan = self.plan
        if lp.fp.kind == "identity":
            return state
        if lp.fp.kind == "per_type":
            project = (stages.feature_projection_sharded if lp.fp.sharded
                       else stages.feature_projection)
            return project(p_l["fp"], state)
        # dense: a single [D, D] re-projection of the carried target table
        w = p_l["fp"]
        if lp.fp.sharded:
            w = stages.shard(w, *stages.HGNN_STAGE_SPECS["fp_weight"])
        x = state[plan.target]
        if plan.partition is not None:
            # keep the dict shape gather_halo expects; heads reshaping
            # happens inside the partitioned NA (as in layer 0)
            return {plan.target: stages.shard(x @ w, BATCH, None, MODEL)}
        h = x @ w
        if lp.fp.sharded:
            h = stages.shard(h, *stages.HGNN_STAGE_SPECS["fp_out"])
        if lp.fp.heads:
            return h.reshape(h.shape[0], self.cfg.n_heads, -1)  # [N, H, Dh]
        return h

    def _handoff(self, lp, batch: Dict, h, out):
        """Package one layer's outputs as the next layer's carried state —
        the device-side realization of ``LayerPlan.handoff``.  ``h`` is this
        layer's FP output (post-``gather_halo`` in the partitioned flow),
        ``out`` its SA output."""
        plan = self.plan
        if lp.handoff == "all":
            return out  # rel_sum SA already returned every type's table
        state = {plan.target: out}
        if lp.handoff == "target+carry":
            if plan.partition is not None:
                part = batch["part"]
                for ty in lp.carry:  # owned rows only; halos re-exchange
                    state[ty] = h[ty][:, : part["feats"][ty].shape[1]]
            else:
                for ty in lp.carry:
                    state[ty] = h[ty]
        return state

    # ------------------------------------------------------------------
    # partitioned flow: the halo feature exchange (the new explicit stage)
    # ------------------------------------------------------------------
    def halo_exchange(self, batch: Dict, h_own: Dict) -> Dict:
        """Exchange-only half of :meth:`gather_halo`: fetch each type's
        halo rows from the other partitions' owned tables — WITHOUT
        appending them to the local pool.  The async schedule dispatches
        this concurrently with NA's owned-rows pre-gather (both depend
        only on FP); the serial path concatenates right below."""
        from repro.dist.partition import gather_halo as _gather

        part = batch["part"]
        mode = self.plan.partition.halo
        res = batch.get("residency")
        out: Dict = {}
        for t, h in h_own.items():
            halo = _gather(h, part["halo_src"][t], mode=mode)
            if res is not None and t in res.get("halo_slot", {}):
                # residency arm (hot-halo path): halo entries whose global
                # vertex is hot are overlaid from the partition-local cache
                # — bitwise copies of owned rows — so they skip the
                # exchange.  Pure indexing: bit-exact under both the
                # shard_map and flat gather lowerings.
                slot = res["halo_slot"][t]  # [K, H_max] (-1 = cold/pad)
                tail = h.shape[2:]
                cache = h.reshape((-1,) + tail)[res["hot_flat"][t]]
                sel = jnp.take(cache, jnp.clip(slot, 0), axis=0)
                cond = (slot >= 0).reshape(slot.shape + (1,) * len(tail))
                halo = jnp.where(cond, sel, halo)
            out[t] = halo
        return out

    def gather_halo(self, batch: Dict, h_own: Dict):
        """Fetch each type's halo rows from the other partitions' owned
        tables and append them: local source table = concat(own, halo).
        The one communication step of the partitioned flow (shard_map
        all-gather on a dividing mesh; see ``repro.dist.partition``)."""
        halos = self.halo_exchange(batch, h_own)
        return {t: jnp.concatenate([h, halos[t]], axis=1)
                for t, h in h_own.items()}

    # ------------------------------------------------------------------
    # Stage 3: Neighbor Aggregation
    # ------------------------------------------------------------------
    def _res_pool(self, batch: Dict, t: str, x):
        """Residency dispatch arm (``plan.residency`` + a prepared batch
        that carries the hot sets): extend type ``t``'s source pool with
        the resident cache section — bitwise copies of the hot rows, which
        the remapped index tables address instead of re-gathering the
        scattered HBM rows.  The hot sets are layer-invariant, so every
        layer of an L-layer stack reuses the same resident rows (HiHGNN
        inter-layer reuse).  Sampled/uncached batches pass through."""
        res = batch.get("residency")
        if res is None or "hot" not in res or t not in res["hot"]:
            return x
        return jnp.concatenate([x, jnp.take(x, res["hot"][t], axis=0)],
                               axis=0)

    def na(self, params: Dict, batch: Dict, h):
        kind = self.plan.na.kind
        if self.plan.partition is not None:
            return self._na_partitioned(params, batch, h)
        if kind == "gat":
            return self._na_gat(params, batch, h)
        if kind == "mean":
            return self._na_mean(params, batch, h)
        if kind == "instance":
            return self._na_instance(params, batch, h)
        if kind == "gcn":
            # both GCN aggregation layers are NA work (the paper's GNN
            # comparison has no semantic stage); the segment count comes
            # from h's static shape so the forward stays jit-able with the
            # batch as an argument (batch["n_nodes"] would be a tracer).
            # The residency pool covers both aggregations — the second one
            # re-gathers z over the same remapped index table, which is the
            # inter-layer reuse in its purest form.
            t = self.plan.target
            z = jax.nn.relu(stages.mean_aggregate_csr(
                self._res_pool(batch, t, h), batch["seg"], batch["idx"],
                h.shape[0]))
            return stages.mean_aggregate_csr(
                self._res_pool(batch, t, z), batch["seg"], batch["idx"],
                z.shape[0])
        raise ValueError(f"unknown NA kind {kind!r}")

    def _na_gat(self, params: Dict, batch: Dict, h: jax.Array):
        plan, cfg = self.plan, self.cfg
        act = _ACT[plan.na.activation]
        # residency arm: the gather pool is the target table extended with
        # the resident hot-row section (uncached batches: pool is h itself)
        pool = self._res_pool(batch, plan.target, h)
        if plan.na.layout == "csr":
            # baseline: independent kernels per subgraph (paper Fig. 5c).
            # h [N, H, Dh] covers the target nodes, so its static leading
            # dim is the segment count (jit-safe: batch["n_nodes"] traces).
            outs: List[jax.Array] = []
            for p_i, (seg, idx) in zip(params["gat"], batch["edges"]):
                z = stages.gat_aggregate_csr(p_i, h, pool, seg, idx,
                                             h.shape[0])
                outs.append(act(z).reshape(z.shape[0], -1))
            return outs  # list of [N, D]
        if plan.na.layout == "bucketed":
            agg_fn = None
            if plan.na.use_pallas:
                kops = _kops()
                agg_fn = lambda p, hd, hs, nn, mm: kops.gat_aggregate(
                    p, hd, hs, nn, mm, use_pallas=True)
            z = jnp.stack([
                stages.gat_aggregate_bucketed(p_i, h, pool, bks,
                                              agg_fn=agg_fn)
                for p_i, bks in zip(params["gat"], batch["buckets"])
            ])  # [P, N, H, Dh]
            z = act(z)
            return z.reshape(z.shape[0], z.shape[1], -1)  # [P, N, D]
        # stacked layout: ONE launch for the whole [P, N, K] stack
        if plan.sa.fuse_epilogue:
            return self._na_gat_fused_sa(params, batch, h)
        stacked_fn = None
        if plan.na.use_pallas:
            kops = _kops()
            stacked_fn = lambda pp, hd, hs, nn, mm: kops.gat_aggregate_stacked(
                pp, hd, hs, nn, mm, use_pallas=True)
        z = stages.gat_aggregate_padded_stacked(
            params["gat"], h, batch["nbr"], batch["mask"],
            stacked_fn=stacked_fn, h_src=pool)
        z = act(z)
        return z.reshape(z.shape[0], z.shape[1], -1)  # [P, N, D]

    def _na_gat_fused_sa(self, params: Dict, batch: Dict, h: jax.Array):
        """Stacked NA with the SA pass-1 epilogue fused in: returns
        ``(z [P, N, D] activation applied, wp [P] semantic-score means)``."""
        if self.plan.na.activation != "elu":
            # the kernel epilogue bakes the NA activation in (elu); a plan
            # declaring another activation would silently diverge
            raise ValueError("sa.fuse_epilogue requires na.activation='elu' "
                             f"(got {self.plan.na.activation!r})")
        kops = _kops()
        specs = stages.HGNN_STAGE_SPECS
        h_src = stages.shard(self._res_pool(batch, self.plan.target, h),
                             *specs["na_src"])
        nbr = stages.shard(batch["nbr"], None, *specs["na_nbr"])
        mask = stages.shard(batch["mask"], None, *specs["na_nbr"])
        z4, wp = kops.gat_aggregate_stacked_fused_sa(
            params["gat"], h, h_src, nbr, mask, params["sem"],
            use_pallas=self.plan.na.use_pallas)
        z4 = stages.shard(z4, None, *specs["na_out"])
        return z4.reshape(z4.shape[0], z4.shape[1], -1), wp

    def _na_mean(self, params: Dict, batch: Dict, h: Dict[str, jax.Array]):
        plan = self.plan
        # "__h__" rides along for the self-loop term in SA (rel_sum)
        out: Dict = {"__h__": h}
        agg_fn = None
        if plan.na.use_pallas and plan.na.layout != "csr":
            kops = _kops()
            agg_fn = lambda hs, nn, mm: kops.segment_spmm(
                hs, nn, mm, mean=True, use_pallas=True)
        for key in sorted(batch["rels"]):
            s, r, d = key
            rel = batch["rels"][key]
            # residency arm: cache-extended per-source-type gather pool
            pool = self._res_pool(batch, s, h[s])
            if plan.na.layout == "csr":
                # h[d]'s static leading dim is the destination-type count
                # (jit-safe: batch["counts"] values trace)
                agg = stages.mean_aggregate_csr(pool, rel[0], rel[1],
                                                h[d].shape[0])
            elif plan.na.layout == "bucketed":
                # the destination table's static leading dim is the row
                # count (jit-safe; for full-graph batches the bucket row_ids
                # partition exactly those rows, for sampled rung-padded
                # buckets the out-of-range pad row_ids scatter-drop)
                agg = stages.mean_aggregate_bucketed(
                    pool, rel, h[d].shape[0], agg_fn=agg_fn)
            else:  # padded
                agg = stages.mean_aggregate_padded_sharded(
                    pool, rel[0], rel[1], agg_fn=agg_fn)
            out["|".join(key)] = agg @ params["w_rel"][key]
        return out

    def _na_instance_one(self, params: Dict, batch: Dict,
                         h: Dict[str, jax.Array], i_path: int) -> jax.Array:
        """One metapath's instance-attention NA — the serial loop body and
        the async schedule's per-metapath stage share it verbatim."""
        plan, cfg = self.plan, self.cfg
        specs = stages.HGNN_STAGE_SPECS
        H = cfg.n_heads
        act = _ACT[plan.na.activation]
        res = batch.get("residency")
        hot = res["hot"] if res is not None and "hot" in res else {}
        p_i = params["att"][i_path]
        nodes, mask = batch["instances"][i_path]
        types = plan.metapaths[i_path]
        nodes = stages.shard(nodes, *specs["na_inst_nodes"])
        mask = stages.shard(mask, *specs["na_nbr"])
        n, i, l = nodes.shape

        # gather projected features per path position (types are static,
        # carried by the plan); the residency arm serves the remapped
        # instance tables through the VMEM-resident cache gather
        def gather(j):
            ty = types[j]
            if ty in hot:
                return _kops().cached_gather(
                    h[ty], hot[ty], nodes[:, :, j],
                    use_pallas=plan.na.use_pallas)
            return h[ty][nodes[:, :, j]]

        h_path = jnp.stack(
            [gather(j) for j in range(l)], axis=2
        )  # [N, I, L, D]
        h_path = h_path.reshape(n, i, l, H, -1)
        enc = stages.rotate_encoder(h_path)  # [N, I, H, Dh]
        h_tgt = h[plan.target].reshape(-1, H, h_path.shape[-1])
        if plan.na.use_pallas:
            # Instance attention IS padded GAT NA with the encoded
            # instances as the source pool (arange neighbor grid).
            kops = _kops()
            flat = enc.reshape(n * i, H, enc.shape[-1])
            nbr_inst = jnp.arange(n * i, dtype=jnp.int32).reshape(n, i)
            z = kops.gat_aggregate(p_i, h_tgt, flat, nbr_inst, mask,
                                   use_pallas=True)
        else:
            z = stages.instance_aggregate(p_i, h_tgt, enc, mask)
        z = act(z).reshape(n, -1)
        return stages.shard(z, *specs["na_flat_out"])  # [N, D]

    def _na_instance(self, params: Dict, batch: Dict, h: Dict[str, jax.Array]):
        return [self._na_instance_one(params, batch, h, i)
                for i in range(len(self.plan.metapaths))]

    def _na_metapath(self, params: Dict, batch: Dict, h, i: int):
        """One metapath's NA as its own schedulable stage (async schedule,
        single-device): the bucketed / csr GAT loop body or one MAGNN
        instance-attention round.  The only delta vs the serial loop is
        *where* the activation applies — per-metapath here vs post-stack
        there — which is elementwise, so SA's re-stack is bitwise equal."""
        plan = self.plan
        act = _ACT[plan.na.activation]
        if plan.na.kind == "instance":
            return self._na_instance_one(params, batch, h, i)
        pool = self._res_pool(batch, plan.target, h)
        if plan.na.layout == "csr":
            seg, idx = batch["edges"][i]
            z = stages.gat_aggregate_csr(params["gat"][i], h, pool, seg, idx,
                                         h.shape[0])
            return act(z).reshape(z.shape[0], -1)  # [N, D]
        agg_fn = None
        if plan.na.use_pallas:
            kops = _kops()
            agg_fn = lambda p, hd, hs, nn, mm: kops.gat_aggregate(
                p, hd, hs, nn, mm, use_pallas=True)
        z = stages.gat_aggregate_bucketed(params["gat"][i], h, pool,
                                          batch["buckets"][i], agg_fn=agg_fn)
        return act(z).reshape(z.shape[0], -1)  # [N, D]

    def _na_partitioned(self, params: Dict, batch: Dict, h_loc: Dict):
        """NA over partition-local shards: destinations are the owned rows,
        sources the concat(own, halo) local tables built by ``gather_halo``.
        Runs the XLA padded path vmapped over the partition dim (fusing the
        Pallas kernels into the per-partition body is future work)."""
        plan, cfg = self.plan, self.cfg
        part = batch["part"]
        t = plan.target
        act = _ACT[plan.na.activation]
        H = cfg.n_heads
        if plan.na.kind == "gat":
            n_own = part["feats"][t].shape[1]
            heads = lambda x: x.reshape(x.shape[0], x.shape[1], H, -1)
            hd = heads(h_loc[t][:, :n_own])  # [K, n, H, Dh] owned rows
            hs = heads(h_loc[t])  # [K, n+halo, H, Dh] local source pool

            def one_part(hd_k, hs_k, nbr_k, mask_k):  # nbr_k [P, n, Kd]
                return jax.vmap(
                    lambda pp, nn, mm: stages.gat_aggregate_padded(
                        pp, hd_k, hs_k, nn, mm),
                    in_axes=(0, 0, 0))(params["gat"], nbr_k, mask_k)

            z = jax.vmap(one_part)(hd, hs, part["nbr"], part["mask"])
            z = act(z)  # [K, P, n, H, Dh]
            z = z.reshape(z.shape[0], z.shape[1], z.shape[2], -1)
            return stages.shard(z, BATCH, None, None, None)  # [K, P, n, D]
        if plan.na.kind == "mean":
            if plan.n_layers > 1:
                # multi-layer partitioning relabels EVERY relation (each
                # destination type aggregates on its own owners); carry the
                # per-type owned rows for the rel_sum self-loop
                out: Dict = {"__h__": {
                    ty: h_loc[ty][:, : part["feats"][ty].shape[1]]
                    for ty in part["feats"]
                }}
            else:
                out = {"__h__": h_loc[t][:, : part["feats"][t].shape[1]]}
            for key in sorted(part["rels"]):
                s = key[0]
                nbr, mask = part["rels"][key]
                agg = jax.vmap(stages.mean_aggregate_padded)(
                    h_loc[s], nbr, mask)  # [K, n_d, D]
                out["|".join(key)] = agg @ params["w_rel"][key]
            return out
        if plan.na.kind == "instance":
            h_tgt = h_loc[t][:, : part["feats"][t].shape[1]]
            h_tgt = h_tgt.reshape(h_tgt.shape[0], h_tgt.shape[1], H, -1)
            outs: List[jax.Array] = []
            for p_i, (nodes, mask), types in zip(params["att"],
                                                 part["instances"],
                                                 plan.metapaths):
                k_, n, i, l = nodes.shape
                h_path = jnp.stack(
                    [jax.vmap(lambda hh, idx: hh[idx])(
                        h_loc[types[j]], nodes[:, :, :, j])
                     for j in range(l)], axis=3)  # [K, n, I, L, D]
                h_path = h_path.reshape(k_, n, i, l, H, -1)
                enc = jax.vmap(stages.rotate_encoder)(h_path)  # [K, n, I, H, Dh]
                z = jax.vmap(stages.instance_aggregate, in_axes=(None, 0, 0, 0))(
                    p_i, h_tgt, enc, mask)
                outs.append(act(z).reshape(k_, n, -1))  # [K, n, D]
            return outs
        raise ValueError(
            f"no partitioned NA path for kind {plan.na.kind!r}")

    # ------------------------------------------------------------------
    # partitioned flow, async schedule: the own/halo NA split.
    #
    # Serial partitioned NA gathers from concat(own, halo) — it cannot
    # start until the exchange lands.  But a gather is a pure row
    # selection, so it splits at the *gather*, never at a float
    # reduction: the owned-side rows (and the per-row source attention
    # scores, which are row-local EW math) pre-gather against the owned
    # table alone while the exchange is still in flight, and the merge
    # where-selects the halo side in afterwards (stages.gather_own /
    # gather_merge — bitwise equal to the concat-then-gather).  All the
    # attention / mean arithmetic runs once, in the merge, on the merged
    # operands — identical values in identical reduction order.
    # ------------------------------------------------------------------
    def _na_partitioned_own(self, params: Dict, batch: Dict, h_own: Dict):
        """Owned-rows pre-gather pass: everything partitioned NA can do
        from FP's output alone (depends only on FP — runs concurrently
        with ``halo_exchange``).  Returns the pre-gathered operand pytree
        :meth:`_na_partitioned_merge` consumes."""
        plan, cfg = self.plan, self.cfg
        part = batch["part"]
        t = plan.target
        H = cfg.n_heads
        if plan.na.kind == "gat":
            heads = lambda x: x.reshape(x.shape[0], x.shape[1], H, -1)
            hs_own = heads(h_own[t])  # [K, n, H, Dh]

            def one_part(hs_k, nbr_k):  # nbr_k [P, n, Kd]
                def one_path(pp, nn):
                    e_tab = (hs_k * pp["a_src"]).sum(-1)  # [n, H] EW
                    return (stages.gather_own(hs_k, nn),
                            stages.gather_own(e_tab, nn))

                return jax.vmap(one_path)(params["gat"], nbr_k)

            hn_own, e_own = jax.vmap(one_part)(hs_own, part["nbr"])
            return {"hn": hn_own,  # [K, P, n, Kd, H, Dh]
                    "e": e_own}  # [K, P, n, Kd, H]
        if plan.na.kind == "mean":
            out: Dict = {}
            for key in sorted(part["rels"]):
                nbr, _ = part["rels"][key]
                out["|".join(key)] = jax.vmap(stages.gather_own)(
                    h_own[key[0]], nbr)  # [K, n_d, Kd, D]
            return out
        if plan.na.kind == "instance":
            outs: List = []
            for (nodes, _), types in zip(part["instances"], plan.metapaths):
                outs.append([
                    jax.vmap(stages.gather_own)(
                        h_own[types[j]], nodes[:, :, :, j])
                    for j in range(nodes.shape[3])
                ])  # per position: [K, n, I, D]
            return outs
        raise ValueError(
            f"no partitioned NA split for kind {plan.na.kind!r}")

    def _na_partitioned_merge(self, params: Dict, batch: Dict, h_own: Dict,
                              halos: Dict, pre):
        """Merge pass: where-select the exchanged halo rows into the
        pre-gathered owned operands, then run the untouched aggregation
        math.  Output bitwise equals ``_na_partitioned(params, batch,
        gather_halo(batch, h_own))``."""
        plan, cfg = self.plan, self.cfg
        part = batch["part"]
        t = plan.target
        act = _ACT[plan.na.activation]
        H = cfg.n_heads
        if plan.na.kind == "gat":
            n_own = part["feats"][t].shape[1]
            heads = lambda x: x.reshape(x.shape[0], x.shape[1], H, -1)
            hd = heads(h_own[t])  # [K, n, H, Dh] owned rows ARE the dsts
            hs_halo = heads(halos[t])  # [K, h_max, H, Dh]

            def one_part(hd_k, hh_k, nbr_k, mask_k, hno_k, eo_k):
                def one_path(pp, nn, mm, hno, eo):
                    hn = stages.gather_merge(hno, hh_k, nn, n_own)
                    e_tab_h = (hh_k * pp["a_src"]).sum(-1)  # [h_max, H]
                    e_nbr = stages.gather_merge(eo, e_tab_h, nn, n_own)
                    return stages.gat_aggregate_padded(
                        pp, hd_k, None, None, mm, hn=hn, e_nbr=e_nbr)

                return jax.vmap(one_path)(params["gat"], nbr_k, mask_k,
                                          hno_k, eo_k)

            z = jax.vmap(one_part)(hd, hs_halo, part["nbr"], part["mask"],
                                   pre["hn"], pre["e"])
            z = act(z)  # [K, P, n, H, Dh]
            z = z.reshape(z.shape[0], z.shape[1], z.shape[2], -1)
            return stages.shard(z, BATCH, None, None, None)  # [K, P, n, D]
        if plan.na.kind == "mean":
            if plan.n_layers > 1:
                out: Dict = {"__h__": {ty: h_own[ty]
                                       for ty in part["feats"]}}
            else:
                out = {"__h__": h_own[t]}
            for key in sorted(part["rels"]):
                s = key[0]
                n_own_s = part["feats"][s].shape[1]
                nbr, mask = part["rels"][key]
                hn = jax.vmap(
                    lambda ho, hl, nn: stages.gather_merge(
                        ho, hl, nn, n_own_s)
                )(pre["|".join(key)], halos[s], nbr)
                agg = jax.vmap(
                    lambda nn, mm, hh: stages.mean_aggregate_padded(
                        None, nn, mm, hn=hh)
                )(nbr, mask, hn)  # [K, n_d, D]
                out["|".join(key)] = agg @ params["w_rel"][key]
            return out
        if plan.na.kind == "instance":
            h_tgt = h_own[t]
            h_tgt = h_tgt.reshape(h_tgt.shape[0], h_tgt.shape[1], H, -1)
            outs: List[jax.Array] = []
            for p_i, (nodes, mask), types, pre_i in zip(params["att"],
                                                        part["instances"],
                                                        plan.metapaths, pre):
                k_, n, i, l = nodes.shape
                h_path = jnp.stack([
                    jax.vmap(
                        lambda ho, hl, nn, ty=types[j]: stages.gather_merge(
                            ho, hl, nn, part["feats"][ty].shape[1])
                    )(pre_i[j], halos[types[j]], nodes[:, :, :, j])
                    for j in range(l)
                ], axis=3)  # [K, n, I, L, D]
                h_path = h_path.reshape(k_, n, i, l, H, -1)
                enc = jax.vmap(stages.rotate_encoder)(h_path)
                z = jax.vmap(stages.instance_aggregate,
                             in_axes=(None, 0, 0, 0))(p_i, h_tgt, enc, mask)
                outs.append(act(z).reshape(k_, n, -1))  # [K, n, D]
            return outs
        raise ValueError(
            f"no partitioned NA split for kind {plan.na.kind!r}")

    # ------------------------------------------------------------------
    # Stage 4: Semantic Aggregation
    # ------------------------------------------------------------------
    def _rel_sum(self, params: Dict, h_own: Dict, z: Dict) -> Dict:
        """The rel_sum SA body shared by the single-table and partitioned
        flows: per type, sum the relation aggregates (Reduce) into the
        ``w_self`` self-loop.  ``h_own`` maps type -> its own feature rows
        (``[N_t, D]`` or ``[K, n_t, D]``); ``z`` the NA output dict keyed
        by ``"s|r|d"`` relation strings."""
        h_new: Dict = {}
        for t in sorted(h_own):
            acc = None
            for key, v in z.items():
                if key != "__h__" and key.split("|")[2] == t:
                    acc = v if acc is None else acc + v  # Reduce (sum)
            h_self = h_own[t] @ params["w_self"][t]
            h_new[t] = jax.nn.relu(h_self if acc is None else h_self + acc)
        return h_new

    def sa(self, params: Dict, batch: Dict, z):
        plan = self.plan
        if plan.partition is not None:
            return self._sa_partitioned(params, batch, z)
        if plan.sa.kind == "none":
            return z
        if plan.sa.kind == "rel_sum":
            return self._rel_sum(params, z["__h__"], z)
        # attention; sampled minibatches carry a row-validity mask so the
        # rung padding never shifts the semantic score means
        row_mask = batch.get("row_mask")
        if isinstance(z, tuple):  # fused NA→SA epilogue: (z, pass-1 scores)
            z_stack, wp = z
            if row_mask is not None:
                # the kernel's pass-1 mean ran over every row incl. the
                # rung pads; a pad row is a zero row (all-masked neighbor
                # lists aggregate to 0), so each contributes exactly
                # c = q·tanh(b) to the mean — remove them in closed form:
                # wp_masked = (wp·N − n_pad·c) / n_real.  n_pad == 0 (full
                # batches / exact rungs) leaves wp bitwise unchanged.
                sem = params["sem"]
                c = jnp.tanh(sem["b"]) @ sem["q"]
                n_real = jnp.maximum(row_mask.sum(), 1.0)
                n_pad = row_mask.shape[0] - row_mask.sum()
                wp = wp + n_pad * (wp - c) / n_real
            beta = jax.nn.softmax(wp)  # O(P) softmax
            # pass 2 (combine) is the only remaining full read of z
            return _kops().semantic_combine(z_stack, beta,
                                            use_pallas=plan.na.use_pallas)
        if plan.sa.stacked:
            z = stages.shard(z, *stages.HGNN_STAGE_SPECS["sa_stacked"])
            return semantics.semantic_attention(params["sem"], z, row_mask)
        return semantics.semantic_attention_list(params["sem"], z, row_mask)

    def _sa_partitioned(self, params: Dict, batch: Dict, z):
        """SA on the partition-local stacks.  Attention reduces per-partition
        score partials to the global masked mean (a [K, P] reduce is the only
        communication); rel_sum is fully partition-local."""
        plan = self.plan
        part = batch["part"]
        mask = part["own_mask"][plan.target]  # [K, n]
        if plan.sa.kind == "rel_sum":
            if plan.n_layers > 1:
                # every type updates (as in the unpartitioned rel_sum);
                # pad rows stay zero: zero feats -> zero aggregates -> relu(0)
                return self._rel_sum(params, z["__h__"], z)
            # single layer: __h__ is the owned target rows [K, n, D] only
            return self._rel_sum(params, {plan.target: z["__h__"]},
                                 z)[plan.target]
        # attention (HAN stacked [K, P, n, D]; MAGNN list of [K, n, D])
        if isinstance(z, list):
            z = jnp.stack(z, axis=1)  # [K, P, n, D]
        return semantics.semantic_attention_partitioned(
            params["sem"], z, mask)  # [K, n, D]

    # ------------------------------------------------------------------
    # head + forward
    # ------------------------------------------------------------------
    def head(self, params: Dict, z, batch: Dict = None) -> jax.Array:
        plan = self.plan
        w = params[plan.head.param]
        if plan.partition is not None:
            # SA already reduced to the owned target rows [K, n, D] (the
            # multi-layer rel_sum returns every type — select the target);
            # classify locally, then invert the ownership permutation back
            # to global node order (`inv` maps global row -> own-order slot).
            if isinstance(z, dict):
                z = z[plan.target]
            out = z @ w  # [K, n, C]
            flat = out.reshape(-1, out.shape[-1])
            return flat[batch["part"]["inv"]]
        if plan.head.kind == "select_linear":
            return z[plan.head.target] @ w
        return z @ w

    def forward(self, params: Dict, batch: Dict) -> jax.Array:
        """The L-layer loop: per-type feature tables are the carried state;
        layer 0 reads the prepared batch, hidden layers the previous
        handoff.  The partitioned flow re-exchanges the *updated* halo
        features every layer over the graph-invariant halo maps.

        Each stage runs under a ``jax.named_scope`` of its
        :meth:`stage_fns` name (``FP``, ``gather_halo``, ``NA``, ``SA``,
        ``head``; ``L{i}.``-prefixed when L > 1), so the compiled ops name
        their stage; the layer handoff is in no stage."""
        plan = self.plan
        state = out = None
        with jax.default_matmul_precision(MATMUL_PRECISION):
            for l, lp in enumerate(plan.layers):
                pre = f"L{l + 1}." if plan.n_layers > 1 else ""
                p_l = self._layer_params(params, l)
                with jax.named_scope(pre + "FP"):
                    h = (self.fp(params, batch) if l == 0
                         else self._fp_hidden(lp, p_l, state))
                if plan.partition is not None:
                    with jax.named_scope(pre + "gather_halo"):
                        h = self.gather_halo(batch, h)
                with jax.named_scope(pre + "NA"):
                    z = self.na(p_l, batch, h)
                with jax.named_scope(pre + "SA"):
                    out = self.sa(p_l, batch, z)
                if l + 1 < plan.n_layers:
                    state = self._handoff(lp, batch, h, out)
            with jax.named_scope("head"):
                return self.head(params, out, batch)

    # ------------------------------------------------------------------
    # the async stage-graph schedule (plan.schedule)
    # ------------------------------------------------------------------
    def _split_halo(self) -> bool:
        """Does the schedule split partitioned NA into own/halo passes?"""
        s = self.plan.schedule
        return (s is not None and s.overlap_halo
                and self.plan.partition is not None)

    def _split_metapaths(self) -> bool:
        """Does the schedule dispatch per-metapath NA stages?  Only where
        the serial path already loops metapaths (bucketed / csr GAT,
        MAGNN instances) — the stacked layout is ONE launch by design,
        and a single metapath has nothing to overlap."""
        plan, s = self.plan, self.plan.schedule
        return (s is not None and s.overlap_metapaths
                and plan.partition is None
                and len(plan.metapaths) > 1
                and ((plan.na.kind == "gat"
                      and plan.na.layout in ("csr", "bucketed"))
                     or plan.na.kind == "instance"))

    def _sa_entry(self, p_l: Dict, batch: Dict, z):
        """SA entry for the schedule driver: per-metapath NA stages hand
        SA a list; stacked-SA plans re-stack it here.  Activation already
        applied per metapath (elementwise) — stack-after-act is bitwise
        equal to the serial act-after-stack."""
        if self._split_metapaths() and self.plan.sa.stacked:
            z = jnp.stack(z)  # [P, N, D]
        return self.sa(p_l, batch, z)

    def schedule_edges(self) -> Dict[str, Tuple[str, ...]]:
        """The plan-derived dependency-edge table: stage name → the stages
        it must wait for, in topological order.  Purely declarative — the
        driver, the accounting, and the tests all read the same DAG.
        Nodes match the schedule's dispatch granularity: the partitioned
        split runs ``gather_halo`` (exchange only) and ``NA.own``
        concurrently, merging in ``NA``; the metapath split fans ``FP``
        out into ``NA.p{i}`` stages that join at ``SA``."""
        plan = self.plan
        edges: Dict[str, Tuple[str, ...]] = {}
        prev = None
        for l in range(plan.n_layers):
            pre = f"L{l + 1}." if plan.n_layers > 1 else ""
            edges[pre + "FP"] = (prev,) if prev else ()
            if plan.partition is not None:
                edges[pre + "gather_halo"] = (pre + "FP",)
                if self._split_halo():
                    edges[pre + "NA.own"] = (pre + "FP",)
                    edges[pre + "NA"] = (pre + "NA.own", pre + "gather_halo")
                else:
                    edges[pre + "NA"] = (pre + "gather_halo",)
                sa_deps: Tuple[str, ...] = (pre + "NA",)
            elif self._split_metapaths():
                names = [pre + f"NA.p{i}"
                         for i in range(len(plan.metapaths))]
                for nm in names:
                    edges[nm] = (pre + "FP",)
                sa_deps = tuple(names)
            else:
                edges[pre + "NA"] = (pre + "FP",)
                sa_deps = (pre + "NA",)
            edges[pre + "SA"] = sa_deps
            prev = pre + "SA"
        edges["head"] = (prev,)
        return edges

    def overlap_record(self) -> Dict:
        """Deterministic schedule counters (no walls): DAG size and the
        path-independent stage pairs — the concurrency the schedule can
        exploit.  Pinned by CI greps and gated at exact equality by the
        bench; the measured critical-path/exposure accounting lives in
        ``characterize.overlap_accounting``."""
        edges = self.schedule_edges()
        names = list(edges)
        anc: Dict[str, set] = {}
        for n in names:  # topological by construction
            a = set()
            for d in edges[n]:
                a.add(d)
                a |= anc[d]
            anc[n] = a
        pairs = [(a, b) for i, a in enumerate(names) for b in names[i + 1:]
                 if a not in anc[b] and b not in anc[a]]
        sched = self.plan.schedule
        return {
            "depth": sched.depth if sched is not None else 1,
            "stages": len(names),
            "edges": sum(len(d) for d in edges.values()),
            "concurrent_pairs": len(pairs),
            "overlapped_stages": len({s for p in pairs for s in p}),
            "pairs": [f"{a}|{b}" for a, b in pairs],
        }

    def _ovjit(self, key: str, fn):
        f = self._ov_jit.get(key)
        if f is None:
            f = self._ov_jit[key] = jax.jit(fn)
        return f

    def _walk_schedule(self, params: Dict, batch: Dict, emit):
        """Walk the stage DAG in topological order, dispatching each stage
        through ``emit(name, key, fn, args) -> value``.  ``name`` is the
        per-layer stage name (matches :meth:`schedule_edges`); ``key`` the
        jit-cache identity (layer-indexed — stage shapes repeat across
        calls, not across layers with different param trees).  Both the
        async driver and the characterization hook walk this one graph."""
        plan = self.plan
        n_l = plan.n_layers
        state = out = None
        for l, lp in enumerate(plan.layers):
            pre = f"L{l + 1}." if n_l > 1 else ""
            if l == 0:
                h = emit(pre + "FP", "FP0",
                         lambda p, b: self.fp(p, b), (params, batch))
            else:
                h = emit(pre + "FP", f"FP{l}",
                         lambda p, s, lp=lp, l=l: self._fp_hidden(
                             lp, self._layer_params(p, l), s),
                         (params, state))
            if plan.partition is not None:
                if self._split_halo():
                    # the exchange and the owned-rows pre-gather both
                    # depend only on FP — the window dispatches them
                    # back-to-back and they run concurrently
                    halos = emit(pre + "gather_halo", "halo_exchange",
                                 lambda b, hh: self.halo_exchange(b, hh),
                                 (batch, h))
                    pre_g = emit(pre + "NA.own", f"NA.own{l}",
                                 lambda p, b, hh, l=l:
                                 self._na_partitioned_own(
                                     self._layer_params(p, l), b, hh),
                                 (params, batch, h))
                    z = emit(pre + "NA", f"NA.merge{l}",
                             lambda p, b, hh, ha, pg, l=l:
                             self._na_partitioned_merge(
                                 self._layer_params(p, l), b, hh, ha, pg),
                             (params, batch, h, halos, pre_g))
                else:
                    h = emit(pre + "gather_halo", "gather_halo",
                             lambda b, hh: self.gather_halo(b, hh),
                             (batch, h))
                    z = emit(pre + "NA", f"NA{l}",
                             lambda p, b, hh, l=l: self.na(
                                 self._layer_params(p, l), b, hh),
                             (params, batch, h))
            elif self._split_metapaths():
                z = [emit(pre + f"NA.p{i}", f"NA.p{l}.{i}",
                          lambda p, b, hh, l=l, i=i: self._na_metapath(
                              self._layer_params(p, l), b, hh, i),
                          (params, batch, h))
                     for i in range(len(plan.metapaths))]
            else:
                z = emit(pre + "NA", f"NA{l}",
                         lambda p, b, hh, l=l: self.na(
                             self._layer_params(p, l), b, hh),
                         (params, batch, h))
            out = emit(pre + "SA", f"SA{l}",
                       lambda p, b, zz, l=l: self._sa_entry(
                           self._layer_params(p, l), b, zz),
                       (params, batch, z))
            if l + 1 < n_l:
                # host-level repackaging (slices are identities on the
                # own-only tables) — not a schedulable stage
                state = self._handoff(lp, batch, h, out)
        return emit("head", "head",
                    lambda p, b, oo: self.head(p, oo, b),
                    (params, batch, out))

    def forward_overlapped(self, params: Dict, batch: Dict) -> jax.Array:
        """``forward``'s layer loop re-expressed over the plan-derived
        stage DAG: each stage is its own jitted call; the host races ahead
        issuing dependents and blocks only when more than
        ``plan.schedule.depth`` stage results are in flight
        (``kernels.streaming.InflightWindow`` — the DMA double-buffer
        discipline at stage granularity), so JAX's async dispatch runs
        independent stages' device work concurrently.  Bit-exact vs the
        serial schedule: the split stages are pure row selections /
        elementwise rearrangements; depth=1 degrades to fully blocking
        dispatch.  Not itself jit-able (it *is* the dispatcher); the
        per-stage jits are cached on the executor, so repeated calls
        re-trace nothing."""
        from repro.kernels.streaming import InflightWindow

        sched = self.plan.schedule
        win = InflightWindow(sched.depth if sched is not None else 1)

        def emit(name, key, fn, args):
            return win.admit(name, self._ovjit(key, fn)(*args))

        with jax.default_matmul_precision(MATMUL_PRECISION):
            out = self._walk_schedule(params, batch, emit)
        win.drain()
        self.last_dispatch = {
            "dispatched": list(win.admitted),
            "max_inflight": win.max_inflight,
            "depth": win.depth,
        }
        return out

    # ------------------------------------------------------------------
    # per-stage characterization hooks
    # ------------------------------------------------------------------
    def stage_fns(self, params: Dict, batch: Dict) -> Dict[str, Tuple]:
        """Jitted per-stage callables chained on concrete intermediates —
        the separate jit per stage mirrors DGL's separate kernel launches
        and exposes the NA→SA barrier (paper Fig. 5c).

        Single-layer plans keep the historical unprefixed stage names
        (``FP``/``gather_halo``/``NA``/``SA``/``head``); an L-layer stack
        prefixes every per-layer stage with ``L{i}.`` (1-based), so the
        characterization handbook can show depth scaling per layer."""
        plan = self.plan
        n_l = plan.n_layers
        fns: Dict[str, Tuple] = {}
        state = out = None
        # one jitted exchange shared by every layer (same computation on
        # same-shaped tables — a per-layer lambda would recompile it L times)
        gh = (jax.jit(lambda hh: self.gather_halo(batch, hh))
              if plan.partition is not None else None)
        for l, lp in enumerate(plan.layers):
            pre = f"L{l + 1}." if n_l > 1 else ""
            if l == 0:
                fp = jax.jit(lambda p: self.fp(p, batch))
                fp_args: Tuple = (params,)
            else:
                fp = jax.jit(lambda p, s, lp=lp, l=l: self._fp_hidden(
                    lp, self._layer_params(p, l), s))
                fp_args = (params, state)
            h = fp(*fp_args)
            fns[pre + "FP"] = (fp, fp_args)
            if gh is not None:
                fns[pre + "gather_halo"] = (gh, (h,))
                h = gh(h)
            na = jax.jit(lambda p, hh, l=l: self.na(
                self._layer_params(p, l), batch, hh))
            z = na(params, h)
            fns[pre + "NA"] = (na, (params, h))
            sa = jax.jit(lambda p, zz, l=l: self.sa(
                self._layer_params(p, l), batch, zz))
            out = sa(params, z)
            fns[pre + "SA"] = (sa, (params, z))
            if l + 1 < n_l:
                state = self._handoff(lp, batch, h, out)
        head = jax.jit(lambda p, oo: self.head(p, oo, batch))
        fns["head"] = (head, (params, out))
        return fns

    def overlap_stage_fns(self, params: Dict, batch: Dict) -> Dict[str, Tuple]:
        """Overlap-granular analogue of :meth:`stage_fns`: one jitted
        callable per node of :meth:`schedule_edges`, chained on concrete
        intermediates.  The benches time each stage's wall and feed the
        DAG + walls to ``characterize.overlap_accounting`` (critical-path
        vs serial-sum, per-stage exposure)."""
        fns: Dict[str, Tuple] = {}

        def emit(name, key, fn, args):
            f = jax.jit(fn)
            fns[name] = (f, args)
            return f(*args)

        self._walk_schedule(params, batch, emit)
        return fns

    def stage_records(self, params: Dict, batch: Dict,
                      n_chips: int = 1, sample_meta: Dict = None) -> Dict:
        """Per-stage characterization: stage name → FLOPs / HBM bytes /
        roofline terms via ``core/characterize.py``, from the exact stage
        functions the executor serves.  ``total`` is the stage-additive sum
        (the fully-jitted forward may fuse across stage boundaries, so the
        per-stage attribution is the meaningful decomposition).

        ``sample_meta`` (request-path serving): the sampler's
        ``SampledBatch.meta``; it becomes the SAMPLE stage — the paper
        taxonomy's Subgraph Build, realized as the neighbor-sampling gather
        — as the first record, with its traffic kept out of the
        compiled-stage ``total``."""
        from repro.core.characterize import (analyze_hlo_text,
                                             partition_traffic,
                                             residency_record, roofline)

        fns = self.stage_fns(params, batch)
        recs: Dict[str, Dict] = {}
        if sample_meta is not None:
            recs["SAMPLE"] = sample_meta
        for name, (fn, args) in fns.items():
            rep = analyze_hlo_text(fn.lower(*args).compile().as_text())
            recs[name] = {
                "flops": rep["total_flops"],
                "hbm_bytes": rep["total_hbm_bytes"],
                "flops_by_class": rep["flops_by_class"],
                "hbm_bytes_by_class": rep["hbm_bytes_by_class"],
                "roofline": roofline(rep, n_chips, 0.0),
            }
        res = batch.get("residency")
        rr = None
        if res is not None:
            # residency accounting: the HLO walker charges every gather at
            # its structural size, so the cache's effect — hot rows served
            # from the resident section instead of re-read from HBM — is
            # applied from the deterministic hit counters.  The hot set is
            # layer-invariant, so only the first cached stage pays the
            # cache fill (HiHGNN inter-layer reuse); hot-halo savings land
            # on the gather_halo records, NA savings on the NA records.
            cached = [n for n in fns if n.endswith(
                "gather_halo" if self.plan.partition is not None else "NA")]
            rr = residency_record(res["counters"], 4 * self.cfg.hidden,
                                  layers=len(cached))
            for i, name in enumerate(cached):
                saved = rr["bytes_saved_per_layer"] - (
                    rr["fill_bytes"] if i == 0 else 0)
                recs[name]["residency_bytes_saved"] = saved
                recs[name]["hit_rate"] = rr["hit_rate"]
                recs[name]["hbm_bytes"] = max(
                    recs[name]["hbm_bytes"] - saved, 0)
        total = {  # compiled stages only — SAMPLE is a host-side gather
            "flops": sum(recs[n]["flops"] for n in fns),
            "hbm_bytes": sum(recs[n]["hbm_bytes"] for n in fns),
        }
        out = {"stages": recs, "total": total}
        if rr is not None:
            out["residency"] = rr
        if self.plan.schedule is not None:
            # schedule accounting: the DAG's deterministic counters (the
            # measured critical-path walls ride the overlap bench, not the
            # HLO records)
            out["overlap"] = self.overlap_record()
        gh_names = [n for n in fns if n.endswith("gather_halo")]
        if gh_names:
            # the communication stage's paper-facing metrics: exchanged halo
            # rows/bytes and the partitioner's cut, from the batch metadata
            # plus the actual per-type feature shapes entering the exchange.
            # Every layer re-exchanges the updated features over the same
            # graph-invariant halo maps, so each per-layer stage gets its
            # own record and the summary reports halo-bytes × L.
            for name in gh_names:
                tr = partition_traffic(batch["part"], fns[name][1][0])
                recs[name]["halo_bytes"] = tr["halo_bytes"]
                recs[name]["cut_edges"] = tr["cut_edges"]
            out["partition"] = partition_traffic(
                batch["part"], fns[gh_names[0]][1][0], layers=len(gh_names))
            if rr is not None:
                # hot halo rows skip the exchange on every layer's re-run
                out["partition"]["halo_bytes_saved_total"] = (
                    rr["bytes_saved_total"])
        return out


class PlannedModel:
    """Base for the model zoo: host-side ``prepare()`` + a ``plan()``
    builder; every device-side stage delegates to the shared executor."""

    def __init__(self, cfg):
        self.cfg = cfg

    def plan(self) -> StagePlan:
        raise NotImplementedError

    @property
    def executor(self) -> StageGraphExecutor:
        ex = self.__dict__.get("_executor")
        if ex is None:
            ex = self.__dict__["_executor"] = StageGraphExecutor(
                self.plan(), self.cfg)
        return ex

    def prepare(self, hg) -> Dict:
        raise NotImplementedError

    def _maybe_partition(self, batch: Dict) -> Dict:
        """End-of-``prepare`` finalize hook: compute the residency hot sets
        from the *unpartitioned* tables (degree ordering is a global-graph
        property), rewrite the batch into the partitioned layout when the
        plan declares one (``repro.dist.partition``), then apply/attach the
        residency tables — single-device batches get their index tables
        remapped into the cache-extended pool, partitioned batches get the
        hot-halo overlay maps."""
        plan = self.plan()
        tables = None
        if plan.residency is not None:
            from repro.core import residency as _rsd

            tables = _rsd.build_tables(plan, batch)
        if plan.partition is not None:
            from repro.dist.partition import partition_batch

            batch = partition_batch(plan, batch)
            if tables is not None:
                batch["residency"] = _rsd.partition_overlay(tables, batch)
            return batch
        if tables is not None:
            batch = _rsd.apply(plan, batch, tables)
        return batch

    def init(self, rng: jax.Array, batch: Dict) -> Dict:
        return self.executor.init(rng, batch)

    def fp(self, params: Dict, batch: Dict):
        return self.executor.fp(params, batch)

    def na(self, params: Dict, batch: Dict, h):
        return self.executor.na(params, batch, h)

    def sa(self, params: Dict, batch: Dict, z):
        return self.executor.sa(params, batch, z)

    def head(self, params: Dict, z, batch: Dict = None):
        return self.executor.head(params, z, batch)

    def forward(self, params: Dict, batch: Dict) -> jax.Array:
        return self.executor.forward(params, batch)

    def forward_overlapped(self, params: Dict, batch: Dict) -> jax.Array:
        return self.executor.forward_overlapped(params, batch)

    def stage_records(self, params: Dict, batch: Dict, n_chips: int = 1):
        return self.executor.stage_records(params, batch, n_chips=n_chips)
