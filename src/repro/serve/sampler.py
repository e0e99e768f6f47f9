"""Request-path neighbor sampling (serving-side Subgraph Build).

Serving traffic arrives as requests — "classify these target vertices, now"
— not as a full-graph forward.  :class:`HGNNSampler` extracts, for a set of
target vertices, the k-hop / per-metapath neighborhood of the graph and
relabels it into the *same* device layouts the stage-graph executor already
dispatches on (stacked ``[P, N, K]`` metapath tables for HAN, per-relation
padded tables for RGCN, instance tables for MAGNN, flat edge lists for
GCN), so the executor's arms — baseline / fused / bucketed / epilogue,
L ≥ 1 — run unchanged on the minibatch.

Two properties make this serving-grade rather than a toy:

* **Shape bucketing.**  Every sampled batch is padded to a rung of the
  plan's ``SampleSpec.ladder`` — a small fixed set of ``(t_cap, f_cap)``
  shapes.  The jitted forward compiles once per rung at warmup
  (:meth:`dummy_batch`) and never again: jax caches on pytree structure +
  shapes, and both are rung-determined.  Pad rows carry all-masked neighbor
  lists (the padded aggregators emit exact zeros for them) and the batch's
  ``row_mask`` keeps them out of the semantic-attention score means.

* **Parity by identity.**  The sampler precomputes the full-graph tables
  with *exactly* the model ``prepare()``'s RNG stream (same seed, same
  build-call order).  Whenever a rung's clamped cap covers a whole node
  type, that type is relabeled by the identity and its tables are reused
  verbatim — so a minibatch over *all* targets with fan-out ≥ max degree is
  bit-exact against the full-graph forward (the parity rows in
  ``tests/test_stage_pipeline.py``).

Fan-out caps: per hop, each row keeps the first ``min(fanout, K_table)``
entries of its precomputed padded row (deterministic; the table itself was
degree-capped with the model's RNG).  Overflowing a rung truncates the
*frontier*, farthest hop first — never the targets — and reports the count.

Feature rows never cross the host link per batch: the raw per-type feature
tables the model's gathers read go on the device once, at construction, and
each batch uploads only int32 row ids; the rows are gathered there
(:meth:`_TypeTable.rows`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metapath as mp
from repro.core.hgraph import HeteroGraph
from repro.core.plan import StagePlan
from repro.serve.spans import span


@dataclasses.dataclass
class SampledBatch:
    """One relabeled, rung-padded minibatch plus its host-side metadata."""

    batch: Dict  # device batch for StageGraphExecutor.forward
    target_ids: np.ndarray  # [n_targets] global ids, request order
    target_rows: np.ndarray  # [n_targets] local row in the logits table
    rung: Tuple[int, int]
    rung_index: int
    local: Dict[str, np.ndarray]  # type -> [n_real] local->global id map
    # the traffic record (rung, targets, frontier rows and bytes, truncation)
    # and the phases' host seconds and uploaded bytes
    meta: Dict

    @property
    def n_targets(self) -> int:
        return len(self.target_ids)


@jax.jit
def _take_rows(table: jax.Array, ids: jax.Array) -> jax.Array:
    """Rows ``ids`` of a device-resident table; an id past its end reads as
    a row of exact zeros."""
    return jnp.take(table, ids, axis=0, mode="fill", fill_value=0)


class _TypeTable:
    """Per-type local vertex table: [targets | frontier (hop order) | pads].

    ``identity`` short-circuits the relabeling when the rung cap covers the
    whole type — local ids == global ids and downstream index tables are
    reused verbatim (the parity path).  ``gathered_bytes`` counts the
    feature bytes :meth:`rows` gathered on the device.
    """

    def __init__(self, n_type: int, cap: int, targets: np.ndarray,
                 frontier: np.ndarray):
        self.n_type = n_type
        self.cap = cap
        self.identity = cap == n_type
        # a target requested twice in one batch (two slots of a serving
        # step may ask for the same vertex) keeps one row, in first-request
        # order, so relabeling stays a bijection
        _, first = np.unique(targets, return_index=True)
        self.n_targets = len(first)
        if self.identity:
            self.ids = np.arange(n_type, dtype=np.int64)
            self.truncated = 0
        else:
            ids = np.concatenate([targets[np.sort(first)], frontier])
            self.truncated = max(0, len(ids) - cap)
            if self.truncated:
                # never drop targets: the engine sizes chunks to t_cap and
                # the frontier is hop-ordered, so the tail is the far rim
                assert self.n_targets <= cap, (
                    f"targets ({self.n_targets}) overflow the rung cap ({cap})")
                ids = ids[:cap]
            self.ids = ids
        self.n_real = len(self.ids)
        self.gathered_bytes = 0
        self._lut = np.full(n_type, -1, np.int64)
        self._lut[self.ids] = np.arange(self.n_real)

    def relabel(self, ids: np.ndarray) -> np.ndarray:
        """Global -> local; dropped (truncated) ids come back as -1."""
        return self._lut[ids]

    def rows(self, resident: jax.Array, up: Callable) -> jax.Array:
        """The local feature table on the device, zero rows past ``n_real``:
        the resident table itself under identity, else its rows gathered on
        the device by ids uploaded through ``up`` (pads point past the end)."""
        if self.identity:
            return resident
        ids = np.full(self.cap, self.n_type, np.int32)
        ids[: self.n_real] = self.ids
        self.gathered_bytes += (self.cap * resident.shape[1]
                                * resident.dtype.itemsize)
        return _take_rows(resident, up(ids))


class HGNNSampler:
    """Neighbor sampler for one (plan, graph) pair.

    ``plan.sample`` must be set (models declare it when ``cfg.fanout >= 1``).
    The constructor precomputes the full-graph index tables with the model
    ``prepare()``'s exact RNG stream and puts on the device, once, every
    feature table the model's gathers read (``resident``: the target type
    for HAN and GCN, every type for RGCN and MAGNN); :meth:`sample` then
    extracts / relabels / rung-pads per request batch in numpy, uploads the
    index tables and row ids, and gathers the feature rows on the device.
    """

    def __init__(self, plan: StagePlan, cfg, hg: HeteroGraph):
        if plan.sample is None:
            raise ValueError(
                f"{plan.model}'s plan has no SampleSpec — set cfg.fanout >= 1")
        if plan.na.layout == "csr" and plan.na.kind != "gcn":
            raise ValueError(
                "request-path sampling needs a padded NA layout (the csr "
                "edge lists have no shape-stable minibatch form): set "
                "cfg.fused=True")
        self.plan = plan
        self.cfg = cfg
        self.hg = hg
        self.spec = plan.sample
        self.ladder = tuple(self.spec.ladder)
        self.target = plan.target
        self.n_target_type = hg.node_counts[self.target]
        self.feat_dims = {t: hg.feat_dim(t) for t in hg.features}
        self._build_full_tables()
        read = (hg.features if plan.na.kind in ("mean", "instance")
                else [self.target])
        self.resident = {t: jax.device_put(np.asarray(hg.features[t],
                                                      np.float32))
                         for t in read}

    # ------------------------------------------------------------------
    # full-graph tables (prepare()'s exact RNG stream)
    # ------------------------------------------------------------------
    def _build_full_tables(self) -> None:
        cfg, plan = self.cfg, self.plan
        rng = np.random.default_rng(cfg.seed)
        kind = plan.na.kind
        if kind == "gat":  # HAN
            self.k_eff = min(self.spec.fanout, cfg.max_degree)
            self.subs = [
                mp.build_padded(self.hg, list(p), cfg.max_degree, rng)
                for p in plan.metapaths
            ]
            if plan.na.layout == "bucketed":
                self.full_buckets = [
                    mp.bucket_padded(s, cfg.degree_buckets) for s in self.subs
                ]
        elif kind == "mean":  # RGCN — replicate prepare()'s loop + RNG order
            self.k_eff = min(self.spec.fanout, cfg.max_degree)
            self.rel_keys = sorted(self.hg.relations.keys())
            self.rel_tables: Dict = {}
            for key in self.rel_keys:
                adj_in = self.hg.relations[key].T.tocsr()
                nbr = np.zeros((adj_in.shape[0], cfg.max_degree), np.int32)
                mask = np.zeros((adj_in.shape[0], cfg.max_degree), np.float32)
                indptr, indices = adj_in.indptr, adj_in.indices
                for u in range(adj_in.shape[0]):
                    nbrs = indices[indptr[u]: indptr[u + 1]]
                    if len(nbrs) > cfg.max_degree:
                        nbrs = rng.choice(nbrs, cfg.max_degree, replace=False)
                    nbr[u, : len(nbrs)] = nbrs
                    mask[u, : len(nbrs)] = 1.0
                self.rel_tables[key] = (nbr, mask)
            if plan.na.layout == "bucketed":
                self.full_buckets = {
                    key: mp.bucket_padded(
                        mp.PaddedSubgraph(nbr, mask, [key[0], key[2]]),
                        cfg.degree_buckets)
                    for key, (nbr, mask) in self.rel_tables.items()
                }
        elif kind == "instance":  # MAGNN
            self.k_eff = min(self.spec.fanout, cfg.max_instances)
            self.insts = [
                mp.enumerate_instances(self.hg, list(p), cfg.max_instances,
                                       rng=rng)
                for p in plan.metapaths
            ]
        elif kind == "gcn":
            csr = mp.build_csr(self.hg, [self.target, self.target])
            self.csr = csr
            deg = np.diff(csr.indptr)
            self.max_deg = int(deg.max()) if len(deg) else 1
            self.k_eff = min(self.spec.fanout, self.max_deg)
        else:
            raise ValueError(f"unknown NA kind {kind!r}")

    # ------------------------------------------------------------------
    # rung selection
    # ------------------------------------------------------------------
    def _clamp(self, f_cap: int, t: str) -> int:
        return min(f_cap, self.hg.node_counts[t])

    def pick_rung(self, n_targets: int, need: Dict[str, int],
                  max_rung: Optional[int] = None) -> int:
        """Smallest rung fitting the targets and every type's real rows;
        overflow falls through to the largest allowed rung (frontier
        truncation).  ``max_rung`` clamps the choice — the serve engine's
        degradation controller passes it to fan work *down* the ladder
        under pressure while staying inside the warmed rung set."""
        ladder = self.spec.ladder
        hi = (len(ladder) - 1 if max_rung is None
              else min(int(max_rung), len(ladder) - 1))
        for i, (t_cap, f_cap) in enumerate(ladder[: hi + 1]):
            if n_targets > t_cap:
                continue
            if all(n <= self._clamp(f_cap, ty) for ty, n in need.items()):
                return i
        if n_targets > max(t for t, _ in ladder[: hi + 1]):
            raise ValueError(
                f"{n_targets} targets overflow the ladder's largest "
                f"allowed t_cap {max(t for t, _ in ladder[: hi + 1])} — "
                "chunk requests (the serve engine's slot_targets does this)")
        return hi

    # ------------------------------------------------------------------
    # sampling entry points
    # ------------------------------------------------------------------
    def sample(self, targets: np.ndarray, rung: Optional[int] = None,
               max_rung: Optional[int] = None) -> SampledBatch:
        """Expand the targets' frontier and choose a rung, then gather the
        local tables: each index array put on the device as soon as it is
        built (:func:`_upload`), the feature rows gathered on the device
        from the resident tables — each phase under its span
        (``repro.serve.spans``), timed into ``meta``.  ``gather_s`` leaves
        out the uploads nested in it; ``resident_gather_bytes`` counts the
        feature bytes gathered on the device."""
        targets = np.asarray(targets, np.int64).reshape(-1)
        if len(targets) and (targets.min() < 0
                             or targets.max() >= self.n_target_type):
            raise ValueError(f"target ids out of range for type "
                             f"{self.target!r} ({self.n_target_type} nodes)")
        kind = self.plan.na.kind
        rec: Dict = {"upload_s": 0.0, "upload_bytes": 0}
        up = functools.partial(_upload, rec=rec)
        with span("hgnn.sample"):
            with span("hgnn.sample.expand", rec, "expand_s"):
                fr = getattr(self, f"_expand_{kind}")(targets)
                tgts = {t: targets if t == self.target
                        else np.zeros(0, np.int64) for t in fr}
                need = {t: len(tgts[t]) + len(fr[t]) for t in fr}
                rung_i = (self.pick_rung(len(targets), need, max_rung)
                          if rung is None else rung)
            with span("hgnn.sample.gather", rec, "gather_s"):
                f_cap = self.spec.ladder[rung_i][1]
                tables = {t: _TypeTable(self.hg.node_counts[t],
                                        self._clamp(f_cap, t), tgts[t], fr[t])
                          for t in fr}
                batch = getattr(self, f"_gather_{kind}")(tables, up)
                tt = tables[self.target]
                target_rows = (targets.copy() if tt.identity
                               else tt.relabel(targets))
            rec["gather_s"] -= rec["upload_s"]
            rec["resident_gather_bytes"] = sum(
                tb.gathered_bytes for tb in tables.values())
        return SampledBatch(
            batch=batch,
            target_ids=targets,
            target_rows=target_rows,
            rung=tuple(self.spec.ladder[rung_i]),
            rung_index=rung_i,
            local={t: tb.ids for t, tb in tables.items()},
            meta={**self._meta(rung_i, targets, tables), **rec},
        )

    def dummy_batch(self, rung: int) -> SampledBatch:
        """An all-pad batch at the rung's exact shapes — warmup compiles the
        jitted forward once per rung so serving never recompiles."""
        return self.sample(np.zeros(0, np.int64), rung=rung)

    # ------------------------------------------------------------------
    # shared pieces
    # ------------------------------------------------------------------
    def _frontier_order(self, hop_sets: List[np.ndarray],
                        exclude: np.ndarray) -> np.ndarray:
        """Frontier ids in (hop, id) order, minus ``exclude`` — the
        truncation order drops the farthest rim first."""
        seen = set(exclude.tolist())
        out: List[int] = []
        for ids in hop_sets:
            for g in np.unique(ids).tolist():
                if g not in seen:
                    seen.add(g)
                    out.append(g)
        return np.asarray(out, np.int64)

    def _meta(self, rung_i: int, targets: np.ndarray,
              tables: Dict[str, _TypeTable]) -> Dict:
        frontier_rows = {
            t: int(tb.n_real - (tb.n_targets if t == self.target else 0))
            for t, tb in tables.items()
        }
        frontier_bytes = sum(
            rows * self.feat_dims[t] * 4 for t, rows in frontier_rows.items())
        return {
            "model": self.plan.model,
            "rung": tuple(self.spec.ladder[rung_i]),
            "rung_index": rung_i,
            "n_targets": int(len(targets)),
            "frontier_rows": int(sum(frontier_rows.values())),
            "frontier_bytes": int(frontier_bytes),
            "truncated_rows": int(sum(tb.truncated for tb in tables.values())),
            "fanout": int(self.spec.fanout),
        }

    @staticmethod
    def _row_mask(table: _TypeTable) -> np.ndarray:
        m = np.zeros(table.cap, np.float32)
        m[: table.n_real] = 1.0
        return m

    # ------------------------------------------------------------------
    # HAN — stacked / bucketed metapath tables (target->target graphs)
    # ------------------------------------------------------------------
    def _expand_gat(self, targets: np.ndarray) -> Dict[str, np.ndarray]:
        """Per-hop frontier over the union of the metapath graphs; hop
        count = n_layers (each layer re-aggregates the same graphs)."""
        k = self.k_eff
        hop_sets: List[np.ndarray] = []
        cur = np.unique(targets)
        known = set(cur.tolist())
        for _ in range(self.plan.n_layers):
            nxt: List[np.ndarray] = []
            for sub in self.subs:
                nbr = sub.nbr[cur, :k]
                msk = sub.mask[cur, :k] > 0
                nxt.append(np.unique(nbr[msk]).astype(np.int64))
            new = (np.unique(np.concatenate(nxt)) if nxt
                   else np.zeros(0, np.int64))
            new = np.asarray([g for g in new.tolist() if g not in known],
                             np.int64)
            if len(new) == 0:
                break
            hop_sets.append(new)
            known.update(new.tolist())
            cur = new
        return {self.target: self._frontier_order(hop_sets, targets)}

    def _gather_gat(self, tables: Dict[str, _TypeTable], up: Callable) -> Dict:
        cfg, plan = self.cfg, self.plan
        k = self.k_eff
        table = tables[self.target]
        batch: Dict = {
            "feats": {self.target: table.rows(self.resident[self.target],
                                              up)},
            "feat_dims": {self.target: self.feat_dims[self.target]},
            "n_nodes": table.cap,
            "row_mask": up(self._row_mask(table)),
        }
        if plan.na.layout == "bucketed":
            batch["buckets"] = [[tuple(map(up, rnm))
                                 for rnm in self._local_buckets(b, table, k)]
                                for b in self.full_buckets]
        else:  # stacked
            if table.identity and k == cfg.max_degree:
                nbr, mask = mp.stack_padded(self.subs)
            else:
                locs = [self._local_padded(s.nbr[:, :k], s.mask[:, :k], table,
                                           table)
                        for s in self.subs]
                nbr, mask = mp.stack_padded([
                    mp.PaddedSubgraph(n, m, list(p))
                    for (n, m), p in zip(locs, plan.metapaths)
                ])
            batch["nbr"] = up(nbr)
            batch["mask"] = up(mask)
        return batch

    def _local_padded(self, nbr: np.ndarray, mask: np.ndarray,
                      dst: _TypeTable, src: _TypeTable,
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """Slice a full padded table to ``dst``'s local rows and relabel the
        entries into ``src``'s local ids; entries outside the local source
        set (or rung pads) mask out."""
        rows_g = dst.ids
        sub_n = nbr[rows_g]  # [n_real, K]
        sub_m = mask[rows_g].copy()
        loc = src.relabel(sub_n.reshape(-1)).reshape(sub_n.shape)
        sub_m[loc < 0] = 0.0
        loc = np.where(loc < 0, 0, loc)
        out_n = np.zeros((dst.cap, nbr.shape[1]), np.int32)
        out_m = np.zeros((dst.cap, nbr.shape[1]), np.float32)
        out_n[: len(rows_g)] = loc
        out_m[: len(rows_g)] = sub_m
        return out_n, out_m

    def _local_buckets(self, full: mp.DegreeBuckets, table: _TypeTable,
                       k: int) -> List[Tuple[np.ndarray, np.ndarray,
                                             np.ndarray]]:
        """Rung-shaped degree buckets: full-graph caps (static), every
        bucket padded to ``table.cap`` rows with out-of-range pad row_ids
        (the scatter drops them).  Identity + full fan-out reuses the full
        tables verbatim — the bucketed parity path."""
        if table.identity and k >= max(n.shape[1] for n in full.nbr):
            return [(full.row_ids[i], full.nbr[i], full.mask[i])
                    for i in range(full.n_buckets)]
        # rebuild the full padded view, then re-bin local rows by the full
        # caps so bucket shapes stay rung-static
        caps = [n.shape[1] for n in full.nbr]
        n_full = full.n_nodes
        nbr_f = np.zeros((n_full, max(caps)), np.int32)
        mask_f = np.zeros((n_full, max(caps)), np.float32)
        for i in range(full.n_buckets):
            rows, cap = full.row_ids[i], caps[i]
            nbr_f[rows, :cap] = full.nbr[i]
            mask_f[rows, :cap] = full.mask[i]
        kk = min(k, max(caps))
        loc_n, loc_m = self._local_padded(nbr_f[:, :kk], mask_f[:, :kk],
                                          table, table)
        deg = loc_m.sum(axis=1)
        out = []
        assigned = np.zeros(table.cap, bool)
        for cap in caps:
            cap_k = min(cap, kk)
            rows = np.flatnonzero(~assigned & (deg <= cap_k)
                                  & (np.arange(table.cap) < table.n_real))
            assigned[rows] = True
            row_ids = np.full(table.cap, table.cap, np.int32)  # OOB pads
            row_ids[: len(rows)] = rows
            b_n = np.zeros((table.cap, cap_k), np.int32)
            b_m = np.zeros((table.cap, cap_k), np.float32)
            b_n[: len(rows)] = loc_n[rows, :cap_k]
            b_m[: len(rows)] = loc_m[rows, :cap_k]
            out.append((row_ids, b_n, b_m))
        return out

    # ------------------------------------------------------------------
    # RGCN — per-relation padded (or bucketed) tables, typed k-hop ball
    # ------------------------------------------------------------------
    def _expand_mean(self, targets: np.ndarray) -> Dict[str, np.ndarray]:
        k = self.k_eff
        # typed frontier expansion: per hop, every relation (s, r, d) pulls
        # the in-neighbors (type s) of the currently-needed rows of type d
        per_type_hops: Dict[str, List[np.ndarray]] = {
            t: [] for t in self.hg.node_counts}
        known: Dict[str, set] = {t: set() for t in self.hg.node_counts}
        cur: Dict[str, np.ndarray] = {
            t: np.zeros(0, np.int64) for t in self.hg.node_counts}
        cur[self.target] = np.unique(targets)
        known[self.target].update(cur[self.target].tolist())
        for _ in range(self.plan.n_layers):
            nxt: Dict[str, List[np.ndarray]] = {
                t: [] for t in self.hg.node_counts}
            for key in self.rel_keys:
                s, _, d = key
                rows = cur[d]
                if len(rows) == 0:
                    continue
                nbr, mask = self.rel_tables[key]
                sub_n, sub_m = nbr[rows, :k], mask[rows, :k] > 0
                nxt[s].append(np.unique(sub_n[sub_m]).astype(np.int64))
            new_cur: Dict[str, np.ndarray] = {}
            for t in self.hg.node_counts:
                cand = (np.unique(np.concatenate(nxt[t])) if nxt[t]
                        else np.zeros(0, np.int64))
                new = np.asarray(
                    [g for g in cand.tolist() if g not in known[t]], np.int64)
                if len(new):
                    per_type_hops[t].append(new)
                    known[t].update(new.tolist())
                new_cur[t] = new
            cur = new_cur
            if not any(len(v) for v in cur.values()):
                break
        return {t: self._frontier_order(
                    per_type_hops[t],
                    targets if t == self.target else np.zeros(0, np.int64))
                for t in self.hg.node_counts}

    def _gather_mean(self, tables: Dict[str, _TypeTable], up: Callable,
                     ) -> Dict:
        cfg, plan = self.cfg, self.plan
        k = self.k_eff
        batch: Dict = {
            "feats": {t: tables[t].rows(self.resident[t], up)
                      for t in self.hg.features},
            "counts": {t: tables[t].cap for t in self.hg.node_counts},
            "feat_dims": dict(self.feat_dims),
            "rels": {},
        }
        for key in self.rel_keys:
            s, _, d = key
            if plan.na.layout == "bucketed":
                batch["rels"][key] = [
                    tuple(map(up, rnm)) for rnm in self._local_buckets_rel(
                        key, tables[d], tables[s], k)]
            else:
                nbr, mask = self.rel_tables[key]
                if (tables[d].identity and tables[s].identity
                        and k == cfg.max_degree):
                    batch["rels"][key] = (up(nbr), up(mask))
                else:
                    batch["rels"][key] = tuple(map(up, self._local_padded(
                        nbr[:, :k], mask[:, :k], tables[d], tables[s])))
        return batch

    def _local_buckets_rel(self, key, dst: _TypeTable, src: _TypeTable,
                           k: int) -> List[Tuple[np.ndarray, np.ndarray,
                                                 np.ndarray]]:
        full = self.full_buckets[key]
        if (dst.identity and src.identity
                and k >= max(n.shape[1] for n in full.nbr)):
            return [(full.row_ids[i], full.nbr[i], full.mask[i])
                    for i in range(full.n_buckets)]
        caps = [n.shape[1] for n in full.nbr]
        nbr, mask = self.rel_tables[key]
        kk = min(k, max(caps))
        loc_n, loc_m = self._local_padded(nbr[:, :kk], mask[:, :kk], dst, src)
        deg = loc_m.sum(axis=1)
        out = []
        assigned = np.zeros(dst.cap, bool)
        for cap in caps:
            cap_k = min(cap, kk)
            rows = np.flatnonzero(~assigned & (deg <= cap_k)
                                  & (np.arange(dst.cap) < dst.n_real))
            assigned[rows] = True
            row_ids = np.full(dst.cap, dst.cap, np.int32)  # OOB pads drop
            row_ids[: len(rows)] = rows
            b_n = np.zeros((dst.cap, cap_k), np.int32)
            b_m = np.zeros((dst.cap, cap_k), np.float32)
            b_n[: len(rows)] = loc_n[rows, :cap_k]
            b_m[: len(rows)] = loc_m[rows, :cap_k]
            out.append((row_ids, b_n, b_m))
        return out

    # ------------------------------------------------------------------
    # MAGNN — instance tables; frontier = instance node sets
    # ------------------------------------------------------------------
    def _expand_instance(self, targets: np.ndarray) -> Dict[str, np.ndarray]:
        plan = self.plan
        i_cap = self.k_eff  # instances per target (the MAGNN fan-out knob)
        # target-type rows that need REAL instance rows: the requested
        # targets plus, per extra layer, the target-type nodes appearing in
        # already-kept instances (layer l's gathers read layer l-1's
        # updated tables)
        rows = np.unique(targets)
        known = set(rows.tolist())
        tgt_hops: List[np.ndarray] = []
        cur = rows
        for _ in range(plan.n_layers - 1):
            nxt: List[np.ndarray] = []
            for ib, p in zip(self.insts, plan.metapaths):
                nodes = ib.nodes[cur, :i_cap]  # [n, I, L]
                msk = ib.mask[cur, :i_cap] > 0
                for j, ty in enumerate(p):
                    if ty == self.target:
                        nxt.append(np.unique(nodes[:, :, j][msk])
                                   .astype(np.int64))
            cand = (np.unique(np.concatenate(nxt)) if nxt
                    else np.zeros(0, np.int64))
            new = np.asarray([g for g in cand.tolist() if g not in known],
                             np.int64)
            if len(new) == 0:
                break
            tgt_hops.append(new)
            known.update(new.tolist())
            cur = new
        inst_rows = (np.concatenate([np.unique(targets)] + tgt_hops)
                     if len(targets) or tgt_hops else np.zeros(0, np.int64))

        # per-type frontiers: every node on a kept instance
        per_type: Dict[str, List[np.ndarray]] = {
            t: [] for t in self.hg.node_counts}
        for ib, p in zip(self.insts, plan.metapaths):
            if len(inst_rows) == 0:
                continue
            nodes = ib.nodes[inst_rows, :i_cap]
            msk = ib.mask[inst_rows, :i_cap] > 0
            for j, ty in enumerate(p):
                per_type[ty].append(
                    np.unique(nodes[:, :, j][msk]).astype(np.int64))
        types_used = {ty for p in plan.metapaths for ty in p} | {self.target}
        fr: Dict[str, np.ndarray] = {}
        for t in sorted(types_used):
            tgt = targets if t == self.target else np.zeros(0, np.int64)
            hops = ([np.asarray(sorted(set(np.concatenate(per_type[t]).tolist())
                                       if per_type[t] else []), np.int64)]
                    if per_type[t] else [])
            fr[t] = self._frontier_order(hops, tgt)
        return fr

    def _gather_instance(self, tables: Dict[str, _TypeTable], up: Callable,
                         ) -> Dict:
        plan, cfg = self.plan, self.cfg
        i_cap = self.k_eff
        tt = tables[self.target]
        batch: Dict = {
            "feats": {t: tables[t].rows(self.resident[t], up)
                      for t in tables},
            "feat_dims": {t: self.feat_dims[t] for t in tables},
            "n_nodes": tt.cap,
            "row_mask": up(self._row_mask(tt)),
        }
        instances = []
        for ib, p in zip(self.insts, plan.metapaths):
            if tt.identity and i_cap == cfg.max_instances and all(
                    tables[ty].identity for ty in p):
                nodes, mask = ib.nodes, ib.mask
            else:
                nodes = np.zeros((tt.cap, i_cap, len(p)), np.int32)
                mask = np.zeros((tt.cap, i_cap), np.float32)
                src_rows = ib.nodes[tt.ids, :i_cap]  # [n_real, I, L]
                src_mask = ib.mask[tt.ids, :i_cap].copy()
                for j, ty in enumerate(p):
                    loc = tables[ty].relabel(src_rows[:, :, j].reshape(-1))
                    loc = loc.reshape(src_rows.shape[:2])
                    # an instance touching a truncated node drops entirely
                    src_mask[(loc < 0) & (src_mask > 0)] = 0.0
                    nodes[: tt.n_real, :, j] = np.where(loc < 0, 0, loc)
                mask[: tt.n_real] = src_mask
                nodes[mask == 0] = 0
            instances.append((up(nodes), up(mask)))
        batch["instances"] = instances
        return batch

    # ------------------------------------------------------------------
    # GCN — homogeneous edge list, 2 aggregation hops per layer
    # ------------------------------------------------------------------
    def _expand_gcn(self, targets: np.ndarray) -> Dict[str, np.ndarray]:
        k = self.k_eff
        indptr, indices = self.csr.indptr, self.csr.indices
        cur = np.unique(targets)
        known = set(cur.tolist())
        hop_sets: List[np.ndarray] = []
        for _ in range(2 * self.plan.n_layers):  # 2 aggregations per layer
            nxt: List[np.ndarray] = []
            for g in cur.tolist():
                nbrs = indices[indptr[g]: indptr[g] + min(
                    k, indptr[g + 1] - indptr[g])]
                nxt.append(nbrs.astype(np.int64))
            cand = (np.unique(np.concatenate(nxt)) if nxt
                    else np.zeros(0, np.int64))
            new = np.asarray([g for g in cand.tolist() if g not in known],
                             np.int64)
            if len(new) == 0:
                break
            hop_sets.append(new)
            known.update(new.tolist())
            cur = new
        return {self.target: self._frontier_order(hop_sets, targets)}

    def _gather_gcn(self, tables: Dict[str, _TypeTable], up: Callable) -> Dict:
        k = self.k_eff
        indptr, indices = self.csr.indptr, self.csr.indices
        table = tables[self.target]
        if table.identity and k == self.max_deg:
            seg, idx = (np.repeat(np.arange(table.cap, dtype=np.int32),
                                  np.diff(indptr)),
                        indices.astype(np.int32))
        else:
            e_cap = table.cap * max(k, 1)
            seg = np.full(e_cap, table.cap, np.int32)  # OOB segments drop
            idx = np.zeros(e_cap, np.int32)
            e = 0
            for u_loc in range(table.n_real):
                g = table.ids[u_loc]
                nbrs = indices[indptr[g]: indptr[g] + min(
                    k, indptr[g + 1] - indptr[g])]
                loc = table.relabel(nbrs.astype(np.int64))
                loc = loc[loc >= 0][: k]
                seg[e: e + len(loc)] = u_loc
                idx[e: e + len(loc)] = loc
                e += len(loc)
        return {
            "x": table.rows(self.resident[self.target], up),
            "seg": up(seg),
            "idx": up(idx),
            "n_nodes": table.cap,
            "feat_dim": self.feat_dims[self.target],
        }


def _upload(x: np.ndarray, rec: Dict) -> jax.Array:
    """Put one host array of a batch — an index table or a type's row ids,
    never feature rows — on the device under the ``hgnn.sample.upload``
    span, adding its seconds and its bytes (padded, as on the device) to
    ``rec``.  Each array goes up as soon as it is built."""
    with span("hgnn.sample.upload", rec, "upload_s"):
        out = jnp.asarray(x)
    rec["upload_bytes"] += out.nbytes
    return out
