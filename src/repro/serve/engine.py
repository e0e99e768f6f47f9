"""Serving engines.

``ServeEngine`` — LM slot-based continuous batching over the prefill/decode
step functions.  Requests occupy fixed batch slots; each decode step advances
every active slot by one token.  Finished slots (EOS or max_tokens) are
refilled from the queue without stopping the decode loop — decode-32k-style
serving as the paper's shapes require.  Sampling: greedy or temperature.

``HGNNInferEngine`` — HGNN inference driven by a :class:`StagePlan`: the
engine holds the stage-graph executor (not a model class), serves the jitted
forward, and exposes the per-stage characterization records from the exact
code path it serves.
"""
from __future__ import annotations

import dataclasses
import queue
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.nn import transformer as tf
from repro.serve import resilience
from repro.serve.resilience import (
    FAILED, OK, PARTIAL, AdmissionController, DegradationController,
    ResilienceConfig, RetryPolicy, StepFailure, finalize_request)
from repro.serve.spans import span

# what the sampler's spans and counters put in SampledBatch.meta
SAMPLE_PARTS = ("expand_s", "gather_s", "upload_s", "upload_bytes",
                "resident_gather_bytes")
# the keys every step_log record gains from its spans and counters
STEP_PARTS = ("refill_s", "sample_s", "forward_s", "scatter_s",
              *SAMPLE_PARTS, "recompiled")


class HGNNInferEngine:
    """Plan-driven HGNN serving.

    Consumes a :class:`repro.core.pipeline.StageGraphExecutor` (built from a
    :class:`repro.core.plan.StagePlan`) plus the prepared params/batch —
    typically the fields of ``launch.serve.build_hgnn_infer``'s result.  The
    executor resolves layout / kernel / sharding dispatch; the engine adds
    the serving loop and the characterization hook, so the stage breakdown
    reported to operators comes from the same plan that serves traffic.
    """

    def __init__(self, executor, params, batch, fn=None):
        self.executor = executor
        self.plan = executor.plan
        self.params = params
        self.batch = batch
        self.fn = fn if fn is not None else jax.jit(executor.forward)

    def infer(self) -> jax.Array:
        """One full forward over the prepared batch -> logits."""
        with span("hgnn.infer"):
            return self.fn(self.params, self.batch)

    def characterize(self, n_chips: int = 1) -> Dict[str, Dict]:
        """Per-stage (FP/NA/SA/head) FLOPs / HBM bytes / roofline records
        via ``core/characterize.py`` — the paper's Fig. 3 breakdown from the
        serving code path."""
        return self.executor.stage_records(self.params, self.batch,
                                           n_chips=n_chips)["stages"]


@dataclasses.dataclass
class HGNNRequest:
    """One HGNN inference request: classify ``targets`` (global target-type
    vertex ids).

    ``serve`` leaves every request in a terminal ``status``
    (``OK`` / ``PARTIAL`` / ``REJECTED`` / ``FAILED`` — see
    ``repro.serve.resilience``) with ``logits`` rows for exactly the target
    ids named by ``served`` (all of ``targets`` when ``OK``; the rows
    completed before the deadline/failure otherwise; always ``n_classes``
    wide, so downstream concatenation over mixed-status requests is
    well-formed).  ``deadline_ms`` overrides the engine-wide default."""
    targets: np.ndarray  # [n] integer, global ids of the plan's target type
    logits: Optional[np.ndarray] = None  # [n_served, n_classes] when done
    deadline_ms: Optional[float] = None  # per-request deadline override
    status: str = "NEW"
    error: Optional[str] = None          # reject/failure reason
    served: Optional[np.ndarray] = None  # target ids the logits rows answer
    _done: int = 0  # host cursor into _serve_ids: rows < _done are served
    _serve_ids: Optional[np.ndarray] = None  # admission's deduped id view
    _inv: Optional[np.ndarray] = None        # original row -> _serve_ids row
    _buf: Optional[np.ndarray] = None        # [len(_serve_ids), C] working
    _deadline: Optional[float] = None        # absolute perf_counter deadline
    # timeline on the perf_counter clock, stamped by the engine: ``seq`` is
    # the engine's admission order (the id its step records list),
    # ``admitted_at`` when ``serve`` took the request, ``started_at`` the
    # start of the first step that served it, ``finished_at`` when it turned
    # terminal; ``steps`` the step indices (within the ``serve`` call) that
    # served it
    seq: Optional[int] = None
    admitted_at: Optional[float] = None
    started_at: Optional[float] = None
    finished_at: Optional[float] = None
    steps: List[int] = dataclasses.field(default_factory=list)

    @property
    def finished(self) -> bool:
        return self.status in resilience.TERMINAL


class HGNNServeEngine:
    """Slot-based continuous batching for HGNN requests.

    The LM ``ServeEngine``'s serving discipline ported to the request path:
    requests occupy fixed batch slots; each step every active slot
    contributes up to ``slot_targets`` of its remaining target vertices to a
    union minibatch, the sampler extracts one bucketed subgraph for the
    union, a single jitted forward serves it, and the logits scatter back
    per request through ``SampledBatch.target_rows`` (the relabel inverse).
    Finished slots refill from the queue without stopping the step loop, so
    a mixed-size queue never idles a slot while work remains.

    ``warmup()`` compiles one entry per ladder rung; afterwards
    ``stats["compiles_after_warmup"]`` must stay 0 — partitioned plans
    included (the ladder is the whole shape space).  Partitioned plans
    re-partition the sampled batch each step, and the minimal host
    relabeling chooses data-dependent owned/halo table widths, so the
    engine serves a ``static_shapes`` copy of the partition spec: every
    per-type table pads to assignment-independent capacities
    (``n_max = ceil(n/k)``, ``h_max = n``), making the partitioned shapes a
    pure function of the rung and killing the per-step re-trace.

    Resilience (``repro.serve.resilience`` policies, threaded through the
    slot loop): admission control with structured per-request statuses,
    per-request deadlines (expired requests complete ``PARTIAL`` with the
    rows served so far), SLO-driven degradation that shrinks the per-slot
    chunk and clamps the rung choice *inside* the warmed ladder, bounded
    retry-with-backoff around the sampler and the jitted forward (failing
    only the affected slots' requests on persistent errors), and — on a
    partitioned plan — failover that re-partitions subsequent batches over
    the surviving partitions when ``injector`` reports a partition loss.
    """

    def __init__(self, executor, params, sampler, slots: int = 8,
                 slot_targets: int = 4, fn=None,
                 resilience_cfg: Optional[ResilienceConfig] = None,
                 injector=None):
        self.executor = executor
        self.plan = executor.plan
        self.params = params
        self.sampler = sampler
        self.slots = slots
        self.slot_targets = slot_targets
        self.fn = fn if fn is not None else jax.jit(executor.forward)
        max_t = max(t for t, _ in sampler.ladder)
        if slots * slot_targets > max_t:
            raise ValueError(
                f"slots*slot_targets={slots * slot_targets} exceeds the "
                f"largest ladder rung's target cap {max_t}; widen the "
                "ladder or shrink the slot plan")
        self.res = (resilience_cfg if resilience_cfg is not None
                    else ResilienceConfig())
        self.injector = injector
        self.n_classes = int(executor.cfg.n_classes)
        # failover target: partition loss swaps in a survivors-only spec.
        # Partitioned serving always pins static per-type table shapes —
        # see the class docstring (compiles_after_warmup == 0).
        self._serve_plan = self.plan
        if self.plan.partition is not None:
            self._serve_plan = dataclasses.replace(
                self.plan, partition=dataclasses.replace(
                    self.plan.partition, static_shapes=True))
        self._warm_compiles: Optional[int] = None
        self.step_log: List[Dict] = []
        self._seq = 0  # next request's admission number
        self.last_sb = None
        # residency: live per-type hot-row caches over the sampled frontier
        # (repro.core.residency.HotRowCache).  Keyed by GLOBAL vertex ids and
        # owned by the engine — not the per-step batch — so cache state is
        # untouched by rung changes, degradation clamps, and partition
        # failover, and the jitted forward's shapes never see the cache
        # (compiles_after_warmup stays 0).
        self.caches: Optional[Dict] = None
        if self.plan.residency is not None:
            from repro.core.residency import HotRowCache, graph_degrees

            cap = self.plan.residency.cache_rows
            self.caches = {t: HotRowCache(cap, d)
                           for t, d in graph_degrees(sampler.hg).items()}
        self._fresh_policies()

    def _fresh_policies(self) -> None:
        """Per-serve policy state (counters reset each ``serve`` call)."""
        self.admission = AdmissionController(
            self.res, self.sampler.n_target_type, self.n_classes)
        self.degrade = DegradationController(
            self.res, len(self.sampler.ladder), self.slot_targets)
        self.retry = RetryPolicy(self.res)
        self._deadline_expired = 0
        self._failovers = 0
        self._lost_partitions: List[int] = []
        self._status_counts: Dict[str, int] = {}

    def _cache_step(self, ids: np.ndarray, sb) -> None:
        """One serving step's residency traffic: pin the in-flight targets
        (never evicted while their request is being served), run the sampled
        frontier — every type's local->global table — through the live
        caches' deterministic admission policy, then unpin."""
        spec = self.plan.residency
        tgt = self.plan.target
        pin = spec.pin_targets and tgt in self.caches
        if pin:
            self.caches[tgt].pin(ids)
        for t, loc in sb.local.items():
            if t in self.caches:
                self.caches[t].access_many(loc)
        if pin:
            self.caches[tgt].unpin(ids)

    def _forward_batch(self, batch: Dict) -> Dict:
        if self._serve_plan.partition is not None:
            from repro.dist.partition import partition_batch
            return partition_batch(self._serve_plan, batch)
        return batch

    def _maybe_failover(self, step: int) -> None:
        """Injected partition loss -> re-assign the lost partition's
        vertices over the survivors (every subsequent ``partition_batch``
        re-partitions with the shrunk spec; the partitioned head's inverse
        permutation keeps global row order, so outputs stay bit-exact vs a
        never-failed run)."""
        if self.injector is None or self._serve_plan.partition is None:
            return
        lost = self.injector.partition_loss(step)
        if lost is None:
            return
        from repro.dist.partition import surviving_partition_spec
        spec = surviving_partition_spec(self._serve_plan.partition, [lost])
        self._serve_plan = dataclasses.replace(self._serve_plan,
                                               partition=spec)
        self._failovers += 1
        self._lost_partitions.append(int(lost))

    def warmup(self) -> int:
        """Compile every ladder rung on a dummy batch; snapshot the jit
        cache size so ``stats`` can report post-warmup recompiles."""
        for i in range(len(self.sampler.ladder)):
            sb = self.sampler.dummy_batch(i)
            jax.block_until_ready(
                self.fn(self.params, self._forward_batch(sb.batch)))
        self._warm_compiles = self.fn._cache_size()
        return self._warm_compiles

    def serve(self, requests: List[HGNNRequest]) -> List[HGNNRequest]:
        """Run the slot loop until every request reaches a terminal status.

        Never raises for admissible traffic: bad requests are REJECTED at
        admission, deadline-expired ones complete PARTIAL, and persistent
        step errors FAIL only the requests in the affected slots.

        Each step runs under the ``hgnn.serve.*`` spans
        (``repro.serve.spans``); its ``step_log`` record carries their
        seconds, the sampler's phases, its uploaded and device-gathered
        bytes, the forward's recompiles, and the ``seq`` of every request
        it served.
        """
        import collections
        import time

        self._fresh_policies()
        adm, deg, retry = self.admission, self.degrade, self.retry
        now = time.perf_counter()
        q: collections.deque = collections.deque()
        for r in requests:
            r.seq, r.admitted_at = self._seq, now
            self._seq += 1
            if adm.admit(r, len(q), now):
                q.append(r)
            else:  # rejected or degenerate: terminal at admission
                r.finished_at = now
        active: List[Optional[HGNNRequest]] = [None] * self.slots
        self.step_log = []
        step = 0
        while q or any(r is not None for r in active):
            rec: Dict = {"step": step, **dict.fromkeys(STEP_PARTS, 0)}
            with span("hgnn.serve.step", rec, "step_s", step=step) as sp:
                with span("hgnn.serve.refill", rec, "refill_s"):
                    q, active, chunks, level_used, rung_limit = self._refill(
                        q, active)
                    if chunks:
                        self._maybe_failover(step)
                        ids = np.concatenate([c[2] for c in chunks])
                if not chunks:  # everything expired this pass
                    continue
                rec["seqs"] = [r.seq for r, _start, _cids in chunks]
                for r, _start, _cids in chunks:
                    if r.started_at is None:
                        r.started_at = sp.t0
                    r.steps.append(step)
                t0 = time.perf_counter()
                inj = self.injector
                try:
                    with span("hgnn.serve.sample", rec, "sample_s"):
                        sb = self._sample(ids, rung_limit, step)
                    for k in SAMPLE_PARTS:
                        rec[k] = sb.meta[k]
                    with span("hgnn.forward", rec, "forward_s"):
                        n_compiled = self.fn._cache_size()
                        out = retry.run(
                            "forward",
                            lambda: np.asarray(self.fn(
                                self.params, self._forward_batch(sb.batch))),
                            hook=(lambda a: inj.check("forward", step, a))
                            if inj else None)
                        rec["recompiled"] = (self.fn._cache_size()
                                             - n_compiled)
                except StepFailure as e:
                    wall = time.perf_counter() - t0
                    inj_lat = inj.latency_s(step) if inj else 0.0
                    wall_obs = wall + inj_lat
                    with span("hgnn.serve.scatter", rec, "scatter_s"):
                        for r, _start, _cids in chunks:
                            finalize_request(r, FAILED, self.n_classes,
                                             error=str(e))
                        for s in range(self.slots):
                            if (active[s] is not None
                                    and active[s].status == FAILED):
                                active[s] = None
                        deg.observe(inj_lat
                                    if self.res.slo_signal == "injected"
                                    else wall_obs)
                    rec.update({
                        "active_slots": len(chunks), "queue_len": len(q),
                        "n_targets": int(len(ids)), "rung_index": -1,
                        "frontier_bytes": 0.0, "truncated_rows": 0,
                        "wall_s": wall, "wall_observed_s": wall_obs,
                        "degrade_level": level_used, "failed": True,
                        "error": str(e),
                    })
                    self.step_log.append(rec)
                    step += 1
                    continue
                with span("hgnn.serve.scatter", rec, "scatter_s"):
                    rows = out[sb.target_rows]
                    wall = time.perf_counter() - t0
                    if self.caches is not None:  # host bookkeeping
                        self._cache_step(ids, sb)
                    inj_lat = inj.latency_s(step) if inj else 0.0
                    wall_obs = wall + inj_lat
                    off = 0
                    for r, start, cids in chunks:
                        n = len(cids)
                        if r._buf is None:
                            r._buf = np.zeros(
                                (len(r._serve_ids), rows.shape[1]),
                                rows.dtype)
                        r._buf[start: start + n] = rows[off: off + n]
                        r._done = start + n
                        off += n
                    for s in range(self.slots):
                        r = active[s]
                        if r is not None and r._done >= len(r._serve_ids):
                            finalize_request(r, OK, self.n_classes)
                            active[s] = None
                    deg.observe(inj_lat if self.res.slo_signal == "injected"
                                else wall_obs)
                rec.update({
                    "active_slots": len(chunks),
                    "queue_len": len(q),
                    "n_targets": int(sb.n_targets),
                    "rung_index": int(sb.rung_index),
                    "frontier_bytes": float(sb.meta["frontier_bytes"]),
                    "truncated_rows": int(sb.meta["truncated_rows"]),
                    "wall_s": wall,
                    "wall_observed_s": wall_obs,
                    "degrade_level": level_used,
                })
                self.step_log.append(rec)
                self.last_sb = sb
                step += 1
        for r in requests:
            self._status_counts[r.status] = (
                self._status_counts.get(r.status, 0) + 1)
        return requests

    def _refill(self, q, active):
        """One step's expiry, refill and chunking.  Returns the live queue
        and slots, the chunks ``(request, start_row, ids)`` to serve, the
        degradation level used and the rung limit."""
        import collections
        import time

        deg = self.degrade
        now = time.perf_counter()
        # deadline expiry: active slots and queued requests complete
        # PARTIAL (rows served so far) without blocking the loop
        active, n_exp = resilience.expire_requests(
            active, now, self.n_classes)
        self._deadline_expired += n_exp
        if q:
            live: collections.deque = collections.deque()
            for r in q:
                if r._deadline is not None and now >= r._deadline:
                    finalize_request(r, PARTIAL, self.n_classes,
                                     error="deadline expired")
                    self._deadline_expired += 1
                else:
                    live.append(r)
            q = live
        # refill: degenerate requests completed at admission, so every
        # queued request is servable and takes exactly one free slot
        for s in range(self.slots):
            if active[s] is None and q:
                active[s] = q.popleft()
                active[s].status = "ACTIVE"
        # degradation: per-slot chunk + rung clamp (warmed rungs only)
        level_used = deg.level
        chunk = deg.chunk()
        rung_limit = deg.rung_limit()
        t_budget = self.sampler.ladder[rung_limit][0]
        chunks = []  # (request, start_row_in_request, ids)
        n_union = 0
        for r in active:
            if r is None:
                continue
            if n_union >= t_budget:
                break  # degraded union budget: remaining slots wait
            take = min(chunk, t_budget - n_union,
                       len(r._serve_ids) - r._done)
            ids = r._serve_ids[r._done: r._done + take]
            chunks.append((r, r._done, np.asarray(ids, np.int64)))
            n_union += take
        return q, active, chunks, level_used, rung_limit

    def _sample(self, ids, rung_limit, step):
        """The step's sampled batch, under the retry policy and the fault
        hook."""
        inj = self.injector
        return self.retry.run(
            "sampler",
            lambda: self.sampler.sample(ids, max_rung=rung_limit),
            hook=(lambda a: inj.check("sampler", step, a))
            if inj else None)

    def stats(self) -> Dict:
        """Deterministic serving counters (walls reported, never gated).

        ``compiles_after_warmup`` is ``None`` until :meth:`warmup` has run —
        there is no warm cache to diff against, so a recompile count would
        be meaningless (previously a silent ``-1`` sentinel).
        """
        rung_hits: Dict[int, int] = {}
        for e in self.step_log:
            if e.get("failed"):
                continue  # failed steps sample no rung
            rung_hits[e["rung_index"]] = rung_hits.get(e["rung_index"], 0) + 1
        compiles = (int(self.fn._cache_size() - self._warm_compiles)
                    if self._warm_compiles is not None else None)
        walls = [e["wall_s"] for e in self.step_log]
        deg, retry, adm = self.degrade, self.retry, self.admission
        inj_counts = dict(self.injector.counters) if self.injector else {}
        out = {
            "steps": len(self.step_log),
            "rung_hits": {int(k): int(v)
                          for k, v in sorted(rung_hits.items())},
            "frontier_bytes": float(
                sum(e["frontier_bytes"] for e in self.step_log)),
            "truncated_rows": int(
                sum(e["truncated_rows"] for e in self.step_log)),
            "compiles_after_warmup": compiles,
            "wall_total_s": float(sum(walls)),
            "wall_mean_ms": float(1e3 * np.mean(walls)) if walls else 0.0,
            "resilience": {
                **{k: int(v) for k, v in adm.counters.items()},
                **{k: int(v) for k, v in retry.counters.items()},
                **{k: int(v) for k, v in deg.counters.items()},
                "retries": int(retry.counters["sampler_retries"]
                               + retry.counters["forward_retries"]),
                "deadline_expired": int(self._deadline_expired),
                "failed_requests": int(
                    self._status_counts.get(FAILED, 0)),
                "partial_requests": int(
                    self._status_counts.get(PARTIAL, 0)),
                "ok_requests": int(self._status_counts.get(OK, 0)),
                "partition_failovers": int(self._failovers),
                "lost_partitions": list(self._lost_partitions),
                "statuses": dict(self._status_counts),
                "injected": inj_counts,
            },
        }
        if self.caches is not None:
            hits = sum(c.hits for c in self.caches.values())
            misses = sum(c.misses for c in self.caches.values())
            out["residency"] = {
                "per_type": {t: dict(c.counters)
                             for t, c in sorted(self.caches.items())},
                "hits": int(hits),
                "misses": int(misses),
                "rows": int(hits + misses),
                "hit_rate": float(hits / max(hits + misses, 1)),
                "evictions": int(sum(c.evictions
                                     for c in self.caches.values())),
                "cache_rows": int(sum(c.capacity
                                      for c in self.caches.values())),
            }
        return out


@dataclasses.dataclass
class Request:
    prompt: np.ndarray  # [T] int32
    max_tokens: int = 32
    temperature: float = 0.0
    out_tokens: Optional[List[int]] = None


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, batch_slots: int = 8,
                 max_len: int = 512, rng_seed: int = 0, eos_id: int = -1):
        self.cfg = cfg
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.key = jax.random.key(rng_seed)
        self._decode = jax.jit(
            lambda p, t, c, pos: tf.lm_decode_step(p, cfg, t, c, pos))

    def _sample(self, logits: jax.Array, temps: Optional[jax.Array]) -> jax.Array:
        """Per-slot sampling: each request in the wave keeps its own
        temperature (greedy where <= 0, categorical otherwise).  ``temps``
        is the device array built ONCE per wave by ``_run_wave`` — None
        means an all-greedy wave, so the per-token loop never re-uploads or
        re-reduces wave-constant facts."""
        greedy = jnp.argmax(logits, axis=-1)
        if temps is None:
            return greedy
        self.key, sub = jax.random.split(self.key)
        sampled = jax.random.categorical(
            sub, logits / jnp.maximum(temps, 1e-6)[:, None], axis=-1)
        return jnp.where(temps > 0.0, sampled, greedy)

    def generate(self, requests: List[Request]) -> List[Request]:
        """Simple batched generation: pad prompts to a common length, prefill
        once, then decode lock-step (same-length prompts per wave)."""
        out: List[Request] = []
        for wave_start in range(0, len(requests), self.slots):
            wave = requests[wave_start: wave_start + self.slots]
            out.extend(self._run_wave(wave))
        return out

    def _run_wave(self, wave: List[Request]) -> List[Request]:
        cfg = self.cfg
        b = len(wave)
        t0 = max(len(r.prompt) for r in wave)
        toks = np.zeros((b, t0), np.int32)
        for i, r in enumerate(wave):
            toks[i, t0 - len(r.prompt):] = r.prompt  # left-pad
        logits, pf_caches = tf.lm_prefill(self.params, cfg, jnp.asarray(toks))
        caches = tf.graft_prefill_caches(
            cfg, tf.init_kv_caches(cfg, b, self.max_len), pf_caches, t0)
        max_new = max(r.max_tokens for r in wave)
        temps_host = np.array([r.temperature for r in wave], np.float32)
        temps = (jnp.asarray(temps_host) if (temps_host > 0).any() else None)
        cur = self._sample(logits[:, 0], temps)
        outs = [[int(cur[i])] for i in range(b)]
        done = np.zeros(b, bool)
        for step in range(1, max_new):
            pos = jnp.int32(t0 + step - 1)
            logits, caches = self._decode(self.params, cur[:, None], caches, pos)
            cur = self._sample(logits[:, 0], temps)
            for i in range(b):
                if done[i] or step >= wave[i].max_tokens:
                    done[i] = True
                    continue
                t = int(cur[i])
                outs[i].append(t)
                if t == self.eos_id:
                    done[i] = True
            if done.all():
                break
        for r, o in zip(wave, outs):
            r.out_tokens = o[: r.max_tokens]
        return wave
