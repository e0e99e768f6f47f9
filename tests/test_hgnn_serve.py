"""HGNN slot-based continuous batching (repro.serve.engine.HGNNServeEngine).

The ISSUE's three serving invariants:

  * slot refill keeps utilization — with a mixed-size request queue, no slot
    idles while the queue is non-empty;
  * per-request results land under the right request id after the
    relabel-inverse scatter (bit-exact vs the full-graph forward when the
    fan-out covers every neighbor);
  * the recompile count after warmup is 0 — the ladder is the whole shape
    space the jitted executor ever sees.
"""
import jax
import numpy as np
import pytest

from repro.configs.base import HGNNConfig
from repro.core.models import get_model
from repro.data.synthetic import DATASET_METAPATHS, DATASET_TARGET
from repro.serve.engine import HGNNRequest, HGNNServeEngine
from repro.serve.sampler import HGNNSampler


def _tiny_tables():
    DATASET_METAPATHS["tiny"] = [["M", "D", "M"], ["M", "A", "M"]]
    DATASET_TARGET["tiny"] = "M"


def _build(tiny_hg, model="han", fanout=64, **kw):
    _tiny_tables()
    kw = {"max_degree": 48, "max_instances": 4, "fused": True, **kw}
    cfg = HGNNConfig(model=model, dataset="tiny", hidden=16, n_heads=4,
                     n_classes=3, fanout=fanout, **kw)
    m = get_model(cfg)
    batch = m.prepare(tiny_hg)
    params = m.init(jax.random.key(0), batch)
    fn = jax.jit(m.forward)
    full = np.asarray(fn(params, batch))
    sampler = HGNNSampler(m.plan(), cfg, tiny_hg)
    return m, params, fn, full, sampler


def _mixed_requests(n, n_nodes=40, seed=3):
    rng = np.random.default_rng(seed)
    return [HGNNRequest(targets=rng.integers(
        0, n_nodes, size=int(rng.integers(1, 9)))) for _ in range(n)]


def test_slot_refill_keeps_utilization(tiny_hg):
    """step_log's queue_len is recorded after refill: whenever requests are
    still waiting, every slot must be occupied that step."""
    m, params, fn, full, sampler = _build(tiny_hg)
    eng = HGNNServeEngine(m.executor, params, sampler, slots=4,
                          slot_targets=2, fn=fn)
    eng.warmup()
    eng.serve(_mixed_requests(16))
    assert len(eng.step_log) > 1
    for e in eng.step_log:
        if e["queue_len"] > 0:
            assert e["active_slots"] == 4, e
        assert e["active_slots"] >= 1


def test_results_land_under_the_right_request(tiny_hg):
    """fanout >= max degree + an identity-wide ladder: every request's
    logits must be BIT-EXACT the full-graph forward's rows for its ids —
    the relabel-inverse scatter keeps request identity through chunking,
    shared steps, and out-of-order slot completion."""
    m, params, fn, full, sampler = _build(tiny_hg)
    eng = HGNNServeEngine(m.executor, params, sampler, slots=4,
                          slot_targets=2, fn=fn)
    eng.warmup()
    reqs = _mixed_requests(12)
    done = eng.serve(reqs)
    assert done is reqs
    for r in reqs:
        assert r.finished
        np.testing.assert_array_equal(r.logits, full[r.targets])


def test_zero_recompiles_after_warmup(tiny_hg):
    """Mixed request sizes sweep multiple ladder rungs; after the per-rung
    warmup the jit cache must not grow."""
    for model, kw in [("han", {}), ("rgcn", {}), ("magnn", {}),
                      ("han", {"degree_buckets": 3}),
                      ("han", {"layers": 2})]:
        m, params, fn, full, sampler = _build(tiny_hg, model=model,
                                              fanout=3, **kw)
        eng = HGNNServeEngine(m.executor, params, sampler, slots=4,
                              slot_targets=2, fn=fn)
        eng.warmup()
        eng.serve(_mixed_requests(10))
        st = eng.stats()
        assert st["compiles_after_warmup"] == 0, (model, kw, st)
        assert st["steps"] == len(eng.step_log)
        assert sum(st["rung_hits"].values()) == st["steps"]
        assert set(st["rung_hits"]) <= set(range(len(sampler.ladder)))


def test_sampled_serving_is_deterministic(tiny_hg):
    """Same queue, small fan-out (genuine subsampling): two engines produce
    identical per-request logits — sampling is precomputed + deterministic,
    so serving results are reproducible."""
    out = []
    for _ in range(2):
        m, params, fn, full, sampler = _build(tiny_hg, fanout=2)
        eng = HGNNServeEngine(m.executor, params, sampler, slots=3,
                              slot_targets=2, fn=fn)
        eng.warmup()
        reqs = _mixed_requests(8)
        eng.serve(reqs)
        out.append([r.logits for r in reqs])
    for a, b in zip(*out):
        np.testing.assert_array_equal(a, b)


def test_empty_request_terminates(tiny_hg):
    m, params, fn, full, sampler = _build(tiny_hg)
    eng = HGNNServeEngine(m.executor, params, sampler, slots=2,
                          slot_targets=2, fn=fn)
    eng.warmup()
    reqs = [HGNNRequest(targets=np.zeros(0, np.int64)),
            HGNNRequest(targets=np.array([5, 7]))]
    eng.serve(reqs)
    assert reqs[0].logits.shape[0] == 0
    np.testing.assert_array_equal(reqs[1].logits, full[[5, 7]])


def test_slot_plan_must_fit_the_ladder(tiny_hg):
    m, params, fn, full, sampler = _build(
        tiny_hg, sample_ladder=((4, 40), (8, 40)))
    with pytest.raises(ValueError, match="slot_targets"):
        HGNNServeEngine(m.executor, params, sampler, slots=8, slot_targets=4,
                        fn=fn)


def test_oversized_request_chunks_across_steps(tiny_hg):
    """A request larger than slots*slot_targets spreads over multiple steps
    and still lands bit-exact."""
    m, params, fn, full, sampler = _build(tiny_hg)
    eng = HGNNServeEngine(m.executor, params, sampler, slots=2,
                          slot_targets=2, fn=fn)
    eng.warmup()
    big = HGNNRequest(targets=np.arange(23))
    eng.serve([big])
    # one occupied slot contributing slot_targets=2 per step
    assert len(eng.step_log) == 12
    np.testing.assert_array_equal(big.logits, full[np.arange(23)])


# ---------------------------------------------------------------------------
# hot-feature residency: the live cache rides the serve loop untraced
# ---------------------------------------------------------------------------


def test_cached_zero_recompiles_across_rungs_and_degradation(tiny_hg):
    """The live cache is engine-level host bookkeeping keyed by global ids:
    mixed request sizes sweep the ladder rungs AND injected-latency
    degradation clamps the rung choice, and the jit cache still never grows
    after warmup — cache state is invisible to the traced shapes."""
    from repro.serve.faults import Fault, FaultInjector
    from repro.serve.resilience import ResilienceConfig

    inj = FaultInjector([Fault(step=s, kind="latency", latency_s=0.2)
                         for s in range(2, 8)])
    res = ResilienceConfig(slo_ms=50.0, slo_signal="injected",
                           degrade_patience=2, recover_patience=2)
    m, params, fn, full, sampler = _build(tiny_hg, cache_rows=8)
    eng = HGNNServeEngine(m.executor, params, sampler, slots=4,
                          slot_targets=2, fn=fn, resilience_cfg=res,
                          injector=inj)
    eng.warmup()
    reqs = _mixed_requests(24)
    eng.serve(reqs)
    st = eng.stats()
    assert st["compiles_after_warmup"] == 0
    assert len(st["rung_hits"]) >= 1
    assert st["resilience"]["max_degrade_level"] >= 1
    rd = st["residency"]
    assert rd["hits"] + rd["misses"] == rd["rows"] > 0
    assert rd["hits"] > 0  # slot chunking re-touches hot frontier rows
    for t, c in rd["per_type"].items():
        assert c["resident"] <= c["capacity"] <= 8, t
    for r in reqs:
        np.testing.assert_array_equal(r.logits, full[r.targets])


def test_cache_state_survives_partition_failover_bit_exact(tiny_hg):
    """K=4 partitioned serving loses partition 1 at step 2: the caches are
    keyed by GLOBAL vertex ids and owned by the engine, so failover cannot
    disturb them — post-recovery logits stay bit-exact vs a never-failed
    cached run, and both runs replay identical residency counters."""
    from repro.serve.faults import Fault, FaultInjector

    def run(inj):
        m, params, fn, full, sampler = _build(
            tiny_hg, partitions=4, cache_rows=8)
        eng = HGNNServeEngine(m.executor, params, sampler, slots=4,
                              slot_targets=2, fn=fn, injector=inj)
        eng.warmup()
        reqs = _mixed_requests(10)
        eng.serve(reqs)
        return eng, reqs, full

    inj = FaultInjector([Fault(step=2, kind="partition", partition=1)])
    e1, r1, full = run(inj)
    e2, r2, _ = run(None)
    assert e1.stats()["resilience"]["partition_failovers"] == 1
    assert e1._serve_plan.partition.k == 3
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(a.logits, b.logits)
        np.testing.assert_array_equal(a.logits, full[a.targets])
    rd1, rd2 = e1.stats()["residency"], e2.stats()["residency"]
    assert rd1 == rd2  # identical traces -> identical cache replay
    assert rd1["rows"] > 0
    # the caches themselves are untouched by the failover: same resident
    # sets in both runs
    for t in e1.caches:
        assert e1.caches[t].resident == e2.caches[t].resident
        assert e1.caches[t].pinned == set() == e2.caches[t].pinned


@pytest.mark.parametrize("fault", [False, True], ids=["healthy", "sampler-fault"])
def test_serve_cli_exits_nonzero_when_a_request_fails(tiny_hg, monkeypatch,
                                                      fault):
    """With no --inject-faults schedule, a FAILED request (here a sampler
    that errors on every real batch) makes the serve run exit non-zero
    instead of printing its counters and passing."""
    import argparse

    from repro.launch.serve import build_hgnn_infer, run_hgnn_serve

    _tiny_tables()
    cfg = HGNNConfig(model="han", dataset="tiny", hidden=16, n_heads=4,
                     n_classes=3, max_degree=48, fused=True, fanout=8)
    if fault:
        orig = HGNNSampler.sample

        def failing(self, targets, *a, **kw):
            if len(targets):  # warmup's all-pad batches still sample
                raise RuntimeError("sampler fault")
            return orig(self, targets, *a, **kw)

        monkeypatch.setattr(HGNNSampler, "sample", failing)
    args = argparse.Namespace(
        max_queue=None, deadline_ms=None, slo_ms=None, slo_signal="observed",
        inject_faults=None, slots=2, slot_targets=2, requests=4,
        characterize=False)
    built = build_hgnn_infer(cfg, tiny_hg)
    if fault:
        with pytest.raises(SystemExit, match="4 of 4 requests FAILED"):
            run_hgnn_serve(args, cfg, tiny_hg, built)
    else:
        run_hgnn_serve(args, cfg, tiny_hg, built)
