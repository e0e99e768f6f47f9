"""Spans and counters of the serving path (``repro.serve.spans``): the
``hgnn.*`` spans nest in a profiler trace's host plane as the engine, the
sampler and the forward run; each step record splits its step, each
request record joins its steps; the executor's stage scopes reach the
lowered program."""
import glob
import re

import jax
import numpy as np
import pytest

from repro.configs.base import HGNNConfig
from repro.core.models import get_model
from repro.data.synthetic import DATASET_METAPATHS, DATASET_TARGET
from repro.serve.engine import (STEP_PARTS, HGNNRequest, HGNNServeEngine)
from repro.serve.sampler import HGNNSampler

PARENT = {
    "hgnn.serve.refill": "hgnn.serve.step",
    "hgnn.serve.sample": "hgnn.serve.step",
    "hgnn.sample": "hgnn.serve.sample",
    "hgnn.sample.expand": "hgnn.sample",
    "hgnn.sample.gather": "hgnn.sample",
    "hgnn.sample.upload": "hgnn.sample.gather",
    "hgnn.forward": "hgnn.serve.step",
    "hgnn.serve.scatter": "hgnn.serve.step",
}


def _cfg(model, **kw):
    DATASET_METAPATHS["tiny"] = [["M", "D", "M"], ["M", "A", "M"]]
    DATASET_TARGET["tiny"] = "M"
    kw = {"max_degree": 48, "max_instances": 4, "fused": True, **kw}
    return HGNNConfig(model=model, dataset="tiny", hidden=16, n_heads=4,
                      n_classes=3, **kw)


def _engine(tiny_hg, model):
    cfg = _cfg(model, fanout=4, layers=2 if model == "rgcn" else 1)
    m = get_model(cfg)
    batch = m.prepare(tiny_hg)
    params = m.init(jax.random.key(0), batch)
    eng = HGNNServeEngine(m.executor, params,
                          HGNNSampler(m.plan(), cfg, tiny_hg), slots=4,
                          slot_targets=2, fn=jax.jit(m.forward))
    eng.warmup()
    return eng


def _requests(n, seed=5):
    rng = np.random.default_rng(seed)
    return [HGNNRequest(targets=rng.integers(0, 40, int(rng.integers(1, 7))))
            for _ in range(n)]


@pytest.mark.parametrize("model", ["han", "rgcn"])
def test_spans_nest_in_the_host_trace(tiny_hg, tmp_path, model):
    from jax.profiler import ProfileData

    eng = _engine(tiny_hg, model)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.serve(_requests(10))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = [[(e.name, e.start_ns, e.end_ns, dict(e.stats))
              for e in ln.events if e.name.startswith("hgnn.")]
             for pl in ProfileData.from_file(path).planes
             if pl.name.startswith("/host:") for ln in pl.lines]
    events = [e for ln in lines for e in ln]
    names = {n for n, *_ in events}
    assert names == set(PARENT) | {"hgnn.serve.step"}
    steps = [a.get("step") for n, _s, _e, a in events
             if n == "hgnn.serve.step"]
    assert steps == [r["step"] for r in eng.step_log]
    for ln in lines:
        for name, s, e, _a in ln:
            if name in PARENT:
                assert any(n == PARENT[name] and ps <= s and e <= pe
                           for n, ps, pe, _ in ln), name


@pytest.mark.parametrize("model", ["han", "rgcn"])
def test_step_records_split_the_step(tiny_hg, model):
    eng = _engine(tiny_hg, model)
    eng.serve(_requests(12))
    assert eng.step_log
    for r in eng.step_log:
        assert set(STEP_PARTS) | {"step", "step_s", "seqs"} <= set(r)
        parts = r["refill_s"] + r["sample_s"] + r["forward_s"] + r["scatter_s"]
        assert 0 < parts <= r["step_s"]
        assert r["expand_s"] + r["gather_s"] + r["upload_s"] <= r["sample_s"]
        assert min(r["expand_s"], r["gather_s"], r["upload_s"]) > 0
        # the tiny graph puts every type on an identity rung: the frontier
        # rows are already on the device, and only index tables go up
        assert r["resident_gather_bytes"] == 0
        assert r["upload_bytes"] > 0 and r["frontier_bytes"] > 0
        assert r["wall_s"] <= r["step_s"]
        assert r["recompiled"] == 0
    assert eng.stats()["compiles_after_warmup"] == 0


@pytest.mark.parametrize("model", ["han", "rgcn"])
def test_request_timeline_joins_step_records(tiny_hg, model):
    eng = _engine(tiny_hg, model)
    first, second = _requests(6, seed=1), _requests(6, seed=2)
    eng.serve(first)
    log = list(eng.step_log)
    eng.serve(second)
    for reqs, steps in ((first, log), (second, eng.step_log)):
        for r in reqs:
            assert r.status == "OK"
            assert r.admitted_at <= r.started_at <= r.finished_at
            assert r.steps == [s["step"] for s in steps if r.seq in s["seqs"]]
            assert r.steps
    assert [r.seq for r in first + second] == list(range(12))


def _scopes_expected(executor, params, batch):
    plan = executor.plan
    out = set()
    for name in executor.stage_fns(params, batch):
        m = re.match(r"L(\d+)\.FP$", name)
        if m and plan.layers[int(m.group(1)) - 1].fp.kind == "identity":
            continue  # an identity FP (R-GCN's hidden layers) emits no op
        out.add(name)
    return out


@pytest.mark.parametrize("model,layers,partitions", [
    ("han", 1, 0), ("han", 2, 0), ("rgcn", 1, 0), ("rgcn", 2, 0),
    ("han", 1, 2),
])
def test_stage_scopes_reach_the_lowered_program(tiny_hg, model, layers,
                                                partitions):
    cfg = _cfg(model, layers=layers, partitions=partitions)
    m = get_model(cfg)
    batch = m.prepare(tiny_hg)
    params = m.init(jax.random.key(0), batch)
    text = jax.jit(m.forward).lower(params, batch).as_text(debug_info=True)
    found = set(re.findall(r"jit\(forward\)/([A-Za-z0-9_.]+)", text))
    want = _scopes_expected(m.executor, params, batch)
    assert ("gather_halo" in want) == bool(partitions)
    assert want <= found, want - found
