"""Run one cell of the benchmark once: set up, measure a window, check the
outputs against the plain reference, and report.

Everything is found by name.  ``BENCHMARK.json`` maps a workload to a
configuration file and a traffic mix; the traffic file
``bench/traffic/<traffic>.json`` is read by the one generator in
``bench/traffic.py``; each per-layer metric is the ``read(ctx)`` function of
``bench/metrics/<name>.py``; each cell's limits are in
``bench/limits/<workload>.json``; everything that differs between models
is in the module ``bench/models/<model>.py`` that the configuration's
``"model"`` names (:func:`model`).

The program is driven through the entry the ``serve --hgnn`` launcher
uses: ``HGNNConfig(fused=True)`` with the configuration's widths and every
path switch at its default, ``build_hgnn_infer``, then ``HGNNInferEngine``
for full-graph traffic or ``HGNNServeEngine`` for sampled serving.  The
benchmark makes the graph, the weights and the requests; the program gets
them through that API.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import graph as graphs
from bench import reference, traffic, weights, work

ROOT = Path(__file__).resolve().parents[1]
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str, root: Path = ROOT) -> Dict:
    """The workload ``name`` with its configuration, traffic, metric names
    and limits, all read from files named by ``BENCHMARK.json``."""
    spec = load_json(root / "BENCHMARK.json")
    wl = {w["name"]: w for w in spec["workloads"]}.get(name)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]

    def reports(m):
        return "workloads" not in m or name in m["workloads"]

    cfg = load_json(root / entry["file"])
    return {
        "name": name,
        "chips": int(wl["chips"]),
        "config": cfg,
        "model": model(cfg["model"], root),
        "traffic": load_json(root / "bench" / "traffic"
                             / f"{wl['traffic']}.json"),
        "end_to_end": [m for m in spec["end_to_end"] if reports(m)],
        "per_layer": [m for m in spec["per_layer"] if reports(m)],
        "limits": load_json(root / "bench" / "limits" / f"{name}.json"),
    }


def _module(path: Path, name: str):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root: Path = ROOT) -> Callable:
    path = root / "bench" / "metrics" / f"{metric}.py"
    return _module(path, f"bench_metric_{metric.replace('.', '_')}").read


class UnknownModel(LookupError):
    pass


def model(name: str, root: Path = ROOT):
    """The module ``bench/models/<name>.py``, which gives everything the
    benchmark knows of one model:

    - ``program_kwargs(cfg)``: the ``HGNNConfig`` arguments beyond the
      shared widths;
    - ``weight_shapes(cfg)``: ``{name: shape}`` of the model's own weights
      (``fp.<type>`` and ``cls`` are :mod:`bench.weights`'s), and
      ``weight_scale(name, shape)``: each weight's init scale;
    - ``program_leaf(flat, layer, keys)``: the benchmark weight behind one
      program leaf (``keys`` its path inside layer ``layer``);
    - ``forward(be, cfg, w, *args)``: the plain float32 forward, and
      ``reference_args(cfg, xs, tables, n, edges, cap, pad)``: its
      ``args``, padded;
    - ``inputs(g, index, local, cap, full_rows, adjacency)``: the feature
      tables and the validated edge lists of one batch;
    - ``row_cap(cfg, spec)``: the neighbors a served row may keep;
    - ``work(cfg, g)``: the counted work of one full-graph forward,
      ``{"flops", "bytes", "edges", "fp_flops", "feature_bytes"}``, as
      :mod:`bench.work` defines it.
    """
    path = root / "bench" / "models" / f"{name}.py"
    if not path.is_file():
        raise UnknownModel(f"model {name!r} has no module "
                           f"bench/models/{name}.py (looked for {path})")
    return _module(path, f"bench_model_{name}")


def seed_word(seed: int) -> int:
    """A 32-bit word from any whole-number seed."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


# ---------------------------------------------------------------------------
# spans, compile counting, the sampler proxy, stamped requests
# ---------------------------------------------------------------------------

class Spans:
    """``jax.profiler.TraceAnnotation`` when tracing, else nothing."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """Counts jaxpr traces and backend compiles while ``armed``."""

    def __init__(self):
        import jax

        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


class SamplerProxy:
    """Stands in for the program's sampler: times each ``sample`` call under
    the ``bench.sample`` span and keeps, per call, what the check needs
    (the batch's local->global ids and its index arrays, never its
    features).  Everything else passes through."""

    def __init__(self, sampler, spans: Spans):
        self._sampler = sampler
        self._spans = spans
        self.calls: List[Dict] = []
        self.seconds: List[float] = []
        self.recording = False

    def __getattr__(self, name):
        return getattr(self._sampler, name)

    def sample(self, *args, **kwargs):
        t0 = time.perf_counter()
        with self._spans("bench.sample"):
            sb = self._sampler.sample(*args, **kwargs)
        if self.recording:
            self.seconds.append(time.perf_counter() - t0)
            self.calls.append({
                "local": sb.local, "target_ids": sb.target_ids,
                "target_rows": sb.target_rows,
                "index": {k: v for k, v in sb.batch.items()
                          if k not in ("feats", "feat_dims")},
            })
        return sb


def request_class():
    """``HGNNRequest`` that stamps when its status turns terminal and logs
    which sampler call served each chunk of its ids."""
    from repro.serve.engine import HGNNRequest
    from repro.serve.resilience import TERMINAL

    @dataclasses.dataclass
    class BenchRequest(HGNNRequest):
        due: float = 0.0
        t_submit: Optional[float] = None
        t_done: Optional[float] = None
        proxy: Optional[SamplerProxy] = None
        chunks: List = dataclasses.field(default_factory=list)

        def __setattr__(self, name, value):
            if (name == "status" and value in TERMINAL
                    and getattr(self, "t_done", None) is None):
                object.__setattr__(self, "t_done", time.perf_counter())
            elif name == "_done" and getattr(self, "proxy", None) is not None:
                start = getattr(self, "_done", 0)
                if value > start:
                    self.chunks.append(
                        (len(self.proxy.calls) - 1, int(start), int(value)))
            object.__setattr__(self, name, value)

    return BenchRequest


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def program_config(cell: Dict, seed: int):
    from repro.configs.base import HGNNConfig

    cfg, spec = cell["config"], cell["traffic"]
    kw = dict(model=cfg["model"], dataset=cfg["dataset"], fused=True,
              layers=cfg["layers"], hidden=cfg["hidden"],
              n_classes=cfg["n_classes"], max_degree=cfg["max_degree"],
              seed=seed_word(seed), **cell["model"].program_kwargs(cfg))
    if spec["kind"] != "full":
        kw["fanout"] = int(spec["fanout"])
    return HGNNConfig(**kw)


def set_up(cell: Dict, seed: int, spans: Spans) -> Dict:
    import jax

    from repro.core.hgraph import HeteroGraph
    from repro.launch.serve import build_hgnn_infer

    cfg, spec = cell["config"], cell["traffic"]
    g = graphs.make_graph(cfg["graph"])
    hg = HeteroGraph(dict(g.counts), g.feats, g.relations,
                     name=cfg["dataset"])
    pcfg = program_config(cell, seed)
    built = build_hgnn_infer(pcfg, hg, rng=jax.random.key(pcfg.seed))
    flat = weights.make(cell["model"], cfg, seed)
    params = weights.to_program(cell["model"], flat, built.params)
    out = {"graph": g, "hg": hg, "pcfg": pcfg, "built": built,
           "flat": flat, "params": params}
    if spec["kind"] == "full":
        from repro.serve.engine import HGNNInferEngine

        engine = HGNNInferEngine(built.executor, params, built.batch,
                                 fn=built.fn)
        for _ in range(2):
            np.asarray(engine.infer())
        out["engine"] = engine
        return out
    from repro.serve.engine import HGNNServeEngine
    from repro.serve.sampler import HGNNSampler

    proxy = SamplerProxy(HGNNSampler(built.plan, pcfg, hg), spans)
    engine = HGNNServeEngine(built.executor, params, proxy,
                             slots=int(spec["slots"]),
                             slot_targets=int(spec["slot_targets"]),
                             fn=built.fn)
    engine.warmup()
    # the host path of the cell's own traffic, on requests of another seed
    req = request_class()
    warm = traffic.ClosedLoop({**spec, "clients": 4 * int(spec["slots"])},
                              g.counts[g.target], seed + 1, period=256)
    engine.serve([req(targets=x) for x in warm.next_batch()])
    out.update(engine=engine, proxy=proxy)
    return out


# ---------------------------------------------------------------------------
# the measured windows
# ---------------------------------------------------------------------------

def window_full(st: Dict, seconds: float, spans: Spans) -> Dict:
    engine = st["engine"]
    outs = []
    t0 = time.perf_counter()
    t_end = t0 + seconds
    with spans("bench.window"):
        while True:
            with spans("bench.forward"):
                x = engine.infer()
            with spans("bench.fetch"):
                outs.append(np.asarray(x))
            if time.perf_counter() >= t_end:
                break
    window_s = time.perf_counter() - t0
    return {"outs": outs, "window_s": window_s, "attempted": len(outs),
            "failed": 0, "forwards": len(outs),
            "e2e": {"fwd_ms": 1e3 * window_s / len(outs)}}


def window_open(st: Dict, spec: Dict, seconds: float, seed: int,
                spans: Spans) -> Dict:
    from repro.serve.resilience import OK

    engine, proxy, g = st["engine"], st["proxy"], st["graph"]
    req = request_class()
    plan = traffic.open_loop(spec, seconds, g.counts[g.target], seed)
    reqs = [req(targets=p["targets"], proxy=proxy) for p in plan]
    steps, late = [], []
    proxy.recording = True
    t0 = time.perf_counter()
    for r, p in zip(reqs, plan):
        r.due = t0 + p["due"]
    i = 0
    with spans("bench.window"):
        while i < len(reqs):
            now = time.perf_counter()
            if reqs[i].due > now:
                with spans("bench.wait_arrival"):
                    time.sleep(reqs[i].due - now)
                now = time.perf_counter()
                late.append(now - reqs[i].due)
            j = i
            while j < len(reqs) and reqs[j].due <= now:
                reqs[j].t_submit = now
                j += 1
            with spans("bench.serve_call"):
                engine.serve(reqs[i:j])
            steps += engine.step_log
            i = j
    window_s = time.perf_counter() - t0
    proxy.recording = False
    lat = np.asarray([1e3 * (r.t_done - r.due) for r in reqs])
    ok = [r for r in reqs if r.status == OK]
    return {"reqs": reqs, "ok": ok, "steps": steps, "window_s": window_s,
            "attempted": len(reqs), "failed": len(reqs) - len(ok),
            "late_s": late,
            "e2e": {"serve_p50_ms": float(np.percentile(lat, 50)),
                    "serve_p95_ms": float(np.percentile(lat, 95))}}


def window_closed(st: Dict, spec: Dict, seconds: float, seed: int,
                  spans: Spans) -> Dict:
    from repro.serve.resilience import OK

    engine, proxy, g = st["engine"], st["proxy"], st["graph"]
    req = request_class()
    gen = traffic.ClosedLoop(spec, g.counts[g.target], seed)
    reqs, steps = [], []
    proxy.recording = True
    t0 = time.perf_counter()
    t_end = t0 + seconds
    with spans("bench.window"):
        while time.perf_counter() < t_end:
            now = time.perf_counter()
            batch = [req(targets=x, due=now, t_submit=now, proxy=proxy)
                     for x in gen.next_batch()]
            with spans("bench.serve_call"):
                engine.serve(batch)
            steps += engine.step_log
            reqs += batch
    window_s = time.perf_counter() - t0
    proxy.recording = False
    ok = [r for r in reqs if r.status == OK]
    done = sum(len(r.targets) for r in ok if r.t_done <= t_end)
    return {"reqs": reqs, "ok": ok, "steps": steps, "window_s": window_s,
            "attempted": len(reqs), "failed": len(reqs) - len(ok),
            "late_s": [],
            "e2e": {"serve_targets_per_s": done / seconds}}


# ---------------------------------------------------------------------------
# the check against the plain reference
# ---------------------------------------------------------------------------

CONTROLS = ("high", "bf16")


def _references(cell: Dict, st: Dict, control: bool) -> List:
    """The reference (``highest``) and, with ``control``, the controls
    (``CONTROLS``), over the benchmark's own weights."""
    w = weights.nested(cell["config"],
                       {k: np.asarray(v) for k, v in st["flat"].items()})
    return [reference.Reference(cell["model"], cell["config"], w, p)
            for p in ("highest",) + (CONTROLS if control else ())]


def _inputs(cell: Dict, st: Dict, index: Dict, local: Dict, cap: int,
            full_rows: bool):
    """Feature tables and validated edge lists (local ids) of one batch:
    the whole graph (identity ``local``) or one sampled step.  The model
    asks for each adjacency as ``adjacency(fn, key)``, ``fn(graph, key)``
    made once per run."""
    cache = st.setdefault("adjacency", {})

    def adjacency(fn, key):
        k = (fn.__name__, tuple(key))
        if k not in cache:
            cache[k] = fn(st["graph"], key)
        return cache[k]

    return cell["model"].inputs(st["graph"], index, local, cap, full_rows,
                                adjacency)


def check(cell: Dict, st: Dict, win: Dict, seed: int,
          control: bool = False, rows: int = 256) -> Dict:
    """``{"logit_rel_err", "edge_faults", "compared"}`` (and
    ``"control_rel_err"`` per control precision when ``control``): every
    forward of a full cell,
    or a seeded sample of a serving cell's answered requests with the
    longest among them, against the float32 reference at ``highest``."""
    refs = _references(cell, st, control)
    try:
        if cell["traffic"]["kind"] == "full":
            local = {ty: np.arange(n) for ty, n in st["graph"].counts.items()}
            xs, edges = _inputs(cell, st, st["built"].batch, local,
                                cell["config"]["max_degree"], True)
            wants = [r(xs, edges, cell["config"]["max_degree"], False)
                     for r in refs]
            errs = [max(reference.relative_error(o, wants[0])
                        for o in win["outs"])]
            compared = len(win["outs"]) * wants[0].size
        else:
            got, wants = _served_rows(cell, st, win, seed, refs, rows)
            errs = [reference.relative_error(got, wants[0])]
            compared = got.size
    except reference.BadEdges as e:
        print(f"check: {e}", file=sys.stderr)
        out = {"logit_rel_err": math.inf, "edge_faults": 1, "compared": 0}
        if control:
            out["control_rel_err"] = {p: math.inf for p in CONTROLS}
        return out
    out = {"logit_rel_err": errs[0], "edge_faults": 0, "compared": compared}
    if control:
        out["control_rel_err"] = {
            p: reference.relative_error(w, wants[0])
            for p, w in zip(CONTROLS, wants[1:])}
    return out


def _served_rows(cell, st, win, seed, refs, rows):
    rng = np.random.default_rng(seed_word(seed) + 1)
    ok = win["ok"]
    if not ok:
        raise reference.BadEdges("no request was answered")
    order = list(rng.permutation(len(ok)))
    longest = max(range(len(ok)), key=lambda i: len(ok[i].targets))
    order.remove(longest)
    picked, n = [], 0
    for i in [longest] + order:
        picked.append(ok[i])
        n += len(ok[i].targets)
        if n >= rows:
            break
    need: Dict[int, List] = {}
    for r in picked:
        uniq, inv = np.unique(np.asarray(r.targets, np.int64),
                              return_inverse=True)
        for i, j in enumerate(inv):
            step = next((s for s, a, b in r.chunks if a <= j < b), None)
            if step is None or step < 0:
                raise reference.BadEdges("an answered row with no step")
            need.setdefault(step, []).append((r, i, int(uniq[j])))
    cap = cell["model"].row_cap(cell["config"], cell["traffic"])
    t = st["graph"].target
    got, wants = [], [[] for _ in refs]
    for step, items in sorted(need.items()):
        call = st["proxy"].calls[step]
        local = {ty: np.asarray(v, np.int64)
                 for ty, v in call["local"].items()}
        rows_ = np.asarray(call["target_rows"], np.int64)
        tids = np.asarray(call["target_ids"], np.int64)
        if not np.array_equal(local[t][rows_], tids):
            raise reference.BadEdges("a target's row holds another vertex")
        xs, edges = _inputs(cell, st, _host(call["index"]), local, cap,
                            False)
        outs = [r(xs, edges, cap, True) for r in refs]
        row_of = dict(zip(tids.tolist(), rows_.tolist()))
        for r, i, tid in items:
            got.append(np.asarray(r.logits)[i])
            for k, o in enumerate(outs):
                wants[k].append(o[row_of[tid]])
    return np.stack(got), [np.stack(w) for w in wants]


def _host(tree):
    import jax

    return jax.tree_util.tree_map(np.asarray, tree)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def limits_line(checks: Dict) -> List[str]:
    return [f"check {k} {v['value']!r} limit {v['limit']!r}"
            for k, v in checks.items()]


def run_cell(cell: Dict, seed: int, seconds: float, trace: bool,
             t_proc0: float, control: bool = False) -> Dict:
    """Set up, measure, check.  Returns the result line's object (its keys
    in the contract's order, ``checks`` last) plus ``_notes`` for the
    earlier lines."""
    import jax

    devs = jax.devices()
    spans = Spans(trace)
    counter = CompileCounter()
    spec = cell["traffic"]
    st = set_up(cell, seed, spans)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_proc0
    counter.armed = True
    if spec["kind"] == "full":
        win = window_full(st, seconds, spans)
    elif spec["kind"] == "open":
        win = window_open(st, spec, seconds, seed, spans)
    else:
        win = window_closed(st, spec, seconds, seed, spans)
    counter.armed = False
    counter.close()
    reduced = None
    if trace:
        from bench import trace as traces

        jax.profiler.stop_trace()
        path = traces.find_xplane(trace_dir)
        reduced = traces.reduce_file(path) if path else None
        shutil.rmtree(trace_dir, ignore_errors=True)
    stats = devs[0].memory_stats() or {}
    peak = int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs))
    st["built"] = st["built"]._replace(params=None)
    st.pop("params")
    st["engine"].params = None
    res = check(cell, st, win, seed, control=control)
    limits = cell["limits"]
    checks = {
        "logit_rel_err": {"value": res["logit_rel_err"],
                          "limit": limits["logit_rel_err"]},
        "edge_faults": {"value": res["edge_faults"], "limit": 0},
        "recompiles": {"value": counter.count, "limit": 0},
        "unanswered": {"value": win["failed"], "limit": 0},
    }
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    ctx = {"window": win, "trace": reduced,
           "work": (cell["model"].work(cell["config"], st["graph"])
                    if spec["kind"] == "full" else None),
           "peaks": work.peaks(devs[0].device_kind)
           if devs[0].platform == "tpu" else None,
           "sample_s": st["proxy"].seconds if "proxy" in st else []}
    if trace:
        metrics = {}
        for m in cell["per_layer"]:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        vals = {**win["e2e"], "setup_s": setup_s}
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": int(win["attempted"]),
           "failed": int(win["failed"]), "metrics": metrics,
           "device": device}
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in reduced["device_ops"]],
            "idle_gaps": [[n, s] for n, s in reduced["idle_gaps"]]}
    out["checks"] = checks
    late = win.get("late_s") or [0.0]
    out["_notes"] = {
        "setup_s": setup_s, "window_s": win["window_s"],
        "recompiles_after_warmup": counter.count,
        "generator_late_ms_max": 1e3 * max(late),
        "generator_late_ms_p99": 1e3 * float(np.percentile(late, 99)),
        "compared": res["compared"],
        "steps": len(win.get("steps", [])),
        "bytes_in_use": stats.get("bytes_in_use"),
    }
    if ctx["work"] is not None and ctx["peaks"] is not None:
        least = work.least_time_s(ctx["work"], ctx["peaks"])
        out["_notes"]["roofline_bound"] = least["bound"]
        out["_notes"]["least_ms"] = 1e3 * least["seconds"]
    if control:
        out["_notes"]["control_rel_err"] = res["control_rel_err"]
    return out


def main(args, t_proc0: float) -> int:
    try:
        cell = find_cell(args.workload)
    except (LookupError, OSError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    try:
        import jax

        import repro  # noqa: F401 — the program under test
    except ImportError as e:
        print(f"bench: cannot import the program ({e})", file=sys.stderr)
        return 2
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU (JAX platform {devs[0].platform!r}); the "
              "benchmark runs on the chip only", file=sys.stderr)
        return 3
    if len(devs) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX "
              f"found {len(devs)}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    out = run_cell(cell, args.seed, float(args.seconds), bool(args.trace),
                   t_proc0)
    emit(out)
    return 0


def _finite(x):
    """JSON has no infinity: an unbounded error prints as 1e30."""
    if isinstance(x, float) and not math.isfinite(x):
        return 1e30
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    return x


def emit(out: Dict) -> None:
    out = _finite(out)
    notes = out.pop("_notes")
    print(" ".join(f"{k}={v}" for k, v in notes.items()), flush=True)
    for line in limits_line(out["checks"]):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
