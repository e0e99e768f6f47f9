"""The readers of the program's own spans and counters, on a traced run of
each serving cell at a size the CPU holds: each reads a finite value, a
request's three latency parts add up to its latency, and the program's
``finished_at`` agrees with the benchmark's own stamp."""
import math
import time

import pytest

from bench import harness

NEW = {
    "han_imdb.serve_zipf": [
        "admit_wait_ms.lat", "queue_ms.lat", "service_ms.lat",
        "sample_expand_ms.lat", "sample_gather_ms.lat",
        "sample_upload_ms.lat", "forward_host_ms.lat", "upload_mb.lat"],
    "rgcn_imdb.serve_sat": [
        "sample_expand_ms.tput", "sample_gather_ms.tput",
        "sample_upload_ms.tput", "forward_host_ms.tput", "upload_mb.tput"],
}


@pytest.mark.parametrize("model,traffic,workload", [
    ("han", "serve_zipf", "han_imdb.serve_zipf"),
    ("rgcn", "serve_sat", "rgcn_imdb.serve_sat"),
])
def test_program_span_readers_on_a_traced_run(cell, monkeypatch, model,
                                              traffic, workload):
    c = cell(model, traffic, workload)
    c["per_layer"] = [m for m in harness.find_cell(workload)["per_layer"]
                      if m["name"] in NEW[workload]]
    assert [m["name"] for m in c["per_layer"]] == NEW[workload]
    windows = []
    for name in ("window_open", "window_closed"):
        inner = getattr(harness, name)

        def keep(*a, _inner=inner, **kw):
            windows.append(_inner(*a, **kw))
            return windows[-1]

        monkeypatch.setattr(harness, name, keep)
    out = harness.run_cell(c, 2**33 + 7, 0.5, True, time.perf_counter())
    assert out["correct"], out["checks"]
    for name in NEW[workload]:
        assert math.isfinite(out["metrics"][name]["value"]), name
    win, = windows
    assert win["ok"]
    for r in win["ok"]:
        parts = ((r.admitted_at - r.due) + (r.started_at - r.admitted_at)
                 + (r.finished_at - r.started_at))
        assert parts == pytest.approx(r.finished_at - r.due, abs=1e-9)
        assert r.due <= r.admitted_at <= r.started_at <= r.finished_at
        assert abs(r.finished_at - r.t_done) < 1e-3
