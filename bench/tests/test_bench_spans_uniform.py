"""The program-span readers on a traced run of ``han_imdb.serve_uniform``:
the checks of ``test_bench_spans`` (finite readings, a request's three
latency parts adding up to its latency, the program's ``finished_at``
within 1 ms of the benchmark's own stamp) on the uniform-id control."""
import pytest

from bench.tests import test_bench_spans as spans


@pytest.mark.parametrize("model,traffic,workload", [
    ("han", "serve_uniform", "han_imdb.serve_uniform"),
])
def test_program_span_readers_on_a_traced_run(cell, monkeypatch, model,
                                              traffic, workload):
    monkeypatch.setitem(spans.NEW, workload, spans.NEW["han_imdb.serve_zipf"])
    spans.test_program_span_readers_on_a_traced_run(
        cell, monkeypatch, model, traffic, workload)
