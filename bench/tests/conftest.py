"""Shared pieces of the benchmark's CPU tests: the small copies of the
configurations and a cell built from them."""
import json
from pathlib import Path

import pytest

from bench import harness

FIXTURES = Path(__file__).resolve().parent / "fixtures"
BENCH = FIXTURES.parents[1]


def tiny_cell(model: str, traffic: str, workload: str) -> dict:
    """A cell of ``BENCHMARK.json`` with its configuration swapped for the
    small copy, its limits as committed, and the open loop at 50 req/s."""
    spec = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    if spec["kind"] == "open":
        spec["rate_per_s"] = 50
    cfg = json.loads((FIXTURES / f"tiny_{model}.json").read_text())
    return {
        "name": workload, "chips": 1, "config": cfg,
        "model": harness.model(cfg["model"]),
        "traffic": spec, "end_to_end": [], "per_layer": [],
        "limits": json.loads((BENCH / "limits"
                              / f"{workload}.json").read_text()),
    }


@pytest.fixture
def cell():
    return tiny_cell
