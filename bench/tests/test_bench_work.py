"""Counted work: pinned to hand arithmetic at the IMDB statistics."""
import json

import pytest

from bench import graph, harness, work
from bench.tests.conftest import BENCH


@pytest.fixture(scope="module")
def imdb():
    cfgs = {m: json.loads((BENCH / "configs" / f"{m}_imdb.json").read_text())
            for m in ("han", "rgcn")}
    return cfgs, graph.make_graph(cfgs["han"]["graph"])


def _forward(cfg, g):
    return harness.model(cfg["model"]).work(cfg, g)


def test_han_fp_is_the_target_projection(imdb):
    cfgs, g = imdb
    w = _forward(cfgs["han"], g)
    assert w["fp_flops"] == 2 * 4278 * 3066 * 64
    assert w["feature_bytes"] == 4 * 4278 * 3066
    assert 54e6 < w["bytes"] < 58e6  # about 55 MB: features + edges


def test_rgcn_fp_is_5p77_gflop(imdb):
    cfgs, g = imdb
    w = _forward(cfgs["rgcn"], g)
    assert w["fp_flops"] == 2 * 64 * (4278 * 3066 + 2081 * 2081 + 5257 * 5257)
    assert abs(w["fp_flops"] - 5.77e9) < 0.01e9
    assert abs(w["feature_bytes"] - 180.3e6) < 0.1e6


def test_edges_are_capped_real_edges(imdb):
    cfgs, g = imdb
    import numpy as np

    deg = np.diff(graph.metapath_adjacency(g, ["M", "D", "M"]).indptr)
    assert work.capped_edges(deg, 64) == np.minimum(deg, 64).sum()
    assert work.capped_edges(np.array([3, 100, 64]), 64) == 3 + 64 + 64


@pytest.mark.parametrize("model", ["han", "rgcn"])
def test_count_ignores_the_layout_switches(imdb, model):
    """The count reads widths and the graph, never the program's layout:
    the fused and the csr layouts get the same yardstick."""
    cfgs, g = imdb
    base = _forward(cfgs[model], g)
    for switch in ({"fused": False}, {"fused": True, "degree_buckets": 3}):
        assert _forward({**cfgs[model], **switch}, g) == base


def test_peaks_carry_their_source_and_refuse_unknown_kinds():
    assert work.peaks("TPU v5 lite") == {"flops": 197e12,
                                         "hbm_bytes_per_s": 819e9}
    with pytest.raises(ValueError):
        work.peaks("TPU v9 imaginary")
    t = work.least_time_s({"flops": 197e12, "bytes": 1.0},
                          work.peaks("TPU v5 lite"))
    assert t == {"seconds": 1.0, "bound": "compute"}
