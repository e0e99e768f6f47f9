"""Each model of the benchmark is one module, ``bench/models/<model>.py``,
found by the configuration's ``"model"``.

The pins were read before the models moved out of the shared modules into
their own: the weights drawn from one seed, the reference logits on one
fixed input, and the counted work of one full-size forward.  Each holds bit
for bit, so the move changed no number that a run reads.  A model joins
these tests with its name in ``PINS`` and its fixed input in ``INDEX``.
"""
import hashlib
import json
import shutil

import numpy as np
import pytest

from bench import graph, harness, reference, weights
from bench.tests.conftest import BENCH, FIXTURES

SEED = 2**33 + 5

PINS = {
    "han": {
        "weights": "11cd3af0269ada69f29ee8b3ec2def10"
                   "80a347dd752ac3c5ab9a67dafdab1089",
        "logits": "a38a312d02c118520f15a7ba2f4a676d"
                  "f0fc9271c3f8ff844dc24c3ab5dbd43d",
        "config": "han_imdb",
        "work": {"flops": 1865231832.0, "bytes": 54235756.0,
                 "edges": 216079.0, "fp_flops": 1678892544.0,
                 "feature_bytes": 52465392.0},
    },
    "rgcn": {
        "weights": "383ffa2c3665a77cf20cb1f4275e51e0"
                   "1f9a9fb733596d2bfbee1a89ca60230c",
        "logits": "fda9779356cf46ec437081319653b950"
                  "c1239b7ac4e867b4a3eca149f3098a90",
        "config": "rgcn_imdb",
        "work": {"flops": 6110097856.0, "bytes": 183405844.0,
                 "edges": 32763.0, "fp_flops": 5770618624.0,
                 "feature_bytes": 180331832.0},
    },
}
MODELS = sorted(PINS)
FUNCTIONS = ("program_kwargs", "weight_shapes", "weight_scale",
             "program_leaf", "forward", "reference_args", "inputs",
             "row_cap", "work")


def _first(adj, cap):
    """The first ``cap`` neighbors of every row, as csr ``(seg, idx)``."""
    deg = np.minimum(np.diff(adj.indptr), cap)
    seg = np.repeat(np.arange(adj.shape[0]), deg)
    idx = np.concatenate([adj.indices[a: a + k]
                          for a, k in zip(adj.indptr[:-1], deg)])
    return seg.astype(np.int64), idx.astype(np.int64)


# The fixed input of each model: the whole tiny graph in the program's csr
# layout, every row keeping its first max_degree neighbors.
INDEX = {
    "han": lambda g, cap: {"edges": [
        _first(graph.metapath_adjacency(g, mp), cap)
        for mp in g.metapaths]},
    "rgcn": lambda g, cap: {"rels": {
        k: _first(graph.in_adjacency(g, k), cap)
        for k in sorted(g.relations)}},
}


def _tiny(name):
    return json.loads((FIXTURES / f"tiny_{name}.json").read_text())


def _weights_digest(flat):
    h = hashlib.sha256()
    for k in sorted(flat):
        a = np.asarray(flat[k])
        h.update(k.encode())
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _logits(model, name, cfg, pad):
    g = graph.make_graph(cfg["graph"])
    cap = cfg["max_degree"]
    local = {t: np.arange(n) for t, n in g.counts.items()}
    xs, edges = model.inputs(g, INDEX[name](g, cap), local, cap, True,
                             lambda fn, key: fn(g, key))
    flat = weights.make(model, cfg, SEED)
    w = weights.nested(cfg, {k: np.asarray(v) for k, v in flat.items()})
    return reference.Reference(model, cfg, w, "highest")(xs, edges, cap, pad)


@pytest.mark.parametrize("name", MODELS)
def test_module_gives_every_function(name):
    model = harness.model(name)
    missing = [f for f in FUNCTIONS if not callable(getattr(model, f, None))]
    assert missing == []


@pytest.mark.parametrize("name", MODELS)
def test_weights_are_the_pinned_bits(name):
    cfg = _tiny(name)
    flat = weights.make(harness.model(name), cfg, SEED)
    assert _weights_digest(flat) == PINS[name]["weights"]


@pytest.mark.parametrize("pad", [True, False])
@pytest.mark.parametrize("name", MODELS)
def test_reference_logits_are_the_pinned_bits(name, pad):
    logits = _logits(harness.model(name), name, _tiny(name), pad)
    assert logits.dtype == np.float64 and np.all(np.isfinite(logits))
    digest = hashlib.sha256(np.ascontiguousarray(logits).tobytes())
    assert digest.hexdigest() == PINS[name]["logits"]


@pytest.mark.parametrize("name", MODELS)
def test_work_is_the_pinned_count(name):
    cfg = json.loads((BENCH / "configs" / f"{PINS[name]['config']}.json")
                     .read_text())
    assert cfg["model"] == name
    w = harness.model(name).work(cfg, graph.make_graph(cfg["graph"]))
    assert w == PINS[name]["work"]


def _root(tmp_path, cfg):
    """A checkout holding one full-graph cell of configuration ``cfg``, and
    nothing under ``bench/models/``."""
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits", "models"):
        (bench / sub).mkdir(parents=True)
    shutil.copy(BENCH / "traffic" / "full.json", bench / "traffic")
    (bench / "limits" / "tiny.full.json").write_text(
        json.dumps({"logit_rel_err": 1e-6}))
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.full", "config": "tiny",
                       "traffic": "full", "chips": 1}],
        "end_to_end": [], "per_layer": []}))
    return tmp_path


def test_a_model_with_no_module_fails_naming_its_path(tmp_path):
    with pytest.raises(harness.UnknownModel, match="bench/models/gat.py"):
        harness.model("gat")
    root = _root(tmp_path, _tiny(MODELS[0]))
    with pytest.raises(harness.UnknownModel,
                       match=f"bench/models/{MODELS[0]}.py"):
        harness.find_cell("tiny.full", root)


@pytest.mark.parametrize("name", MODELS)
def test_a_copied_module_is_found_under_its_new_name(tmp_path, name):
    copy = f"{name}_copy"
    root = _root(tmp_path, {**_tiny(name), "model": copy})
    path = root / "bench" / "models" / f"{copy}.py"
    shutil.copy(BENCH / "models" / f"{name}.py", path)
    with pytest.raises(harness.UnknownModel):
        harness.model(copy)  # not in the repository's own bench/models/
    cell = harness.find_cell("tiny.full", root)
    model = cell["model"]
    assert model.__file__ == str(path)
    cfg = cell["config"]
    assert cfg["model"] == copy
    assert _weights_digest(weights.make(model, cfg, SEED)) \
        == PINS[name]["weights"]
    logits = _logits(model, name, cfg, True)
    digest = hashlib.sha256(np.ascontiguousarray(logits).tobytes())
    assert digest.hexdigest() == PINS[name]["logits"]
