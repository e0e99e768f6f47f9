"""MAGNN in the benchmark (``bench/models/magnn.py``): the pinned weights,
reference logits and work count, the program against the reference on the
small IMDB copy, and four departures from the paper that the comparison
must catch."""
import hashlib
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import graph, harness, reference, weights
from bench.tests.conftest import BENCH, FIXTURES, tiny_cell
from repro.core import stages

_INSTANCE_AGGREGATE = stages.instance_aggregate

SEED = 2**33 + 5
PIN = {
    "weights": "a53d10431a5da48050559599d082a1ed"
               "d8b39859500d791fe650397810a220f5",
    "logits": "ae8bbc970ac6f24ed9a964bc08517c3b"
              "ea350ff9239d259af951e8b1df3f06ee",
    "work": {"flops": 8606412112.0, "bytes": 190903504.0,
             "edges": 628722.0, "fp_flops": 5770618624.0,
             "feature_bytes": 180331832.0},
}
FUNCTIONS = ("program_kwargs", "weight_shapes", "weight_scale",
             "program_leaf", "forward", "reference_args", "inputs",
             "row_cap", "work")


def _tiny():
    return json.loads((FIXTURES / "tiny_magnn.json").read_text())


def _digest(flat):
    h = hashlib.sha256()
    for k in sorted(flat):
        a = np.asarray(flat[k])
        h.update(k.encode())
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _index(g, model):
    """The program's ``instances`` layout of every instance of the graph."""
    out = []
    for mp in g.metapaths:
        inst = model.instances(g, mp)
        cnt = np.bincount(inst[:, 0], minlength=g.counts[g.target])
        nodes = np.zeros((len(cnt), cnt.max(), len(mp)), np.int32)
        mask = np.zeros((len(cnt), cnt.max()), np.float32)
        pos = np.arange(len(inst)) - np.repeat(np.cumsum(cnt) - cnt, cnt)
        nodes[inst[:, 0], pos] = inst
        mask[inst[:, 0], pos] = 1.0
        out.append((nodes, mask))
    return {"instances": out}


def _logits(pad):
    cfg = _tiny()
    model = harness.model("magnn")
    g = graph.make_graph(cfg["graph"])
    local = {t: np.arange(n) for t, n in g.counts.items()}
    xs, paths = model.inputs(g, _index(g, model), local, 8, True,
                             lambda fn, key: fn(g, key))
    flat = weights.make(model, cfg, SEED)
    w = weights.nested(cfg, {k: np.asarray(v) for k, v in flat.items()})
    return reference.Reference(model, cfg, w, "highest")(xs, paths, 8, pad)


def test_module_gives_every_function():
    model = harness.model("magnn")
    assert [f for f in FUNCTIONS
            if not callable(getattr(model, f, None))] == []


def test_weights_are_the_pinned_bits():
    flat = weights.make(harness.model("magnn"), _tiny(), SEED)
    assert flat["cls"].shape == (8 * 64, 3)
    assert _digest(flat) == PIN["weights"]


@pytest.mark.parametrize("pad", [True, False])
def test_reference_logits_are_the_pinned_bits(pad):
    logits = _logits(pad)
    assert logits.dtype == np.float64 and np.all(np.isfinite(logits))
    digest = hashlib.sha256(np.ascontiguousarray(logits).tobytes())
    assert digest.hexdigest() == PIN["logits"]


def test_work_is_the_pinned_count():
    cfg = json.loads((BENCH / "configs" / "magnn_imdb.json").read_text())
    assert cfg["model"] == "magnn"
    w = harness.model("magnn").work(cfg, graph.make_graph(cfg["graph"]))
    assert w == PIN["work"]
    assert w["edges"] == 175622 + 453100  # every MDM and MAM instance


# ---------------------------------------------------------------------------
# the program against the reference, and what the comparison must catch
# ---------------------------------------------------------------------------

def _feature_products(rows):
    """The encoder this repository had: each position multiplied, as
    complex numbers, by the features of the nodes before it on the path,
    the products averaged."""
    def mul(x, y):
        a, b = x[..., 0::2], x[..., 1::2]
        c, d = y[..., 0::2], y[..., 1::2]
        return jnp.stack([a * c - b * d, a * d + b * c], -1).reshape(
            jnp.broadcast_shapes(x.shape, y.shape))

    acc = out = rows[0]
    for h in rows[1:]:
        acc = mul(acc, h)
        out = out + acc
    return out / len(rows)


def _split_heads(p, h_tgt, enc, mask):
    """HAN's convention: head k reads and writes only the k-th D/H slice."""
    heads, d = p["a_dst"].shape
    sl = np.kron(np.eye(heads), np.ones((1, d // heads))).astype(np.float32)
    p = {k: v * sl for k, v in p.items()}
    return _INSTANCE_AGGREGATE(p, h_tgt, enc, mask) * sl[None]


def _no_conjugate(rel, a, b):
    """The reverse relation rotated by the base vector itself."""
    r = rel[f"{a}|{b}"] if f"{a}|{b}" in rel else rel[f"{b}|{a}"]
    return r / jnp.sqrt((r * r).sum(-1, keepdims=True))


MUTATIONS = {
    "none": None,
    "cap16": None,
    "feature_products": ("rotation_encoder", _feature_products),
    "split_heads": ("instance_aggregate", _split_heads),
    "no_conjugate": ("edge_rotation", _no_conjugate),
}


@pytest.mark.parametrize("mutation", sorted(MUTATIONS))
def test_program_against_the_reference(monkeypatch, mutation):
    """The program's logits on every instance of the small IMDB copy match
    the reference's; a cap of 16 instances, the old feature-product
    encoder, split 8-wide heads or a dropped conjugate each fail."""
    c = tiny_cell("magnn", "full", "magnn_imdb.full")
    if mutation == "cap16":
        c["config"]["max_instances"] = 16
    elif MUTATIONS[mutation] is not None:
        name, fn = MUTATIONS[mutation]
        monkeypatch.setattr(stages, name, fn)
    out = harness.run_cell(c, SEED + 1, 0.2, False, time.perf_counter())
    err = out["checks"]["logit_rel_err"]
    assert out["checks"]["edge_faults"]["value"] == 0
    if mutation == "none":
        assert out["correct"], out["checks"]
    else:
        assert err["value"] > err["limit"], (mutation, err)
        assert not out["correct"]


def test_an_instance_off_the_graph_is_an_edge_fault():
    model = harness.model("magnn")
    g = graph.make_graph(_tiny()["graph"])
    local = {t: np.arange(n) for t, n in g.counts.items()}
    index = _index(g, model)
    nodes, mask = index["instances"][0]
    v, i = np.argwhere(mask > 0)[0]
    adj = g.rel("M", "D")
    nodes[v, i, 1] = int(np.flatnonzero(adj[v].toarray()[0] == 0)[0])
    with pytest.raises(reference.BadEdges, match="graph does not have"):
        model.inputs(g, index, local, 8, True, lambda fn, key: fn(g, key))
    nodes[v, i, 0] = (v + 1) % len(mask)
    with pytest.raises(reference.BadEdges, match="another target"):
        model.inputs(g, index, local, 8, True, lambda fn, key: fn(g, key))
