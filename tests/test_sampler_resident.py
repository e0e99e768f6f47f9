"""Device-resident feature tables of the request-path sampler.

``HGNNSampler`` puts the raw per-type feature tables on the device once and
gathers each batch's rows there from uploaded int32 ids.  Each batch's
feature table must be, bit for bit, the host construction it replaces
(zero-filled to the rung cap, frontier rows copied in); an identity rung
hands over the resident table itself; the counters say which bytes went up
and which were gathered on the device; and no rung compiles after warm-up.
"""
import jax
import numpy as np
import pytest

from repro.configs.base import HGNNConfig
from repro.core.models import get_model
from repro.data.synthetic import DATASET_METAPATHS, DATASET_TARGET
from repro.serve import sampler as sampler_mod
from repro.serve.engine import HGNNRequest, HGNNServeEngine
from repro.serve.sampler import HGNNSampler

# rung 0 cuts every type of the tiny graph (40 / 15 / 25 vertices) to 12
# rows; rung 1 covers every type whole, so it is the identity
TINY_LADDER = ((4, 12), (8, 40))


def _tiny_cfg(model, **kw):
    DATASET_METAPATHS["tiny"] = [["M", "D", "M"], ["M", "A", "M"]]
    DATASET_TARGET["tiny"] = "M"
    kw = {"max_degree": 48, "max_instances": 4, "fused": True, "fanout": 4,
          "sample_ladder": TINY_LADDER, **kw}
    return HGNNConfig(model=model, dataset="tiny", hidden=16, n_heads=4,
                      n_classes=3, **kw)


def _setup(model, tiny_hg):
    """``(cfg, hg)`` for one model; GCN takes a homogeneous graph."""
    if model != "gcn":
        return _tiny_cfg(model), tiny_hg
    from repro.data.synthetic import make_reddit_like

    hg = make_reddit_like(scale=0.005)
    n = hg.node_counts["N"]
    return HGNNConfig(model="gcn", dataset="reddit", hidden=16, n_classes=5,
                      fanout=4, sample_ladder=((4, 64), (8, n))), hg


def _feature_tables(model, batch, target):
    return {target: batch["x"]} if model == "gcn" else batch["feats"]


def _host_rows(feats, ids, cap):
    """The parent's host construction of a local feature table."""
    out = np.zeros((cap,) + feats.shape[1:], feats.dtype)
    out[: len(ids)] = feats[ids]
    return out


@pytest.mark.parametrize("model", ["han", "rgcn", "magnn", "gcn"])
def test_feature_tables_match_host_construction(tiny_hg, model):
    cfg, hg = _setup(model, tiny_hg)
    m = get_model(cfg)
    sampler = HGNNSampler(m.plan(), cfg, hg)
    targets = np.random.default_rng(3).choice(
        hg.node_counts[sampler.target], 4, replace=False)
    kinds = set()
    for rung, (_t_cap, f_cap) in enumerate(sampler.ladder):
        sb = sampler.sample(targets, rung=rung)
        tables = _feature_tables(model, sb.batch, sampler.target)
        assert set(tables) <= set(sampler.resident)
        for t, got in tables.items():
            n_type = hg.node_counts[t]
            cap = min(f_cap, n_type)
            assert isinstance(got, jax.Array) and got.shape[0] == cap
            if cap == n_type:
                assert got is sampler.resident[t]
                kinds.add("identity")
                continue
            kinds.add("gathered")
            want = _host_rows(hg.features[t], sb.local[t], cap)
            np.testing.assert_array_equal(
                np.asarray(got).view(np.uint32), want.view(np.uint32))
    assert kinds == {"identity", "gathered"}


def test_upload_and_gather_counters(tiny_hg):
    """A non-identity R-GCN batch uploads ids and index tables only; the
    feature rows are counted as gathered on the device."""
    cfg = _tiny_cfg("rgcn", layers=2)
    m = get_model(cfg)
    sampler = HGNNSampler(m.plan(), cfg, tiny_hg)
    sb = sampler.sample(np.arange(4), rung=0)
    caps = {t: min(sampler.ladder[0][1], n)
            for t, n in tiny_hg.node_counts.items()}
    assert all(caps[t] < n for t, n in tiny_hg.node_counts.items())
    index_bytes = sum(x.nbytes
                      for x in jax.tree_util.tree_leaves(sb.batch["rels"]))
    id_bytes = sum(cap * 4 for cap in caps.values())
    assert sb.meta["upload_bytes"] == index_bytes + id_bytes
    assert sb.meta["resident_gather_bytes"] == sum(
        caps[t] * tiny_hg.feat_dim(t) * 4 for t in caps)
    assert (sb.meta["upload_bytes"] + sb.meta["resident_gather_bytes"]
            >= sb.meta["frontier_bytes"] > 0)

    identity = sampler.sample(np.arange(4), rung=1)
    assert identity.meta["resident_gather_bytes"] == 0
    assert identity.meta["upload_bytes"] == sum(
        x.nbytes for x in jax.tree_util.tree_leaves(identity.batch["rels"]))


def test_serving_every_rung_compiles_nothing_after_warmup(tiny_hg):
    cfg = _tiny_cfg("rgcn", layers=2)
    m = get_model(cfg)
    batch = m.prepare(tiny_hg)
    params = m.init(jax.random.key(0), batch)
    eng = HGNNServeEngine(m.executor, params,
                          HGNNSampler(m.plan(), cfg, tiny_hg), slots=2,
                          slot_targets=4, fn=jax.jit(m.forward))
    eng.warmup()
    n_gather = sampler_mod._take_rows._cache_size()
    rng = np.random.default_rng(11)
    # one-target requests fit rung 0 alone; pairs of four need rung 1
    reqs = ([HGNNRequest(targets=rng.integers(0, 40, 1)) for _ in range(3)]
            + [HGNNRequest(targets=rng.integers(0, 40, 4)) for _ in range(6)])
    eng.serve(reqs)
    assert {e["rung_index"] for e in eng.step_log} == {0, 1}
    assert all(e["recompiled"] == 0 for e in eng.step_log)
    assert sampler_mod._take_rows._cache_size() == n_gather
    assert eng.stats()["compiles_after_warmup"] == 0
    gathered = [e["resident_gather_bytes"] for e in eng.step_log]
    assert any(g > 0 for g in gathered) and 0 in gathered
