"""MAGNN as published (Fu et al., WWW 2020): every metapath instance, the
relational-rotation encoder with relation vectors, full-width heads, and
the instance-count bucketed layout of the full-graph path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro.configs.base import HGNNConfig
from repro.core import metapath as mp
from repro.core import stages
from repro.core.hgraph import HeteroGraph
from repro.core.models import get_model
from repro.data.synthetic import DATASET_METAPATHS, DATASET_TARGET

PATHS = [["M", "D", "M"], ["M", "A", "M"]]


def _hub_graph() -> HeteroGraph:
    """40 movies, 6 directors, 12 actors.  Director 0 directs movies 0-19
    and director 1 movies 20-27; actor 0, the hub, plays in movies 0-29,
    actor 1 in movies 0-11.  Instance counts run from 0 (movies 34-39 have
    no director) to 49, so MAM's rows fall into four count buckets."""
    rng = np.random.default_rng(11)
    counts = {"M": 40, "D": 6, "A": 12}
    md = np.zeros((40, 6), np.float32)
    md[:20, 0] = 1
    md[20:28, 1] = 1
    md[np.arange(28, 34), rng.integers(2, 6, 6)] = 1
    ma = np.zeros((40, 12), np.float32)
    ma[:30, 0] = 1
    ma[:12, 1] = 1
    ma[np.arange(40), rng.integers(2, 12, 40)] = 1
    feats = {t: rng.standard_normal((n, d)).astype(np.float32)
             for (t, n), d in zip(counts.items(), (12, 8, 10))}
    md, ma = sp.csr_matrix(md), sp.csr_matrix(ma)
    g = HeteroGraph(counts, feats,
                    {("M", "md", "D"): md, ("D", "dm", "M"): md.T.tocsr(),
                     ("M", "ma", "A"): ma, ("A", "am", "M"): ma.T.tocsr()},
                    name="hub")
    g.validate()
    return g


def _cfg(**kw):
    DATASET_METAPATHS["hub"] = PATHS
    DATASET_TARGET["hub"] = "M"
    kw = {"max_instances": 0, **kw}
    return HGNNConfig(model="magnn", dataset="hub", hidden=16, n_heads=4,
                      n_classes=3, **kw)


def _forward(cfg, hg, params=None):
    m = get_model(cfg)
    batch = m.prepare(hg)
    if params is None:
        params = m.init(jax.random.key(0), batch)
    return m, params, batch, np.asarray(m.forward(params, batch))


def _complex(x):
    return x[..., 0::2] + 1j * x[..., 1::2]


@pytest.mark.parametrize("length", [2, 3, 5])
def test_rotation_encoder_matches_the_closed_form(length):
    """h_P = (1/L) sum_i h_{t_i} * prod_{j > i} r_j on random complex rows
    and relation vectors, positions counted from the far end: the
    program's per-position rotations, applied row by row, then averaged."""
    rng = np.random.default_rng(length)
    d = 8
    h = [rng.standard_normal((5, 3, d)).astype(np.float32)
         for _ in range(length)]
    rel = {"M|D": jnp.asarray(rng.standard_normal((d // 2, 2)), jnp.float32),
           "M|A": jnp.asarray(rng.standard_normal((d // 2, 2)), jnp.float32)}
    types = ["M", "D", "M", "A", "M"][:length]
    rot = stages.position_rotations(rel, types)
    got = _complex(np.asarray(stages.rotation_encoder(
        [stages.rotate(jnp.asarray(x), r) for x, r in zip(h, rot)])))
    unit = {k: _complex(np.asarray(v).reshape(-1)) for k, v in rel.items()}
    unit = {k: v / np.abs(v) for k, v in unit.items()}
    edge = [unit[f"{a}|{b}"] if f"{a}|{b}" in unit
            else np.conj(unit[f"{b}|{a}"])
            for a, b in zip(types[1:], types[:-1])]  # edge p+1 -> p
    want = 0
    for p in range(length):  # position p sits p edges from the target
        want = want + _complex(h[p]) * np.prod(edge[:p], axis=0)
    np.testing.assert_allclose(got, want / length, rtol=2e-5, atol=2e-6)


def test_relations_are_first_seen_from_the_far_end():
    assert stages.instance_relations(PATHS) == (("M", "D"), ("M", "A"))
    assert stages.instance_relations(
        [["A", "P", "V", "P", "A"]]) == (("A", "P"), ("P", "V"))


def test_exact_enumeration_counts_every_instance():
    """Every instance of every target, the returning (m, d, m) included:
    per target, the product of degrees along the path; each row a real
    path that starts at its own target, none twice; MAM's rows in at least
    three instance-count buckets, none dropping a slot's instance."""
    hg = _hub_graph()
    for path in PATHS:
        ib = mp.enumerate_instances(hg, path, max_instances=0)
        want = hg.rel(path[0], path[1]) @ (
            hg.rel(path[1], path[2]) @ np.ones(40))
        np.testing.assert_array_equal(ib.mask.sum(1), want)
        assert ib.max_degree == want.max()
        v, i = np.nonzero(ib.mask)
        inst = ib.nodes[v, i]
        assert np.array_equal(inst[:, 0], v)
        for j, (a, b) in enumerate(zip(path[:-1], path[1:])):
            assert np.all(hg.rel(a, b)[inst[:, j], inst[:, j + 1]] > 0)
        assert len(np.unique(inst, axis=0)) == len(inst)
        bk = mp.bucket_padded(ib, 8)
        assert bk.n_buckets >= (3 if path[1] == "A" else 2)
        assert sum(m.sum() for m in bk.mask) == ib.n_instances
        assert np.array_equal(np.sort(np.concatenate(bk.row_ids)),
                              np.arange(40))


def test_prepare_records_instances_and_slots():
    hg = _hub_graph()
    m = get_model(_cfg())
    batch = m.prepare(hg)
    rec = batch["record"]
    assert jax.tree_util.tree_leaves(rec) == []  # static: no device argument
    want = [int((hg.rel(p[0], p[1]) @ (hg.rel(p[1], p[2]) @ np.ones(40)))
                .sum()) for p in PATHS]
    assert list(rec.instances) == want
    assert list(rec.instance_slots) == [
        sum(int(np.asarray(mk).size) for _r, _n, mk in bks)
        for bks in batch["buckets"]]
    assert all(s >= n for s, n in zip(rec.instance_slots, rec.instances))
    assert rec.enumerate_s > 0


def test_heads_are_full_width_and_han_keeps_split_heads(tiny_hg):
    DATASET_METAPATHS["tiny"] = PATHS
    DATASET_TARGET["tiny"] = "M"
    kw = dict(dataset="tiny", hidden=16, n_heads=4, n_classes=3,
              max_instances=4)
    mg = get_model(HGNNConfig(model="magnn", **kw))
    p = mg.init(jax.random.key(0), mg.prepare(tiny_hg))
    assert p["att"][0]["a_dst"].shape == (4, 16)
    assert p["sem"]["W"].shape == (64, 128)
    assert p["cls"].shape == (64, 3)
    assert set(p["rel"]) == {"M|D", "M|A"}
    assert p["rel"]["M|D"].shape == (8, 2)
    hn = get_model(HGNNConfig(model="han", fused=True, **kw))
    p = hn.init(jax.random.key(0), hn.prepare(tiny_hg))
    assert p["gat"]["a_dst"].shape == (2, 4, 4)
    assert p["cls"].shape == (16, 3)


@pytest.mark.parametrize("kw", [{}, {"layers": 2}, {"use_pallas": True}],
                         ids=["L1", "L2", "pallas"])
def test_bucketed_layout_matches_one_table(monkeypatch, kw):
    """The instance-count buckets are a layout change only: the same
    logits as one [N, I_max, L] table of every instance."""
    if kw.get("use_pallas"):
        from repro.kernels import ops

        orig = ops.gat_aggregate
        monkeypatch.setattr(
            ops, "gat_aggregate",
            lambda *a, use_pallas=False, interpret=False, **k:
            orig(*a, use_pallas=True, interpret=True, **k))
    hg = _hub_graph()
    _, params, b1, one = _forward(_cfg(degree_buckets=1, **kw), hg)
    _, _, b2, bucketed = _forward(_cfg(**kw), hg, params)
    assert "instances" in b1 and "buckets" in b2
    assert len(b2["buckets"][1]) >= 3
    np.testing.assert_allclose(bucketed, one, rtol=2e-5, atol=2e-6)


def test_a_target_with_no_instance_gets_a_zero_row():
    hg = _hub_graph()
    m = get_model(_cfg())
    batch = m.prepare(hg)
    assert "buckets" in batch  # every instance: binned by count unasked
    params = m.init(jax.random.key(0), batch)
    h = m.fp(params, batch)
    z = np.asarray(m.na(params, batch, h)[0])  # MDM: movies 34-39 have none
    assert np.all(z[34:] == 0.0)
    assert np.all(np.abs(z[:34]).sum(1) > 0)


@pytest.mark.parametrize("buckets", [1, 0])
def test_serving_keeps_its_per_target_cap(buckets):
    """max_instances=0 keeps every instance on the full-graph path; the
    sampler still caps a served target at the fan-out, in the plan's own
    layout (one rung-shaped bucket where the plan is bucketed)."""
    from repro.serve.sampler import HGNNSampler

    hg = _hub_graph()
    cfg = _cfg(degree_buckets=buckets, fanout=3, sample_ladder=((8, 40),))
    m, params, full, _ = _forward(cfg, hg)
    sampler = HGNNSampler(m.plan(), cfg, hg)
    assert sampler.k_eff == 3
    sb = sampler.sample(np.arange(5))
    if not buckets:
        (rows, nodes, mask), = sb.batch["buckets"][1]
        np.testing.assert_array_equal(np.asarray(rows),
                                      np.arange(nodes.shape[0]))
    else:
        nodes, mask = sb.batch["instances"][1]
    n = nodes.shape[0]
    assert nodes.shape == (n, 3, 3) and mask.shape == (n, 3)
    assert np.asarray(mask)[:5].sum(1).min() >= 1
    assert max(int(np.asarray(m_).sum(1).max()) for _r, _n, m_ in (
        full["buckets"][1] if not buckets else [(None,) + tuple(
            full["instances"][1])])) > 3
    out = np.asarray(m.forward(params, sb.batch))
    assert out.shape == (n, 3) and np.isfinite(out).all()
