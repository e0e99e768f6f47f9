"""Stage-graph executor: the parity matrix.

Every model runs through the one executor (core/pipeline.py) across the
execution modes the plan can express — {baseline, fused, bucketed,
streaming, sharded-8dev, fused NA→SA epilogue} — and must match the seed
reference path.  Also pins: plan-layout resolution, the RGCN bucketed-mean
dispatch, and that per-stage characterization records sum to the
whole-model totals.
"""
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import WALK_IDS, WALK_MATRIX
from repro.configs.base import HGNNConfig
from repro.core import metapath as mp, stages
from repro.core.models import get_model
from repro.data.synthetic import DATASET_METAPATHS, DATASET_TARGET


def _tiny_tables():
    DATASET_METAPATHS["tiny"] = [["M", "D", "M"], ["M", "A", "M"]]
    DATASET_TARGET["tiny"] = "M"


def _cfg(model, **kw):
    _tiny_tables()
    kw = {"max_degree": 48, "max_instances": 4, **kw}
    return HGNNConfig(model=model, dataset="tiny", hidden=16, n_heads=4,
                      n_classes=3, **kw)


def _forward(cfg, hg, params=None):
    m = get_model(cfg)
    batch = m.prepare(hg)
    if params is None:
        params = m.init(jax.random.key(0), batch)
    return m, params, np.asarray(m.forward(params, batch))


def _force_interpret(monkeypatch, name):
    """Force an ops wrapper onto the Pallas path in interpret mode."""
    from repro.kernels import ops

    orig = getattr(ops, name)
    monkeypatch.setattr(
        ops, name,
        lambda *args, use_pallas=False, interpret=False, **kw:
        orig(*args, use_pallas=True, interpret=True, **kw))


def _force_streaming(monkeypatch, name):
    """Route an ops wrapper straight into the streaming kernel (small chunk
    size so the double-buffered DMA path genuinely runs)."""
    from repro.kernels import gat_na as gmod, segment_spmm as smod, ops

    if name == "gat_aggregate_stacked":
        monkeypatch.setattr(
            ops, name,
            lambda p, hd, hs, nn, mm, **kw: gmod.gat_na(
                p, hd, hs, nn, mm, block_n=16, block_m=8, interpret=True))
    elif name == "gat_aggregate_stacked_fused_sa":
        monkeypatch.setattr(
            ops, name,
            lambda p, hd, hs, nn, mm, sem, **kw: gmod.gat_na(
                p, hd, hs, nn, mm, block_n=16, block_m=8, interpret=True,
                sem=sem))
    elif name == "segment_spmm":
        monkeypatch.setattr(
            ops, name,
            lambda hs, nn, mm, mean=True, **kw: smod.segment_spmm(
                hs, nn, mm, mean=mean, block_n=16, block_m=8, interpret=True))


# ---------------------------------------------------------------------------
# the parity matrix
# ---------------------------------------------------------------------------

MATRIX = [
    # (model, reference kwargs, variant kwargs, ops wrapper to force, mode)
    ("han", {"fused": False}, {"fused": True}, None, None),
    ("han", {"fused": True}, {"fused": True, "degree_buckets": 3},
     None, None),
    ("han", {"fused": True}, {"fused": True, "use_pallas": True},
     "gat_aggregate_stacked", "interpret"),
    ("han", {"fused": True}, {"fused": True, "use_pallas": True},
     "gat_aggregate_stacked", "streaming"),
    ("han", {"fused": True}, {"fused": True, "fuse_na_sa": True},
     None, None),
    ("han", {"fused": True},
     {"fused": True, "fuse_na_sa": True, "use_pallas": True},
     "gat_aggregate_stacked_fused_sa", "interpret"),
    ("han", {"fused": True},
     {"fused": True, "fuse_na_sa": True, "use_pallas": True},
     "gat_aggregate_stacked_fused_sa", "streaming"),
    ("rgcn", {"fused": False}, {"fused": True}, None, None),
    ("rgcn", {"fused": True}, {"fused": True, "degree_buckets": 3},
     None, None),
    ("rgcn", {"fused": True}, {"fused": True, "use_pallas": True},
     "segment_spmm", "streaming"),
    ("rgcn", {"fused": True},
     {"fused": True, "degree_buckets": 3, "use_pallas": True},
     "segment_spmm", "interpret"),
    ("magnn", {}, {"use_pallas": True}, "gat_aggregate", "interpret"),
    # graph-partitioned execution (repro.dist.partition): K=1 exercises the
    # machinery with empty halos, K=4 the real halo exchange
    ("han", {"fused": True}, {"fused": True, "partitions": 1}, None, None),
    ("han", {"fused": True}, {"fused": True, "partitions": 4}, None, None),
    ("rgcn", {"fused": True}, {"fused": True, "partitions": 4}, None, None),
    ("magnn", {}, {"partitions": 1}, None, None),
    ("magnn", {}, {"partitions": 4}, None, None),
    # multi-layer stacks (L=2): every layout pair must agree at depth, and
    # the partitioned flow (per-layer halo re-exchange over the
    # graph-invariant maps) must match the unpartitioned L=2 forward
    ("han", {"fused": False, "layers": 2}, {"fused": True, "layers": 2},
     None, None),
    ("han", {"fused": True, "layers": 2},
     {"fused": True, "layers": 2, "degree_buckets": 3}, None, None),
    ("han", {"fused": True, "layers": 2},
     {"fused": True, "layers": 2, "fuse_na_sa": True}, None, None),
    ("han", {"fused": True, "layers": 2},
     {"fused": True, "layers": 2, "partitions": 4}, None, None),
    ("rgcn", {"fused": False, "layers": 2}, {"fused": True, "layers": 2},
     None, None),
    ("rgcn", {"fused": True, "layers": 2},
     {"fused": True, "layers": 2, "degree_buckets": 3}, None, None),
    ("rgcn", {"fused": True, "layers": 2},
     {"fused": True, "layers": 2, "partitions": 4}, None, None),
    ("magnn", {"layers": 2}, {"layers": 2, "partitions": 4}, None, None),
]


@pytest.mark.parametrize(
    "model,ref_kw,var_kw,wrapper,mode", MATRIX,
    ids=[f"{m}-{'_'.join(f'{k}{v}' for k, v in v_kw.items())}-{md or 'xla'}"
         for m, _, v_kw, _, md in MATRIX])
def test_executor_parity_matrix(tiny_hg, monkeypatch, model, ref_kw, var_kw,
                                wrapper, mode):
    cfg_ref = _cfg(model, **ref_kw)
    _, params, want = _forward(cfg_ref, tiny_hg)
    if wrapper is not None:
        (_force_streaming if mode == "streaming"
         else _force_interpret)(monkeypatch, wrapper)
    if wrapper == "gat_aggregate_stacked_fused_sa":
        # SA pass 2 of the same plan runs the semantic_combine kernel
        _force_interpret(monkeypatch, "semantic_combine")
    cfg_var = _cfg(model, **var_kw)
    m_var = get_model(cfg_var)
    b_var = m_var.prepare(tiny_hg)
    # same init key: identical params modulo layout (stacking / lists)
    p_var = m_var.init(jax.random.key(0), b_var)
    got = np.asarray(m_var.forward(p_var, b_var))
    np.testing.assert_allclose(got, want, rtol=3e-3, atol=3e-3)


def test_gcn_runs_through_executor():
    from repro.data.synthetic import make_reddit_like

    hg = make_reddit_like(scale=0.005)
    cfg = HGNNConfig(model="gcn", dataset="reddit", hidden=16, n_classes=5)
    m, params, out = _forward(cfg, hg)
    assert m.plan().na.kind == "gcn" and m.plan().sa.kind == "none"
    assert out.shape[1] == 5 and np.isfinite(out).all()


def test_gcn_two_layer_matches_manual_block_composition():
    """GCN depth semantics pinned by hand: one LayerPlan is one
    agg(relu(agg(h @ w))) block, L=2 stacks two blocks before the head."""
    from repro.data.synthetic import make_reddit_like

    hg = make_reddit_like(scale=0.005)
    cfg = HGNNConfig(model="gcn", dataset="reddit", hidden=16, n_classes=5,
                     layers=2)
    m = get_model(cfg)
    batch = m.prepare(hg)
    params = m.init(jax.random.key(0), batch)
    got = np.asarray(m.forward(params, batch))

    def block(h, w):
        h = h @ w
        z = jax.nn.relu(stages.mean_aggregate_csr(
            h, batch["seg"], batch["idx"], h.shape[0]))
        return stages.mean_aggregate_csr(z, batch["seg"], batch["idx"],
                                         z.shape[0])

    want = block(block(batch["x"], params["w1"]),
                 params["layers"][0]["fp"]) @ params["w2"]
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-6, atol=1e-6)


def test_multilayer_forward_differs_from_single_layer(tiny_hg):
    """A second layer must actually change the output (no silent L=1
    fallthrough) while keeping shapes and finiteness."""
    for model, kw in [("han", {"fused": True}), ("rgcn", {"fused": True}),
                      ("magnn", {})]:
        _, _, one = _forward(_cfg(model, **kw), tiny_hg)
        _, _, two = _forward(_cfg(model, layers=2, **kw), tiny_hg)
        assert one.shape == two.shape
        assert np.isfinite(two).all()
        assert np.abs(one - two).max() > 1e-6, model


def test_multilayer_stage_records_per_layer(tiny_hg):
    """The acceptance invariant: an L-layer run's stage_records carries
    per-layer ``L{i}.FP/NA/SA`` whose sums reconcile with the end-to-end
    totals; partitioned runs add per-layer ``L{i}.gather_halo`` records and
    the partition summary reports halo-bytes × L."""
    cfg = _cfg("han", fused=True, layers=2)
    m = get_model(cfg)
    batch = m.prepare(tiny_hg)
    params = m.init(jax.random.key(0), batch)
    recs = m.stage_records(params, batch)
    assert set(recs["stages"]) == {
        "L1.FP", "L1.NA", "L1.SA", "L2.FP", "L2.NA", "L2.SA", "head"}
    for name, r in recs["stages"].items():
        assert r["flops"] > 0 and r["hbm_bytes"] > 0, name
    assert recs["total"]["flops"] == pytest.approx(
        sum(r["flops"] for r in recs["stages"].values()))
    assert recs["total"]["hbm_bytes"] == pytest.approx(
        sum(r["hbm_bytes"] for r in recs["stages"].values()))

    cfg_p = _cfg("han", fused=True, layers=2, partitions=3)
    m = get_model(cfg_p)
    batch = m.prepare(tiny_hg)
    params = m.init(jax.random.key(0), batch)
    recs = m.stage_records(params, batch)
    assert {"L1.gather_halo", "L2.gather_halo"} <= set(recs["stages"])
    pt = recs["partition"]
    assert pt["layers"] == 2
    gh_sum = (recs["stages"]["L1.gather_halo"]["halo_bytes"]
              + recs["stages"]["L2.gather_halo"]["halo_bytes"])
    assert pt["halo_bytes_total"] == pytest.approx(gh_sum)
    assert pt["halo_bytes_total"] == pytest.approx(2 * pt["halo_bytes"])
    assert pt["halo_bytes"] > 0


def test_multilayer_params_layout(tiny_hg):
    """Layer 0 stays at the pytree root (bit-exact single-layer layout);
    hidden layers ride params["layers"] with mirrored leaf names, and the
    same init key yields identical layer-0 leaves for L=1 and L=2."""
    cfg1 = _cfg("han", fused=True)
    m1 = get_model(cfg1)
    b1 = m1.prepare(tiny_hg)
    p1 = m1.init(jax.random.key(0), b1)
    cfg2 = _cfg("han", fused=True, layers=2)
    m2 = get_model(cfg2)
    b2 = m2.prepare(tiny_hg)
    p2 = m2.init(jax.random.key(0), b2)
    assert set(p2) == set(p1) | {"layers"}
    for leaf1, leaf2 in zip(jax.tree.leaves(p1),
                            jax.tree.leaves({k: v for k, v in p2.items()
                                             if k != "layers"})):
        np.testing.assert_array_equal(np.asarray(leaf1), np.asarray(leaf2))
    hidden = p2["layers"][0]
    assert {"fp", "gat", "sem"} <= set(hidden)
    assert hidden["fp"].shape == (cfg2.hidden, cfg2.hidden)


def test_executor_sharded_8dev_matches_single_device(tiny_hg):
    """{HAN stacked, HAN bucketed, RGCN bucketed, MAGNN} through
    build_hgnn_infer on a forced 2x4 host mesh == unsharded forward."""
    code = textwrap.dedent("""
        import numpy as np, scipy.sparse as sp, jax
        from repro.configs.base import HGNNConfig
        from repro.core.hgraph import HeteroGraph
        from repro.data.synthetic import DATASET_METAPATHS, DATASET_TARGET
        from repro.launch.mesh import make_smoke_mesh
        from repro.launch.serve import build_hgnn_infer

        rng = np.random.default_rng(7)
        counts = {"M": 40, "D": 15, "A": 25}
        dims = {"M": 12, "D": 8, "A": 10}
        feats = {t: rng.standard_normal((n, dims[t])).astype(np.float32)
                 for t, n in counts.items()}
        def rr(ns, nd, e):
            r = rng.integers(0, ns, e); c = rng.integers(0, nd, e)
            return sp.csr_matrix((np.ones(e, np.float32), (r, c)),
                                 shape=(ns, nd))
        md, ma = rr(40, 15, 60), rr(40, 25, 80)
        hg = HeteroGraph(counts, feats,
                         {("M", "md", "D"): md, ("D", "dm", "M"): md.T.tocsr(),
                          ("M", "ma", "A"): ma, ("A", "am", "M"): ma.T.tocsr()},
                         name="tiny")
        DATASET_METAPATHS["tiny"] = [["M", "D", "M"], ["M", "A", "M"]]
        DATASET_TARGET["tiny"] = "M"

        mesh = make_smoke_mesh(data=2, model=4)
        cases = [
            dict(model="han", fused=True),
            dict(model="han", fused=True, degree_buckets=3),
            dict(model="han", fused=True, layers=2),
            dict(model="rgcn", fused=True, degree_buckets=3),
            dict(model="magnn"),
        ]
        for kw in cases:
            cfg = HGNNConfig(dataset="tiny", hidden=16, n_heads=4,
                             n_classes=3, max_degree=12, max_instances=4, **kw)
            built = build_hgnn_infer(cfg, hg, mesh)
            sharded = np.asarray(built.fn(built.params, built.batch))
            ref = build_hgnn_infer(cfg, hg)  # single-device, same plan
            plain = np.asarray(ref.fn(ref.params, ref.batch))
            np.testing.assert_allclose(sharded, plain, rtol=2e-4, atol=2e-4)
            print("OK", kw)
    """)
    env = {**os.environ, "PYTHONPATH": "src",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("OK") == 5


def test_partitioned_8dev_matches_single_device(tiny_hg):
    """The acceptance row: K=4 graph-partitioned execution on a forced
    8-device host (mesh data=4 so the halo exchange runs the shard_map
    all-gather path) == unpartitioned single-device forward, for
    HAN / RGCN / MAGNN — with nonzero halo_bytes in stage_records."""
    code = textwrap.dedent("""
        import numpy as np, scipy.sparse as sp, jax
        from repro.configs.base import HGNNConfig
        from repro.core.hgraph import HeteroGraph
        from repro.data.synthetic import DATASET_METAPATHS, DATASET_TARGET
        from repro.launch.mesh import make_smoke_mesh
        from repro.launch.serve import build_hgnn_infer

        rng = np.random.default_rng(7)
        counts = {"M": 40, "D": 15, "A": 25}
        dims = {"M": 12, "D": 8, "A": 10}
        feats = {t: rng.standard_normal((n, dims[t])).astype(np.float32)
                 for t, n in counts.items()}
        def rr(ns, nd, e):
            r = rng.integers(0, ns, e); c = rng.integers(0, nd, e)
            return sp.csr_matrix((np.ones(e, np.float32), (r, c)),
                                 shape=(ns, nd))
        md, ma = rr(40, 15, 60), rr(40, 25, 80)
        hg = HeteroGraph(counts, feats,
                         {("M", "md", "D"): md, ("D", "dm", "M"): md.T.tocsr(),
                          ("M", "ma", "A"): ma, ("A", "am", "M"): ma.T.tocsr()},
                         name="tiny")
        DATASET_METAPATHS["tiny"] = [["M", "D", "M"], ["M", "A", "M"]]
        DATASET_TARGET["tiny"] = "M"

        mesh = make_smoke_mesh(data=4, model=2)
        cases = [
            dict(model="han", fused=True, partitions=4),
            dict(model="han", fused=True, partitions=4, layers=2),
            dict(model="rgcn", fused=True, partitions=4),
            dict(model="rgcn", fused=True, partitions=4, layers=2),
            dict(model="magnn", partitions=4),
        ]
        for kw in cases:
            cfg = HGNNConfig(dataset="tiny", hidden=16, n_heads=4,
                             n_classes=3, max_degree=12, max_instances=4, **kw)
            built = build_hgnn_infer(cfg, hg, mesh)
            sharded = np.asarray(built.fn(built.params, built.batch))
            ref = build_hgnn_infer(cfg.replace(partitions=0), hg)
            plain = np.asarray(ref.fn(ref.params, ref.batch))
            np.testing.assert_allclose(sharded, plain, rtol=2e-4, atol=2e-4)
            recs = built.executor.stage_records(built.params, built.batch)
            gh = [n for n in recs["stages"] if n.endswith("gather_halo")]
            assert len(gh) == kw.get("layers", 1), kw
            assert all(recs["stages"][n]["halo_bytes"] > 0 for n in gh), kw
            assert recs["partition"]["cut_edges"] > 0, kw
            assert recs["partition"]["layers"] == kw.get("layers", 1), kw
            print("OK", kw)
    """)
    env = {**os.environ, "PYTHONPATH": "src",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("OK") == 5


# ---------------------------------------------------------------------------
# plan + dispatch invariants
# ---------------------------------------------------------------------------

def test_plan_layout_resolution():
    _tiny_tables()
    assert get_model(_cfg("han", fused=False)).plan().na.layout == "csr"
    assert get_model(_cfg("han", fused=True)).plan().na.layout == "stacked"
    p = get_model(_cfg("han", fused=True, degree_buckets=3)).plan()
    assert p.na.layout == "bucketed"
    assert not p.sa.fuse_epilogue  # epilogue is stacked-only
    p = get_model(_cfg("han", fused=True, fuse_na_sa=True)).plan()
    assert p.sa.fuse_epilogue
    assert get_model(_cfg("rgcn", fused=True)).plan().na.layout == "padded"
    assert get_model(
        _cfg("rgcn", fused=True, degree_buckets=3)).plan().na.layout == "bucketed"
    assert get_model(_cfg("magnn")).plan().na.layout == "instances"
    # CSR layouts refuse to shard
    assert not get_model(_cfg("han", fused=False)).plan().shards_on_mesh
    assert get_model(_cfg("magnn")).plan().shards_on_mesh
    # partitioned plans: PartitionSpec set, epilogue disabled, CSR refused
    p = get_model(_cfg("han", fused=True, partitions=4)).plan()
    assert p.partition is not None and p.partition.k == 4
    p = get_model(_cfg("han", fused=True, fuse_na_sa=True,
                       partitions=4)).plan()
    assert not p.sa.fuse_epilogue  # epilogue needs the single-table stack
    assert get_model(_cfg("rgcn", fused=True)).plan().partition is None
    # multi-layer plans: StagePlan is the L-layer container; layer 0 owns
    # the raw-feature FP, hidden layers the per-model re-projection kind;
    # plan.fp/na/sa keep reading layer 0
    p = get_model(_cfg("han", fused=True, layers=3)).plan()
    assert p.n_layers == 3 and len(p.layers) == 3
    assert p.layers[0].fp.kind == "per_type" and p.layers[0].fp.heads
    assert all(lp.fp.kind == "dense" for lp in p.layers[1:])
    assert all(lp.handoff == "target" for lp in p.layers)
    assert p.na is p.layers[0].na and p.fp is p.layers[0].fp
    p = get_model(_cfg("rgcn", fused=True, layers=2)).plan()
    assert p.layers[1].fp.kind == "identity"
    assert all(lp.handoff == "all" for lp in p.layers)
    p = get_model(_cfg("magnn", layers=2)).plan()
    assert p.layers[1].handoff == "target+carry"
    assert set(p.layers[1].carry) == {"D", "A"}
    assert get_model(_cfg("han", fused=True)).plan().n_layers == 1
    with pytest.raises(ValueError, match="layers must be >= 1"):
        _cfg("han", fused=True, layers=0)


def test_stageplan_rejects_nonuniform_layers():
    """The host-side index tables are built once and reused per layer, so
    NA kind/layout and SA kind must be uniform across the stack."""
    from repro.core.plan import (FPSpec, HeadSpec, LayerPlan, NASpec, SASpec,
                                 StagePlan)

    l0 = LayerPlan(fp=FPSpec(), na=NASpec(kind="gat", layout="stacked"),
                   sa=SASpec(kind="attention"))
    l1 = LayerPlan(fp=FPSpec(), na=NASpec(kind="gat", layout="csr"),
                   sa=SASpec(kind="attention"))
    with pytest.raises(ValueError, match="layer-uniform"):
        StagePlan(model="x", target="M", layers=(l0, l1), head=HeadSpec())
    with pytest.raises(ValueError, match="at least one"):
        StagePlan(model="x", target="M", layers=(), head=HeadSpec())


def test_partitioned_stage_records_report_halo_traffic(tiny_hg):
    """Single-device partitioned run: stage_records grows the gather_halo
    stage with nonzero halo_bytes + the partition cut summary, and the
    stage-additive totals still include it."""
    cfg = _cfg("han", fused=True, partitions=3)
    m = get_model(cfg)
    batch = m.prepare(tiny_hg)
    params = m.init(jax.random.key(0), batch)
    recs = m.stage_records(params, batch)
    assert set(recs["stages"]) == {"FP", "gather_halo", "NA", "SA", "head"}
    gh = recs["stages"]["gather_halo"]
    assert gh["halo_bytes"] > 0 and gh["hbm_bytes"] > 0
    pt = recs["partition"]
    assert pt["k"] == 3 and 0 < pt["cut_ratio"] <= 1
    assert pt["halo_rows"] > 0 and pt["cut_edges"] == gh["cut_edges"]
    assert recs["total"]["hbm_bytes"] == pytest.approx(
        sum(r["hbm_bytes"] for r in recs["stages"].values()))


def test_mean_aggregate_bucketed_matches_padded(tiny_hg):
    """RGCN satellite: bucketed mean NA == single-K padded mean NA."""
    sub = mp.build_padded(tiny_hg, ["M", "D", "M"], max_degree=16)
    bk = mp.bucket_padded(sub, n_buckets=3)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((sub.n_nodes, 8)), jnp.float32)
    want = stages.mean_aggregate_padded(h, jnp.asarray(sub.nbr),
                                        jnp.asarray(sub.mask))
    buckets = [(jnp.asarray(bk.row_ids[i]), jnp.asarray(bk.nbr[i]),
                jnp.asarray(bk.mask[i])) for i in range(bk.n_buckets)]
    got = stages.mean_aggregate_bucketed(h, buckets, sub.n_nodes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_rgcn_bucketed_layout_strictly_smaller(tiny_hg):
    cfg = _cfg("rgcn", fused=True, degree_buckets=3, max_degree=16)
    m = get_model(cfg)
    batch = m.prepare(tiny_hg)
    cfg_p = _cfg("rgcn", fused=True, max_degree=16)
    batch_p = get_model(cfg_p).prepare(tiny_hg)
    for key, buckets in batch["rels"].items():
        assert isinstance(buckets, list)
        padded = sum(b[1].size for b in buckets)
        assert padded <= batch_p["rels"][key][0].size


# ---------------------------------------------------------------------------
# characterization records
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model,kw", WALK_MATRIX, ids=WALK_IDS)
def test_stage_records_sum_to_totals(tiny_hg, model, kw):
    """Per-stage characterization records must sum to the whole-model
    totals the executor reports (and each stage must be populated)."""
    cfg = _cfg(model, **{"fused": True, **kw})
    m = get_model(cfg)
    batch = m.prepare(tiny_hg)
    params = m.init(jax.random.key(0), batch)
    recs = m.stage_records(params, batch)
    assert list(recs["stages"]) == list(m.executor.stage_fns(params, batch))
    layers = m.executor.plan.layers
    for name, r in recs["stages"].items():
        hidden_fp = re.match(r"L(\d+)\.FP$", name)
        if not (hidden_fp and layers[int(hidden_fp.group(1)) - 1].fp.kind
                == "identity"):  # R-GCN's hidden FP computes nothing
            assert r["flops"] > 0, name
        assert r["hbm_bytes"] > 0, name
        assert r["roofline"]["bound"] in ("compute", "memory", "collective")
    assert recs["total"]["flops"] == pytest.approx(
        sum(r["flops"] for r in recs["stages"].values()))
    assert recs["total"]["hbm_bytes"] == pytest.approx(
        sum(r["hbm_bytes"] for r in recs["stages"].values()))


def test_fused_epilogue_saves_an_hbm_pass(tiny_hg):
    """The acceptance invariant, counted via core/characterize.py: with the
    epilogue, the SA stage fn moves at least one full [P, N, D] pass less."""
    from repro.core.characterize import analyze_hlo_text

    def sa_bytes(cfg):
        m = get_model(cfg)
        batch = m.prepare(tiny_hg)
        params = m.init(jax.random.key(0), batch)
        fns = m.executor.stage_fns(params, batch)
        fn, args = fns["SA"]
        rep = analyze_hlo_text(fn.lower(*args).compile().as_text())
        z = args[1]  # the SA input: [P, N, D] stack (or (stack, scores))
        z = z[0] if isinstance(z, tuple) else z
        return rep["total_hbm_bytes"], z.size * z.dtype.itemsize

    two_pass, z_bytes = sa_bytes(_cfg("han", fused=True))
    fused, _ = sa_bytes(_cfg("han", fused=True, fuse_na_sa=True))
    assert two_pass - fused >= 0.9 * z_bytes, (two_pass, fused, z_bytes)


@pytest.mark.parametrize("n,block_n", [(200, 64), (256, 64), (70, 512)])
def test_semantic_scores_streaming_parity(n, block_n):
    """SA pass-1 streaming split: an oversized [P, N, D] stack stays in HBM
    behind double-buffered DMAs (tail chunk aligned to the array end, no
    padded whole-array copy) and must match the resident path / the math —
    including a nonzero bias, which the pad rows must not leak."""
    from repro.kernels.semantic_attn import semantic_scores

    rng = np.random.default_rng(1)
    p, d, hs = 3, 16, 8
    z = jnp.asarray(rng.standard_normal((p, n, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((d, hs)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.standard_normal(hs) * 0.5, jnp.float32)
    q = jnp.asarray(rng.standard_normal(hs), jnp.float32)
    want = jnp.einsum("pnh,h->pn", jnp.tanh(z @ w + b), q).mean(axis=1)
    # vmem_budget=1 forces the streaming path whenever n > block_n
    got = semantic_scores(z, w, b, q, block_n=block_n, interpret=True,
                          vmem_budget=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    resident = semantic_scores(z, w, b, q, block_n=block_n, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(resident),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# request-path sampling: sampled-vs-full parity
# ---------------------------------------------------------------------------

SAMPLED_MATRIX = [
    # with fanout >= max degree and an exact-size rung, a sampled minibatch
    # over ALL targets must reproduce the full-graph forward bit-for-bit
    ("han", {"fused": True}),
    ("han", {"fused": True, "layers": 2}),
    ("han", {"fused": True, "fuse_na_sa": True}),
    ("han", {"fused": True, "degree_buckets": 3}),
    ("han", {"fused": True, "degree_buckets": 3, "layers": 2}),
    ("rgcn", {"fused": True}),
    ("rgcn", {"fused": True, "layers": 2}),
    ("rgcn", {"fused": True, "degree_buckets": 3}),
    ("magnn", {}),
    ("magnn", {"layers": 2}),
]


@pytest.mark.parametrize(
    "model,kw", SAMPLED_MATRIX,
    ids=[f"{m}-{'_'.join(f'{k}{v}' for k, v in kw.items()) or 'base'}"
         for m, kw in SAMPLED_MATRIX])
def test_sampled_minibatch_matches_full_forward(tiny_hg, model, kw):
    """The acceptance row: fan-out >= max degree + an exact-size ladder rung
    means sampling drops nothing, so the sampled minibatch logits over all
    40 targets are BIT-EXACT vs the full-graph forward — per executor
    dispatch arm (stacked, bucketed, fused-epilogue, padded-relational,
    instances) at L in {1, 2}."""
    from repro.serve.sampler import HGNNSampler

    cfg = _cfg(model, fanout=64, sample_ladder=((40, 40),), **kw)
    m = get_model(cfg)
    batch = m.prepare(tiny_hg)
    params = m.init(jax.random.key(0), batch)
    fn = jax.jit(m.forward)  # the executable serving actually runs
    want = np.asarray(fn(params, batch))
    sampler = HGNNSampler(m.plan(), cfg, tiny_hg)
    sb = sampler.sample(np.arange(40))
    got = np.asarray(fn(params, sb.batch))[sb.target_rows]
    np.testing.assert_array_equal(got, want)


def test_sampled_gcn_matches_full_forward():
    from repro.data.synthetic import make_reddit_like
    from repro.serve.sampler import HGNNSampler

    hg = make_reddit_like(scale=0.005)
    n = hg.node_counts["N"]
    cfg = HGNNConfig(model="gcn", dataset="reddit", hidden=16, n_classes=5,
                     fanout=4096, sample_ladder=((n, n),))
    m = get_model(cfg)
    batch = m.prepare(hg)
    params = m.init(jax.random.key(0), batch)
    fn = jax.jit(m.forward)
    want = np.asarray(fn(params, batch))
    sampler = HGNNSampler(m.plan(), cfg, hg)
    sb = sampler.sample(np.arange(n))
    got = np.asarray(fn(params, sb.batch))[sb.target_rows]
    np.testing.assert_array_equal(got, want)


def test_sampler_rejects_csr_plans(tiny_hg):
    from repro.serve.sampler import HGNNSampler

    cfg = _cfg("han", fused=False, fanout=4)
    m = get_model(cfg)
    with pytest.raises(ValueError, match="csr"):
        HGNNSampler(m.plan(), cfg, tiny_hg)
    cfg = _cfg("han", fused=True)  # fanout=0: no SampleSpec on the plan
    m = get_model(cfg)
    with pytest.raises(ValueError, match="SampleSpec"):
        HGNNSampler(m.plan(), cfg, tiny_hg)


def test_sample_stage_record_rides_stage_records(tiny_hg):
    """stage_records grows a SAMPLE stage from the sampler's meta: the
    sampled-frontier bytes are the Subgraph-Build traffic of the request
    path, and the compiled-stage totals stay additive without it."""
    from repro.serve.sampler import HGNNSampler

    cfg = _cfg("han", fused=True, fanout=4)
    m = get_model(cfg)
    batch = m.prepare(tiny_hg)
    params = m.init(jax.random.key(0), batch)
    sampler = HGNNSampler(m.plan(), cfg, tiny_hg)
    sb = sampler.sample(np.arange(10))
    recs = m.executor.stage_records(params, sb.batch, sample_meta=sb.meta)
    assert "SAMPLE" in recs["stages"]
    sm = recs["stages"]["SAMPLE"]
    assert sm["n_targets"] == 10 and sm["fanout"] == 4
    # identity rung: the movie table is the resident one, nothing gathered
    assert sb.batch["feats"]["M"] is sampler.resident["M"]
    assert sm["resident_gather_bytes"] == 0 and sm["frontier_bytes"] > 0
    # a rung that cuts the movies: the frontier rows reach the device by
    # upload or by the resident gather
    cut_cfg = _cfg("han", fused=True, fanout=4, sample_ladder=((16, 24),))
    cut = HGNNSampler(get_model(cut_cfg).plan(), cut_cfg, tiny_hg)
    cm = cut.sample(np.arange(10)).meta
    assert cm["resident_gather_bytes"] == 24 * tiny_hg.feat_dim("M") * 4
    assert cm["upload_bytes"] + cm["resident_gather_bytes"] >= (
        cm["frontier_bytes"]) > 0
    assert tuple(sm["rung"]) in m.plan().sample.ladder
    # SAMPLE is host-side traffic: the FLOPs/bytes totals still reconcile
    # over the compiled stages only
    assert recs["total"]["flops"] == pytest.approx(
        sum(r["flops"] for n, r in recs["stages"].items() if n != "SAMPLE"))


def test_hgnn_infer_engine_serves_and_characterizes(tiny_hg):
    from repro.launch.serve import build_hgnn_infer
    from repro.serve.engine import HGNNInferEngine

    cfg = _cfg("han", fused=True)
    built = build_hgnn_infer(cfg, tiny_hg)
    engine = HGNNInferEngine(built.executor, built.params, built.batch,
                             fn=built.fn)
    logits = engine.infer()
    assert logits.shape == (40, 3)
    recs = engine.characterize()
    assert {"FP", "NA", "SA"} <= set(recs)
    assert engine.plan.na.layout == "stacked"


# ---------------------------------------------------------------------------
# hot-feature residency (repro.core.residency): cached == uncached, bitwise
# ---------------------------------------------------------------------------

# cached-vs-uncached parity is WITHIN one layout, so the bar is exact
# equality — the cache section holds bitwise row copies and the remapped
# index tables must reproduce the uncached forward to the last ulp
CACHED_MATRIX = [
    ("han", {"fused": False}),
    ("han", {"fused": True}),
    ("han", {"fused": True, "layers": 2}),
    ("han", {"fused": True, "degree_buckets": 3}),
    ("han", {"fused": True, "degree_buckets": 3, "layers": 2}),
    ("han", {"fused": True, "fuse_na_sa": True}),
    ("han", {"fused": True, "fuse_na_sa": True, "layers": 2}),
    ("han", {"fused": True, "partitions": 4}),
    ("han", {"fused": True, "partitions": 4, "layers": 2}),
    ("rgcn", {"fused": False}),
    ("rgcn", {"fused": True}),
    ("rgcn", {"fused": True, "layers": 2}),
    ("rgcn", {"fused": True, "degree_buckets": 3}),
    ("rgcn", {"fused": True, "partitions": 4}),
    ("magnn", {}),
    ("magnn", {"layers": 2}),
    ("magnn", {"partitions": 4}),
]


@pytest.mark.parametrize(
    "model,kw", CACHED_MATRIX,
    ids=[f"{m}-{'_'.join(f'{k}{v}' for k, v in kw.items()) or 'base'}"
         for m, kw in CACHED_MATRIX])
def test_cached_forward_bit_exact(tiny_hg, model, kw):
    m0 = get_model(_cfg(model, **kw))
    b0 = m0.prepare(tiny_hg)
    params = m0.init(jax.random.key(0), b0)
    want = np.asarray(m0.forward(params, b0))

    m1 = get_model(_cfg(model, cache_rows=8, **kw))
    b1 = m1.prepare(tiny_hg)
    assert "residency" in b1
    ctr = b1["residency"]["counters"]
    assert ctr["hits"] + ctr["misses"] == ctr["rows"] > 0
    got = np.asarray(m1.forward(params, b1))
    np.testing.assert_array_equal(got, want)


def test_cached_serving_bit_exact(tiny_hg):
    """Sampled serving with the live cache: the per-step frontier rides the
    engine-level HotRowCache (accounting only — batch shapes never change),
    so cached serving returns bitwise the uncached logits and reports
    residency counters that conserve."""
    from repro.serve.engine import HGNNRequest, HGNNServeEngine
    from repro.serve.sampler import HGNNSampler

    outs = []
    for rows in (0, 8):
        cfg = _cfg("han", fused=True, fanout=64, cache_rows=rows)
        m = get_model(cfg)
        batch = m.prepare(tiny_hg)
        params = m.init(jax.random.key(0), batch)
        sampler = HGNNSampler(m.plan(), cfg, tiny_hg)
        engine = HGNNServeEngine(m.executor, params, sampler, slots=4,
                                 slot_targets=4)
        engine.warmup()
        rng = np.random.default_rng(0)
        reqs = [HGNNRequest(targets=rng.integers(0, 40, size=5))
                for _ in range(6)]
        engine.serve(reqs)
        st = engine.stats()
        assert st["compiles_after_warmup"] == 0
        if rows:
            rd = st["residency"]
            assert rd["hits"] + rd["misses"] == rd["rows"] > 0
            for t, c in rd["per_type"].items():
                assert c["resident"] <= c["capacity"] <= rows
        else:
            assert "residency" not in st
        outs.append(np.concatenate([r.logits for r in reqs]))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_cached_stage_records_na_bytes_strictly_decrease(tiny_hg):
    """The headline accounting: with the cache enabled, every NA stage's
    ``hbm_bytes`` strictly decreases (hits x row_bytes saved; the fill is
    charged once, at the first cached stage — inter-layer reuse), and the
    partitioned flow books the savings on the ``gather_halo`` records."""
    for model, kw, stage_suffix in [
            ("han", {"fused": True, "layers": 2}, "NA"),
            ("rgcn", {"fused": False, "layers": 2}, "NA"),
            ("han", {"fused": True, "layers": 2, "partitions": 4},
             "gather_halo")]:
        m0 = get_model(_cfg(model, **kw))
        b0 = m0.prepare(tiny_hg)
        params = m0.init(jax.random.key(0), b0)
        r0 = m0.stage_records(params, b0)
        m1 = get_model(_cfg(model, cache_rows=12, **kw))
        b1 = m1.prepare(tiny_hg)
        r1 = m1.stage_records(params, b1)
        rr = r1["residency"]
        assert rr["hits"] > 0
        assert rr["hit_rate"] == pytest.approx(rr["hits"] / rr["rows"])
        names = [n for n in r1["stages"] if n.endswith(stage_suffix)]
        assert len(names) == 2  # one per layer
        for i, n in enumerate(names):
            assert (r1["stages"][n]["hbm_bytes"]
                    < r0["stages"][n]["hbm_bytes"]), (model, n)
            saved = r1["stages"][n]["residency_bytes_saved"]
            want = rr["bytes_saved_per_layer"] - (
                rr["fill_bytes"] if i == 0 else 0)
            assert saved == want
        # uncached stages are untouched by the accounting
        for n in r1["stages"]:
            if not n.endswith(stage_suffix):
                assert (r1["stages"][n]["hbm_bytes"]
                        == r0["stages"][n]["hbm_bytes"]), (model, n)
        assert r1["total"]["hbm_bytes"] < r0["total"]["hbm_bytes"]
