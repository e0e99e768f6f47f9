"""Property tests for the request-path neighbor sampler (repro.serve.sampler).

The four ISSUE invariants, each over randomized heterographs / fan-outs /
target sets (hypothesis, or the deterministic conftest stub on minimal CI
images):

  1. soundness — every neighbor a sampled minibatch wires up is an edge of
     the full graph (metapath reachability for HAN, relation in-neighbors
     for RGCN, consecutive relation hops for MAGNN instances);
  2. relabeling is a bijection between the extracted vertex set and the
     local id range (and ``target_rows`` inverts it for the request ids);
  3. fan-out caps hold per hop / per metapath / per relation;
  4. every batch's pytree signature (structure + leaf shapes) comes from
     the declared ladder — byte-identical to the warmup ``dummy_batch`` of
     its rung, which is exactly why the jitted executor never recompiles.
"""
import jax
import numpy as np
import pytest
import scipy.sparse as sp
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import HGNNConfig
from repro.core.hgraph import HeteroGraph, metapath_adjacency
from repro.core.models import get_model
from repro.data.synthetic import DATASET_METAPATHS, DATASET_TARGET
from repro.serve.sampler import HGNNSampler

DATASET_METAPATHS["sampt"] = [["M", "D", "M"], ["M", "A", "M"]]
DATASET_TARGET["sampt"] = "M"


def _rand_hg(seed: int) -> HeteroGraph:
    rng = np.random.default_rng(seed)
    nm = int(rng.integers(12, 40))
    nd = int(rng.integers(5, 16))
    na = int(rng.integers(6, 20))
    counts = {"M": nm, "D": nd, "A": na}
    dims = {"M": 6, "D": 5, "A": 4}
    feats = {t: rng.standard_normal((n, dims[t])).astype(np.float32)
             for t, n in counts.items()}

    def rr(ns, nd_, e):
        r = rng.integers(0, ns, e)
        c = rng.integers(0, nd_, e)
        return sp.csr_matrix((np.ones(e, np.float32), (r, c)),
                             shape=(ns, nd_))

    md = rr(nm, nd, 3 * nm)
    ma = rr(nm, na, 3 * nm)
    g = HeteroGraph(
        counts, feats,
        {("M", "md", "D"): md, ("D", "dm", "M"): md.T.tocsr(),
         ("M", "ma", "A"): ma, ("A", "am", "M"): ma.T.tocsr()},
        name="sampt")
    g.validate()
    return g


def _cfg(model: str, fanout: int, **kw) -> HGNNConfig:
    return HGNNConfig(model=model, dataset="sampt", hidden=8, n_heads=2,
                      n_classes=3, max_degree=6, max_instances=3,
                      fused=True, fanout=fanout, **kw)


def _sampler(model: str, hg: HeteroGraph, fanout: int, **kw) -> HGNNSampler:
    cfg = _cfg(model, fanout, **kw)
    return HGNNSampler(get_model(cfg).plan(), cfg, hg)


def _targets(hg: HeteroGraph, seed: int, n: int) -> np.ndarray:
    rng = np.random.default_rng(seed + 1000)
    return rng.integers(0, hg.node_counts["M"], size=n).astype(np.int64)


def _check_bijection(sb) -> None:
    for t, ids in sb.local.items():
        assert len(np.unique(ids)) == len(ids), t  # injective
        assert ids.min() >= 0 if len(ids) else True
    ids = sb.local["M"]
    # target_rows is the relabel inverse for the request ids (duplicates
    # included): local row r holds global vertex target_ids[i]
    for i, r in enumerate(sb.target_rows):
        assert ids[r] == sb.target_ids[i]


def _sig(batch):
    leaves, treedef = jax.tree.flatten(batch)
    return (str(treedef),
            tuple((tuple(getattr(x, "shape", ())),
                   str(getattr(x, "dtype", type(x).__name__)))
                  for x in leaves))


# ---------------------------------------------------------------------------
# 1 + 2 + 3: soundness / bijection / fan-out caps, per layout
# ---------------------------------------------------------------------------

@settings(max_examples=8)
@given(seed=st.integers(0, 10_000), fanout=st.integers(1, 6),
       n_req=st.integers(1, 10), layers=st.integers(1, 2))
def test_han_sampled_edges_exist_and_caps_hold(seed, fanout, n_req, layers):
    hg = _rand_hg(seed)
    smp = _sampler("han", hg, fanout, layers=layers)
    sb = smp.sample(_targets(hg, seed, n_req))
    _check_bijection(sb)
    nbr = np.asarray(sb.batch["nbr"])
    mask = np.asarray(sb.batch["mask"])
    ids = sb.local["M"]
    n_real = len(ids)
    # fan-out cap: the neighbor axis is min(fanout, max_degree) wide
    assert nbr.shape[2] == min(fanout, smp.cfg.max_degree)
    assert mask[:, n_real:].sum() == 0  # rung pads are all-masked
    for p, path in enumerate(smp.plan.metapaths):
        adj = metapath_adjacency(hg, list(path)).toarray()
        for u in range(n_real):
            ks = np.flatnonzero(mask[p, u])
            assert len(ks) <= fanout  # per-row, per-metapath cap
            for k in ks:
                v = nbr[p, u, k]
                assert v < n_real  # wired rows are extracted vertices
                # edge exists in the full graph (build_padded self-loops on)
                assert adj[ids[u], ids[v]] != 0 or ids[u] == ids[v]


@settings(max_examples=6)
@given(seed=st.integers(0, 10_000), fanout=st.integers(1, 6),
       n_req=st.integers(1, 10), layers=st.integers(1, 2))
def test_rgcn_sampled_edges_exist_and_caps_hold(seed, fanout, n_req, layers):
    hg = _rand_hg(seed)
    smp = _sampler("rgcn", hg, fanout, layers=layers)
    sb = smp.sample(_targets(hg, seed, n_req))
    _check_bijection(sb)
    for key, (nbr, mask) in sb.batch["rels"].items():
        s, _, d = key
        nbr, mask = np.asarray(nbr), np.asarray(mask)
        assert nbr.shape == (sb.batch["counts"][d],
                             min(fanout, smp.cfg.max_degree))
        ids_d, ids_s = sb.local[d], sb.local[s]
        for u in range(len(ids_d)):
            ks = np.flatnonzero(mask[u])
            assert len(ks) <= fanout
            full_nbrs = set(hg.in_neighbors(key, int(ids_d[u])).tolist())
            for k in ks:
                v = nbr[u, k]
                assert v < len(ids_s)
                assert int(ids_s[v]) in full_nbrs


@settings(max_examples=6)
@given(seed=st.integers(0, 10_000), fanout=st.integers(1, 4),
       n_req=st.integers(1, 8), layers=st.integers(1, 2))
def test_magnn_sampled_instances_are_real_paths(seed, fanout, n_req, layers):
    hg = _rand_hg(seed)
    smp = _sampler("magnn", hg, fanout, layers=layers)
    sb = smp.sample(_targets(hg, seed, n_req))
    _check_bijection(sb)
    rels = {(a, b): hg.rel(a, b).toarray()
            for p in smp.plan.metapaths for a, b in zip(p, p[1:])}
    for (nodes, mask), path in zip(sb.batch["instances"],
                                   smp.plan.metapaths):
        nodes, mask = np.asarray(nodes), np.asarray(mask)
        # fan-out cap: instances-per-target axis
        assert nodes.shape[1] == min(fanout, smp.cfg.max_instances)
        n_real = len(sb.local["M"])
        assert mask[n_real:].sum() == 0
        for u in range(n_real):
            for i in np.flatnonzero(mask[u]):
                gl = [int(sb.local[ty][nodes[u, i, j]])
                      for j, ty in enumerate(path)]
                assert gl[0] == int(sb.local["M"][u])  # anchored at the row
                for j, (a, b) in enumerate(zip(path, path[1:])):
                    assert rels[(a, b)][gl[j], gl[j + 1]] != 0, (path, gl)


# ---------------------------------------------------------------------------
# 4: shapes come only from the declared ladder
# ---------------------------------------------------------------------------

@settings(max_examples=4)
@given(seed=st.integers(0, 10_000), fanout=st.integers(1, 5),
       model=st.sampled_from(["han", "rgcn", "magnn"]),
       bucketed=st.booleans())
def test_batch_shapes_come_from_the_ladder(seed, fanout, model, bucketed):
    hg = _rand_hg(seed)
    kw = {"degree_buckets": 3} if bucketed and model != "magnn" else {}
    smp = _sampler(model, hg, fanout, **kw)
    rung_sigs = [_sig(smp.dummy_batch(i).batch)
                 for i in range(len(smp.ladder))]
    rng = np.random.default_rng(seed)
    for _ in range(6):
        tg = _targets(hg, int(rng.integers(0, 2**31)),
                      int(rng.integers(1, 11)))
        sb = smp.sample(tg)
        assert sb.rung in smp.ladder
        # pytree structure + leaf shapes identical to the warmup batch of
        # the same rung => the jitted forward hits the warmup compilation
        assert _sig(sb.batch) == rung_sigs[sb.rung_index]


@settings(max_examples=4)
@given(seed=st.integers(0, 10_000), n_req=st.integers(1, 6))
def test_truncation_never_drops_targets(seed, n_req):
    """A deliberately starved ladder truncates the frontier (counted in
    meta) but every requested target keeps a real row."""
    hg = _rand_hg(seed)
    cfg = _cfg("han", fanout=6,
               sample_ladder=((8, max(10, n_req + 2)),))
    smp = HGNNSampler(get_model(cfg).plan(), cfg, hg)
    tg = _targets(hg, seed, min(n_req, 8))
    sb = smp.sample(tg)
    _check_bijection(sb)
    row_mask = np.asarray(sb.batch["row_mask"])
    assert (row_mask[sb.target_rows] == 1.0).all()
    assert sb.meta["truncated_rows"] >= 0


def test_repeated_targets_share_one_row():
    """Two slots of a serving step may ask for the same vertex: the batch
    keeps one row for it (relabeling stays a bijection, the vertex counts
    once in the row means) and every request of it reads that row."""
    hg = _rand_hg(7)
    smp = _sampler("han", hg, fanout=1, layers=1)
    sb = smp.sample(np.array([5, 2, 5, 2, 9]))
    ids = sb.local["M"]
    assert len(ids) < hg.node_counts["M"]  # relabeled, not the identity
    _check_bijection(sb)
    assert ids[:3].tolist() == [5, 2, 9]  # first-request order
    rows = sb.target_rows
    assert rows[0] == rows[2] and rows[1] == rows[3]
    assert sb.meta["frontier_rows"] == len(ids) - 3
    assert np.asarray(sb.batch["row_mask"]).sum() == len(ids)


def test_pick_rung_prefers_smallest_fit():
    hg = _rand_hg(0)
    cfg = _cfg("han", fanout=2, sample_ladder=((2, 8), (4, 16), (8, 64)))
    smp = HGNNSampler(get_model(cfg).plan(), cfg, hg)
    assert smp.pick_rung(1, {"M": 3}) == 0
    assert smp.pick_rung(3, {"M": 3}) == 1  # targets overflow rung 0
    assert smp.pick_rung(1, {"M": 12}) == 1  # frontier overflows rung 0
    assert smp.pick_rung(8, {"M": 200}) == 2  # falls through: truncation
    with pytest.raises(ValueError, match="overflow"):
        smp.pick_rung(9, {"M": 1})
