"""`repro.dist.partition`: partition/relabel round-trip invariants.

Pins the host-side partitioner contracts the partitioned execution mode
rests on: ownership is a capacity-bounded exact cover, every (masked-valid)
edge of the original layout survives relabeling exactly once and maps back
to the same global endpoints, the halo index maps point at rows the owner
actually populates, and the device-side ``gather_halo`` exchange fetches
exactly the rows the maps name.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.base import HGNNConfig
from repro.core.models import get_model
from repro.data.synthetic import DATASET_METAPATHS, DATASET_TARGET
from repro.dist import partition as dp
from repro.serve.engine import HGNNRequest, HGNNServeEngine
from repro.serve.sampler import HGNNSampler


def _tiny_tables():
    DATASET_METAPATHS["tiny"] = [["M", "D", "M"], ["M", "A", "M"]]
    DATASET_TARGET["tiny"] = "M"


def _cfg(model, **kw):
    _tiny_tables()
    kw = {"max_degree": 48, "max_instances": 4, **kw}
    return HGNNConfig(model=model, dataset="tiny", hidden=16, n_heads=4,
                      n_classes=3, **kw)


# ---------------------------------------------------------------------------
# assignment primitives
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,k", [(40, 4), (17, 4), (5, 8), (0, 2)])
def test_edge_cut_assign_exact_cover_and_capacity(n, k):
    rng = np.random.default_rng(0)
    neigh = [rng.integers(0, max(n, 1), rng.integers(0, 6)).astype(np.int64)
             for _ in range(n)]
    owner = dp.edge_cut_assign(neigh, max(n, 1), k)
    assert owner.shape == (n,)
    if n:
        assert owner.min() >= 0 and owner.max() < k
        cap = -(-n // k)
        assert np.bincount(owner, minlength=k).max() <= cap


def test_edge_cut_assign_clusters_shared_neighbors():
    # two cliques reading disjoint token sets must not be interleaved
    neigh = [np.array([0, 1, 2])] * 4 + [np.array([10, 11, 12])] * 4
    owner = dp.edge_cut_assign(neigh, 13, 2)
    assert len(set(owner[:4])) == 1 and len(set(owner[4:])) == 1
    assert owner[0] != owner[4]


def test_reference_assign_majority_and_capacity():
    votes = np.zeros((8, 2))
    votes[:6, 1] = 5.0  # six vertices read mostly by partition 1
    owner = dp.reference_assign(votes, 2)
    assert np.bincount(owner, minlength=2).max() <= 4  # cap = ceil(8/2)
    assert (owner[:6] == 1).sum() == 4  # majority honoured up to capacity


def test_type_partition_is_a_bijection():
    owner = np.array([1, 0, 1, 2, 0, 2, 1], np.int32)
    tp = dp.build_type_partition(owner, 3)
    flat = tp.flat
    assert len(np.unique(flat)) == len(owner)  # injective into own slots
    own_flat = tp.own.reshape(-1)
    mask_flat = tp.own_mask.reshape(-1)
    assert (mask_flat[flat] == 1.0).all()
    assert (own_flat[flat] == np.arange(len(owner))).all()  # round trip


# ---------------------------------------------------------------------------
# partitioned-batch round-trip invariants
# ---------------------------------------------------------------------------


def _roundtrip_stacked(tiny_hg, k):
    cfg_ref = _cfg("han", fused=True)
    ref = get_model(cfg_ref).prepare(tiny_hg)
    nbr, mask = np.asarray(ref["nbr"]), np.asarray(ref["mask"])
    m = get_model(_cfg("han", fused=True, partitions=k))
    b = m.prepare(tiny_hg)
    part = b["part"]
    own = np.asarray(part["own_mask"]["M"])
    inv = np.asarray(part["inv"])
    n_max = own.shape[1]
    # ownership covers every target row exactly once
    assert (own.reshape(-1)[inv] == 1.0).all()
    assert own.sum() == nbr.shape[1]
    own_ids = np.asarray(part["own"]["M"]).astype(np.int64)
    # inv and own agree: the flat own-order slot of row g holds g
    assert (own_ids.reshape(-1)[inv] == np.arange(nbr.shape[1])).all()
    # reconstruct the global layout from the partition-local one
    local_tab = _local_to_global(part, "M")  # [K, n_max + H]
    nbr_p, mask_p = np.asarray(part["nbr"]), np.asarray(part["mask"])
    total_edges = 0
    for j in range(k):
        rows = np.flatnonzero(own[j] > 0)
        for i in rows:
            g = int(own_ids[j, i])
            for p in range(nbr.shape[0]):
                valid = mask_p[j, p, i] > 0
                total_edges += int(valid.sum())
                # same neighbor multiset, mapped back to global ids
                got = np.sort(local_tab[j, nbr_p[j, p, i][valid]])
                want = np.sort(nbr[p, g][mask[p, g] > 0])
                np.testing.assert_array_equal(got, want)
    # every edge covered exactly once
    assert total_edges == int((mask > 0).sum())
    return part


@pytest.mark.parametrize("k", [1, 3])
def test_stacked_partition_roundtrip(tiny_hg, k):
    part = _roundtrip_stacked(tiny_hg, k)
    meta = part["meta"]
    assert 0 <= meta["cut_edges"] <= meta["edges_total"]
    if k == 1:
        assert meta["cut_edges"] == 0
        assert np.asarray(part["halo_src"]["M"]).shape[1] == 0


def test_halo_maps_point_at_populated_remote_rows(tiny_hg):
    m = get_model(_cfg("han", fused=True, partitions=3))
    part = m.prepare(tiny_hg)["part"]
    own = np.asarray(part["own_mask"]["M"])
    halo_src = np.asarray(part["halo_src"]["M"])
    halo_mask = np.asarray(part["halo_mask"]["M"])
    n_max = own.shape[1]
    for j in range(halo_src.shape[0]):
        valid = halo_src[j][halo_mask[j] > 0]
        # every halo entry names a populated slot owned by ANOTHER partition
        assert (own.reshape(-1)[valid] == 1.0).all()
        assert (valid // n_max != j).all()
        assert len(np.unique(valid)) == len(valid)  # no duplicate fetches


def _local_to_global(part, ty):
    """[K, n_max + H_max] table: partition-local coordinate -> global id."""
    own_ids = np.asarray(part["own"][ty]).astype(np.int64)
    halo_src = np.asarray(part["halo_src"][ty])
    halo_ids = own_ids.reshape(-1)[halo_src]
    return np.concatenate([own_ids, halo_ids], axis=1)


def test_relational_partition_roundtrip(tiny_hg):
    k = 3
    ref = get_model(_cfg("rgcn", fused=True)).prepare(tiny_hg)
    m = get_model(_cfg("rgcn", fused=True, partitions=k))
    b = m.prepare(tiny_hg)
    part = b["part"]
    assert sorted(b["rels"]) == sorted(ref["rels"])  # init keys preserved
    inv = np.asarray(part["inv"])
    own_t = np.asarray(part["own_mask"]["M"])
    own_ids_t = np.asarray(part["own"]["M"]).astype(np.int64)
    assert (own_t.reshape(-1)[inv] == 1.0).all()
    for key, (nbr_p, mask_p) in part["rels"].items():
        s = key[0]
        assert key[2] == "M"  # only relations into the target are kept
        nbr_ref, mask_ref = (np.asarray(x) for x in ref["rels"][key])
        local_tab = _local_to_global(part, s)
        nbr_pn, mask_pn = np.asarray(nbr_p), np.asarray(mask_p)
        total = 0
        for j in range(k):
            for i in np.flatnonzero(own_t[j] > 0):
                g = int(own_ids_t[j, i])
                valid = mask_pn[j, i] > 0
                total += int(valid.sum())
                got = np.sort(local_tab[j, nbr_pn[j, i][valid]])
                want = np.sort(nbr_ref[g][mask_ref[g] > 0])
                np.testing.assert_array_equal(got, want)
        assert total == int((mask_ref > 0).sum())  # every edge exactly once


def test_instances_partition_roundtrip(tiny_hg):
    k = 3
    m_ref = get_model(_cfg("magnn"))
    ref = m_ref.prepare(tiny_hg)
    m = get_model(_cfg("magnn", partitions=k))
    b = m.prepare(tiny_hg)
    part = b["part"]
    own_t = np.asarray(part["own_mask"]["M"])
    own_ids_t = np.asarray(part["own"]["M"]).astype(np.int64)
    assert (np.asarray(part["own_mask"]["M"]).reshape(-1)[
        np.asarray(part["inv"])] == 1.0).all()
    tabs = {ty: _local_to_global(part, ty) for ty in part["own"]}
    for (nodes_ref, mask_ref), (nodes_p, mask_p), path in zip(
            ref["instances"], part["instances"], m.plan().metapaths):
        nodes_ref, mask_ref = np.asarray(nodes_ref), np.asarray(mask_ref)
        nodes_p, mask_p = np.asarray(nodes_p), np.asarray(mask_p)
        assert mask_p.sum() == mask_ref.sum()  # instance count preserved
        for j in range(k):
            for i in np.flatnonzero(own_t[j] > 0):
                g = int(own_ids_t[j, i])
                valid = mask_p[j, i] > 0
                assert valid.sum() == (mask_ref[g] > 0).sum()
                # each position's local ids map back to the same global ids
                for pos, ty in enumerate(path):
                    got = np.sort(tabs[ty][j, nodes_p[j, i][valid][:, pos]])
                    want = np.sort(nodes_ref[g][mask_ref[g] > 0][:, pos])
                    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# the device-side halo exchange
# ---------------------------------------------------------------------------


def test_gather_halo_matches_flat_numpy_gather():
    rng = np.random.default_rng(3)
    k, n, h, d = 4, 6, 5, 8
    h_own = rng.standard_normal((k, n, d)).astype(np.float32)
    halo_src = rng.integers(0, k * n, (k, h)).astype(np.int32)
    got = np.asarray(dp.gather_halo(jax.numpy.asarray(h_own),
                                    jax.numpy.asarray(halo_src)))
    want = h_own.reshape(k * n, d)[halo_src]
    np.testing.assert_allclose(got, want)


def test_gather_halo_empty_halo():
    h_own = jax.numpy.ones((2, 3, 4))
    halo_src = jax.numpy.zeros((2, 0), jax.numpy.int32)
    assert dp.gather_halo(h_own, halo_src).shape == (2, 0, 4)


def test_partition_batch_rejects_unsupported_layouts(tiny_hg):
    with pytest.raises(ValueError, match="stacked layout"):
        get_model(_cfg("han", fused=True, degree_buckets=3,
                       partitions=2)).plan()
    with pytest.raises(ValueError, match="padded per-relation"):
        get_model(_cfg("rgcn", fused=False, partitions=2)).plan()
    from repro.core.models.gcn import GCN

    with pytest.raises(ValueError, match="no partitioned execution"):
        GCN(HGNNConfig(model="gcn", dataset="reddit", partitions=2)).plan()


# ---------------------------------------------------------------------------
# static partition shapes (what partitioned serving runs)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model,kw", [
    ("han", {}), ("rgcn", {}), ("magnn", {}), ("rgcn", {"layers": 2}),
    ("han", {"layers": 2}),
], ids=["han", "rgcn", "magnn", "rgcn-L2", "han-L2"])
def test_static_partition_shapes_are_bitexact(tiny_hg, model, kw):
    """static_shapes pads every per-type table to assignment-independent
    capacities (n_max=ceil(n/k), h_max=n).  Pad rows are masked dead weight:
    the forward over the padded batch must be BIT-EXACT the dynamic one."""
    cfg = _cfg(model, fused=True, partitions=4, **kw)
    m = get_model(cfg)
    b_dyn = m.prepare(tiny_hg)
    params = m.init(jax.random.key(0), b_dyn)
    out_dyn = np.asarray(jax.jit(m.forward)(params, b_dyn))
    plan = m.plan()
    plan_s = dataclasses.replace(
        plan, partition=dataclasses.replace(plan.partition,
                                            static_shapes=True))
    b_raw = get_model(_cfg(model, fused=True, **kw)).prepare(tiny_hg)
    b_stat = dp.partition_batch(plan_s, b_raw)
    out_stat = np.asarray(jax.jit(m.forward)(params, b_stat))
    np.testing.assert_array_equal(out_dyn, out_stat)
    # the capacities are assignment-independent: ceil(40/4) target rows
    # per partition, halo capped at the type count
    t = plan.target
    assert b_stat["part"]["own"][t].shape == (4, 10)
    assert b_stat["part"]["halo_src"][t].shape == (4, 40)


def _mixed_requests(n, n_nodes=40, seed=3):
    rng = np.random.default_rng(seed)
    return [HGNNRequest(targets=rng.integers(
        0, n_nodes, size=int(rng.integers(1, 9)))) for _ in range(n)]


def test_partitioned_sampled_serving_zero_recompiles(tiny_hg):
    """Partitioned sampled serving used to re-trace every step
    (data-dependent halo widths).  With the engine's static_shapes serve
    plan the warmed ladder covers every step."""
    for model in ("han", "rgcn", "magnn"):
        cfg = _cfg(model, fused=True, fanout=8, partitions=4)
        m = get_model(cfg)
        sampler = HGNNSampler(m.plan(), cfg, tiny_hg)
        batch = m.prepare(tiny_hg)
        params = m.init(jax.random.key(0), batch)
        eng = HGNNServeEngine(m.executor, params, sampler, slots=4,
                              slot_targets=2)
        eng.warmup()
        eng.serve(_mixed_requests(10))
        st = eng.stats()
        assert st["steps"] > 1
        assert st["compiles_after_warmup"] == 0, model
