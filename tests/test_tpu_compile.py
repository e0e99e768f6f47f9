"""The main-path Pallas kernels compile for a TPU v5e at IMDB widths, and off
TPU no kernel wrapper pretends to have run one.

The compiles are ahead of time, for a v5e described (not attached) by the
TPU compiler installed with JAX: what Mosaic refuses there (vector gathers,
3-D contractions, blocks off the (8, 128) tiling, more scoped VMEM than a
kernel asked for) fails here.  Interpret-mode parity lives in
``test_gat_na.py`` / ``test_kernels.py``; this file only proves the kernels
lower.  Widths are HAN/RGCN on IMDB: 4278 movies, 8 heads x 8 dims, K=64
neighbors, 2 metapaths, a 128-wide semantic-attention hidden layer.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import stages
from repro.kernels import (feature_cache, fused_fp_na, gat_na, ops,
                           segment_spmm, semantic_attn, streaming)

N = M = 4278  # IMDB movies: HAN's target table and metapath source pool
H, DH, K, P, HS = 8, 8, 64, 2, 128
D = H * DH
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 (never attached)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


GAT_ARGS = [((P, H, DH), F32), ((P, H, DH), F32), ((N, H, DH), F32),
            ((M, H, DH), F32), ((P, N, K), I32), ((P, N, K), F32)]


@pytest.mark.parametrize("block_m", [0, 512], ids=["resident", "streaming"])
def test_gat_na_compiles(one_chip, block_m):
    def fn(ad, as_, hd, hs, nbr, mask):
        return gat_na.gat_na({"a_dst": ad, "a_src": as_}, hd, hs, nbr, mask,
                             block_m=block_m)

    assert "tpu_custom_call" in _hlo(fn, one_chip, *GAT_ARGS)


def test_gat_na_fused_sa_compiles(one_chip):
    def fn(ad, as_, hd, hs, nbr, mask, w, b, q):
        return gat_na.gat_na({"a_dst": ad, "a_src": as_}, hd, hs, nbr, mask,
                             sem={"W": w, "b": b, "q": q})

    hlo = _hlo(fn, one_chip, *GAT_ARGS, ((D, HS), F32), ((HS,), F32),
               ((HS,), F32))
    assert "tpu_custom_call" in hlo


def test_gat_na_resident_table_at_vmem_budget(one_chip):
    """A source table just under VMEM_TABLE_BUDGET stays resident and fits
    the scoped VMEM limit the kernel asks for (double-buffered block)."""
    m = streaming.VMEM_TABLE_BUDGET // (128 * 4) - 8  # 64-wide f32 rows
    assert streaming.table_fits_vmem(m, D, 4)
    assert not streaming.table_fits_vmem(m + 16, D, 4)

    def fn(ad, as_, hd, hs, nbr, mask):
        return gat_na.gat_na({"a_dst": ad, "a_src": as_}, hd, hs, nbr, mask)

    args = list(GAT_ARGS)
    args[3] = ((m, H, DH), F32)
    assert "tpu_custom_call" in _hlo(fn, one_chip, *args)


def test_xla_gat_na_gathers_each_neighbor_once_as_a_flat_row(one_chip):
    """HAN's default NA (the XLA body, no Pallas) at IMDB widths: one
    gather over the P*N*K neighbor slots, of 64-wide rows, never an
    ``[H, Dh] = [8, 8]`` block per neighbor (each its own (8, 128) tile,
    16x padded), and temporaries far under the 2.5 GB that form took."""
    def fn(ad, as_, hd, hs, nbr, mask):
        z = stages.gat_aggregate_padded_stacked(
            {"a_dst": ad, "a_src": as_}, hd, nbr, mask, h_src=hs)
        return jax.nn.elu(z)

    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in GAT_ARGS]
    compiled = jax.jit(fn).lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" not in hlo
    slots = rf"\[(?:{P * N * K}|{P},{N},{K})"  # leading dims of a slot array
    gathers = re.findall(rf"{slots}[\d,]*\]\S* gather\(", hlo)
    assert len(gathers) == 1, gathers
    assert not re.search(rf"{slots},{H},{DH}\]", hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


@pytest.mark.parametrize("block_m", [0, 512], ids=["resident", "streaming"])
def test_segment_spmm_compiles(one_chip, block_m):
    """RGCN's mean NA: actors (5257 x 64 projected) into movies."""
    def fn(h, nbr, mask):
        return segment_spmm.segment_spmm(h, nbr, mask, block_m=block_m)

    hlo = _hlo(fn, one_chip, ((5257, D), F32), ((N, K), I32), ((N, K), F32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m,f", [(2081, 2081), (M, 3066)],
                         ids=["resident-directors", "streaming-movies"])
def test_fused_fp_na_compiles(one_chip, m, f):
    """Raw IMDB features: a director slab fits VMEM, a movie slab streams."""
    hlo = _hlo(fused_fp_na.fused_fp_na, one_chip, ((m, f), F32),
               ((f, D), F32), ((N, K), I32), ((N, K), F32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("budget", [streaming.VMEM_TABLE_BUDGET, 1],
                         ids=["resident", "streaming"])
def test_semantic_attention_compiles(one_chip, budget):
    def fn(z, w, b, q):
        wp = semantic_attn.semantic_scores(z, w, b, q, vmem_budget=budget)
        return semantic_attn.semantic_combine(z, jax.nn.softmax(wp))

    hlo = _hlo(fn, one_chip, ((P, N, D), F32), ((D, HS), F32), ((HS,), F32),
               ((HS,), F32))
    assert "tpu_custom_call" in hlo


def test_semantic_combine_compiles(one_chip):
    hlo = _hlo(semantic_attn.semantic_combine, one_chip, ((P, N, D), F32),
               ((P,), F32))
    assert "tpu_custom_call" in hlo


def test_cached_gather_compiles(one_chip):
    """MAGNN's instance gathers (16 per movie) through a 1024-row cache."""
    hlo = _hlo(feature_cache.cached_gather, one_chip, ((M, D), F32),
               ((1024,), I32), ((N, 16), I32))
    assert "tpu_custom_call" in hlo


# ---------------------------------------------------------------------------
# off TPU, use_pallas=True without interpret raises (no silent reference)
# ---------------------------------------------------------------------------

def _z(*shape, dtype=jnp.float32):
    return jnp.zeros(shape, dtype)


_GAT = ({"a_dst": _z(2, 4), "a_src": _z(2, 4)}, _z(6, 2, 4), _z(6, 2, 4),
        _z(6, 3, dtype=jnp.int32), _z(6, 3))
_GAT_S = ({"a_dst": _z(2, 2, 4), "a_src": _z(2, 2, 4)}, _z(6, 2, 4),
          _z(6, 2, 4), _z(2, 6, 3, dtype=jnp.int32), _z(2, 6, 3))
WRAPPERS = {
    "segment_spmm": (_z(6, 4), _z(6, 3, dtype=jnp.int32), _z(6, 3)),
    "fused_fp_na": (_z(6, 5), _z(5, 4), _z(6, 3, dtype=jnp.int32), _z(6, 3)),
    "semantic_attention": (_z(2, 6, 4), _z(4, 3), _z(3), _z(3)),
    "flash_attention": (_z(1, 8, 2, 4), _z(1, 8, 2, 4), _z(1, 8, 2, 4)),
    "decode_attention": (_z(1, 2, 4), _z(1, 8, 2, 4), _z(1, 8, 2, 4),
                         jnp.ones((1,), jnp.int32)),
    "cached_gather": (_z(6, 4), _z(2, dtype=jnp.int32),
                      _z(5, dtype=jnp.int32)),
    "gat_aggregate": _GAT,
    "gat_aggregate_stacked": _GAT_S,
    "gat_aggregate_stacked_fused_sa": _GAT_S + (
        {"W": _z(8, 3), "b": _z(3), "q": _z(3)},),
    "semantic_combine": (_z(2, 6, 4), _z(2)),
}


@pytest.mark.parametrize("name", sorted(WRAPPERS))
def test_pallas_wrapper_raises_off_tpu(name):
    assert jax.default_backend() != "tpu"
    fn, args = getattr(ops, name), WRAPPERS[name]
    with pytest.raises(RuntimeError, match="use_pallas=True needs a TPU"):
        fn(*args, use_pallas=True)
    out = fn(*args, use_pallas=False)  # the XLA path still runs
    assert all(np.isfinite(np.asarray(o)).all()
               for o in jax.tree_util.tree_leaves(out))
