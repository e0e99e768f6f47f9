"""The characterizer itself (the paper's methodology): exact FLOP counts on
known graphs, loop trip-count handling, class attribution, collective bytes,
roofline terms."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import characterize as ch


def _analyze(fn, *args):
    comp = jax.jit(fn).lower(*args).compile()
    return ch.analyze_hlo_text(comp.as_text())


def test_matmul_flops_exact():
    a = jnp.ones((64, 128), jnp.float32)
    b = jnp.ones((128, 32), jnp.float32)
    rep = _analyze(lambda a, b: a @ b, a, b)
    assert rep["flops_by_class"]["DM"] == 2 * 64 * 128 * 32


def test_scan_trip_count_multiplies():
    def f(x, ws):
        y, _ = jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, ws)
        return y

    x = jnp.ones((32, 32), jnp.float32)
    ws = jnp.ones((12, 32, 32), jnp.float32)
    rep = _analyze(f, x, ws)
    assert rep["flops_by_class"]["DM"] == 12 * 2 * 32 * 32 * 32


def test_gather_classified_tb():
    x = jnp.ones((100, 16), jnp.float32)
    idx = jnp.zeros((50,), jnp.int32)
    rep = _analyze(lambda x, i: x[i], x, idx)
    assert rep["op_counts"].get("TB", 0) >= 1


def test_ew_and_dr_classes():
    x = jnp.ones((64, 64), jnp.float32)
    rep = _analyze(lambda x: jnp.tanh(x) + 1.0, x)
    assert rep["flops_by_class"].get("EW", 0) > 0
    rep2 = _analyze(lambda x: jnp.concatenate([x, x], axis=0).T, x)
    assert rep2["hbm_bytes_by_class"].get("DR", 0) > 0 or \
        rep2["hbm_bytes_by_class"].get("EW", 0) > 0


def test_shape_bytes_parsing():
    assert ch.shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert ch.shape_bytes("bf16[2,3,4]") == 48
    assert ch.shape_bytes("(s32[], f32[8]{0})") == 4 + 32
    assert ch.shape_bytes("pred[]") == 1
    assert ch.shape_bytes("token[]") == 0


def test_roofline_terms_and_bound():
    per_dev = {"total_flops": 197e12, "total_hbm_bytes": 819e9 / 2,
               "collective_bytes": 0.0}
    r = ch.roofline(per_dev, n_chips=1, model_fl=197e12)
    assert abs(r["compute_s"] - 1.0) < 1e-6
    assert abs(r["memory_s"] - 0.5) < 1e-6
    assert r["bound"] == "compute"
    assert abs(r["mfu_proxy"] - 1.0) < 1e-6
    per_dev["collective_bytes"] = 50e9 * 3
    r = ch.roofline(per_dev, n_chips=1, model_fl=197e12)
    assert r["bound"] == "collective"


def test_peaks_keyed_by_device_kind():
    """One table keyed by device_kind; an unknown kind raises; a CPU host
    costs its HLO against the named v5e model."""
    v5e = ch.peaks("TPU v5 lite")
    assert (v5e["flops"], v5e["hbm_bw"]) == (197e12, 819e9)
    with pytest.raises(ValueError, match="TPU v9"):
        ch.peaks("TPU v9")
    assert ch.peaks()["device_kind"] == ch.CPU_MODEL_KIND == "TPU v5 lite"
    per_dev = {"total_flops": 1.0, "total_hbm_bytes": 1.0,
               "collective_bytes": 0.0}
    assert ch.roofline(per_dev, 1, 0.0)["peaks_of"] == "TPU v5 lite"
    with pytest.raises(ValueError):
        ch.roofline(per_dev, 1, 0.0, device_kind="TPU v9")


def test_model_flops_formulas():
    from repro.configs import get_config
    from repro.configs.base import SHAPES

    cfg = get_config("granite-8b")
    n_total, n_active = ch.analytic_param_counts(cfg)
    assert n_total == n_active  # dense
    mf_train = ch.model_flops(cfg, SHAPES["train_4k"], n_total, n_active)
    assert abs(mf_train - 6 * n_total * 256 * 4096) / mf_train < 1e-9
    mf_dec = ch.model_flops(cfg, SHAPES["decode_32k"], n_total, n_active)
    assert abs(mf_dec - 2 * n_total * 128) / mf_dec < 1e-9


def test_moe_active_params_fraction():
    from repro.configs import get_config

    cfg = get_config("phi3.5-moe-42b-a6.6b")
    total, active = ch.analytic_param_counts(cfg)
    assert active < 0.35 * total  # top-2 of 16 experts + attention


def test_collective_bytes_sharded_matmul():
    """All-gather bytes appear for a TP matmul on a small forced-device run."""
    import subprocess, sys, textwrap

    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import characterize as ch
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        w_s = NamedSharding(mesh, P(None, "model"))
        x_s = NamedSharding(mesh, P("data", None))
        f = jax.jit(lambda x, w: (x @ w).sum(), in_shardings=(x_s, w_s))
        c = f.lower(jax.ShapeDtypeStruct((64, 64), jnp.float32),
                    jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
        rep = ch.analyze_hlo_text(c.as_text())
        assert rep["collective_bytes"] > 0, rep
        print("OK", rep["collective_bytes"])
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**__import__("os").environ,
                                       "PYTHONPATH": "src"})
    assert "OK" in r.stdout, r.stdout + r.stderr
