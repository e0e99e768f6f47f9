"""The XLA GAT body over flat neighbor rows (``stages.gat_aggregate_padded``)
against the per-head ``[N, K, H, Dh]`` formula it replaced: masked softmax
over K with the ``-1e9`` fill, max subtraction and ``1e-9`` floor, and zero
output for a row with no neighbor."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import stages


def _per_head(p, h_dst, h_src, nbr, mask):
    """Gathers ``[N, K, H, Dh]`` blocks and the source scores separately."""
    e_dst = (h_dst * p["a_dst"]).sum(-1)
    hn = h_src[nbr]
    e_nbr = (h_src * p["a_src"]).sum(-1)[nbr]
    e = stages._leaky_relu(e_dst[:, None, :] + e_nbr)
    e = jnp.where(mask[..., None] > 0, e, -1e9)
    e = e - e.max(axis=1, keepdims=True)
    w = jnp.exp(e) * mask[..., None]
    alpha = w / jnp.maximum(w.sum(axis=1, keepdims=True), 1e-9)
    return jnp.einsum("nkh,nkhd->nhd", alpha, hn,
                      precision=jax.lax.Precision.HIGHEST)


def _case(seed, n, m, k, h, dh):
    """Random inputs; row 0 has no neighbor, row 1 exactly one (its last
    slot), the rest a random mask."""
    rng = np.random.default_rng(seed)
    arr = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    mask = (rng.random((n, k)) < 0.7).astype(np.float32)
    mask[0] = 0.0
    mask[1] = 0.0
    mask[1, -1] = 1.0
    p = {"a_dst": 0.3 * arr(h, dh), "a_src": 0.3 * arr(h, dh)}
    nbr = jnp.asarray(rng.integers(0, m, (n, k)), jnp.int32)
    return p, arr(n, h, dh), arr(m, h, dh), nbr, jnp.asarray(mask)


@pytest.mark.parametrize("n,m,k,h,dh", [
    (24, 30, 1, 8, 8),
    (24, 30, 8, 8, 8),
    (40, 50, 64, 8, 8),
    (16, 20, 5, 1, 8),
    (16, 20, 6, 4, 16),
], ids=["K1", "K8", "K64", "one-head", "wide-heads"])
def test_flat_rows_match_per_head_formula(n, m, k, h, dh):
    p, h_dst, h_src, nbr, mask = _case(k * h, n, m, k, h, dh)
    got = np.asarray(stages.gat_aggregate_padded(p, h_dst, h_src, nbr, mask))
    want = np.asarray(_per_head(p, h_dst, h_src, nbr, mask))
    assert got.shape == (n, h, dh)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert not got[0].any()  # every neighbor masked -> exactly 0
    only = np.asarray(h_src)[int(nbr[1, -1])]  # the one neighbor, alpha 1
    np.testing.assert_allclose(got[1], only, rtol=1e-6, atol=1e-6)


def test_head_matrices():
    """Block-diagonal scores equal the per-head dot; the spread is 0/1."""
    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.standard_normal((4, 8)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((5, 4, 8)), jnp.float32)
    score, spread = stages.head_matrices(a)
    assert score.shape == (32, 4) and spread.shape == (4, 32)
    np.testing.assert_allclose(
        jnp.dot(x.reshape(5, -1), score, precision=jax.lax.Precision.HIGHEST),
        (x * a).sum(-1), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(spread, np.repeat(np.eye(4), 8, axis=1))
