"""The persistent compile cache is placed from outside the program: the
environment's directory when ``JAX_COMPILATION_CACHE_DIR`` is set, else one
fixed directory inside the checkout.  Nothing here turns the cache on."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache as cc

REPO = Path(__file__).resolve().parents[1]


def test_env_var_set_wins_and_is_not_set_in_code():
    assert cc.cache_dir({cc.ENV: "/srv/jax-cache"}) == ("/srv/jax-cache", False)


def test_unset_uses_a_fixed_path_in_the_checkout():
    path, set_in_code = cc.cache_dir({})
    assert set_in_code
    assert Path(path) == REPO / ".jax_cache"
    assert cc.cache_dir({}) == (path, True)  # no temp/pid/time component
    assert cc.cache_dir({cc.ENV: ""}) == (path, True)  # empty means unset
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


@pytest.mark.parametrize("env", [{cc.ENV: "/srv/jax-cache"}, {}],
                         ids=["env-set", "env-unset"])
def test_enable_sets_the_directory_only_when_unset(monkeypatch, env):
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.__setitem__(name, value))
    path = cc.enable_compile_cache(env)
    if env:
        assert path == "/srv/jax-cache"
        assert "jax_compilation_cache_dir" not in updates
    else:
        assert updates["jax_compilation_cache_dir"] == str(REPO / ".jax_cache")
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0
