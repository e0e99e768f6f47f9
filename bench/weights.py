"""The benchmark's weights: made on the device in one jitted call from the
seed, in the benchmark's own layout, then laid into the program's
parameter tree (:func:`to_program`)."""
from __future__ import annotations

from typing import Dict

import numpy as np


def shapes(model, cfg: Dict) -> Dict:
    """``{name: shape}`` of every weight, in the benchmark's layout: the
    per-type input projections ``fp.<type>`` and the head ``cls``, then the
    weights of ``model`` (a module of ``bench/models/``)."""
    g, d = cfg["graph"], cfg["hidden"]
    out = {f"fp.{t}": (int(g["dims"][t]), d) for t in sorted(g["dims"])}
    out["cls"] = (d, cfg["n_classes"])
    out.update(model.weight_shapes(cfg))
    return out


def make(model, cfg: Dict, seed: int) -> Dict:
    """Flat ``{name: device array}``, float32, each N(0, 1) times the
    model's ``weight_scale``, drawn in sorted name order from ``seed`` in one
    jitted call."""
    import jax
    import jax.numpy as jnp

    sh = shapes(model, cfg)
    names = sorted(sh)

    def draw(key):
        out = {}
        for name, k in zip(names, jax.random.split(key, len(names))):
            shape = sh[name]
            scale = model.weight_scale(name, shape)
            out[name] = jax.random.normal(k, shape, jnp.float32) * scale
        return out

    word = np.random.SeedSequence(seed).generate_state(1)[0]
    return jax.jit(draw)(jax.random.key(int(word)))


def nested(cfg: Dict, flat: Dict) -> Dict:
    """The reference's view: ``fp``, ``cls`` and one dict per layer."""
    w = {"fp": {}, "cls": flat["cls"], "layers": [{} for _ in
                                                  range(cfg["layers"])]}
    for name, v in flat.items():
        parts = name.split(".")
        if parts[0] == "fp":
            w["fp"][parts[1]] = v
        elif parts[0] != "cls":
            lw = w["layers"][int(parts[0])]
            if len(parts) == 3:
                lw.setdefault(parts[1], {})[parts[2]] = v
            else:
                lw[parts[1]] = v
    return w


class LayoutChanged(Exception):
    pass


def to_program(model, flat: Dict, like):
    """The program's parameter pytree (``like``, as its ``init`` made it)
    with every leaf taken from ``flat``: the shared ``fp.<type>`` and
    ``cls`` here, every other leaf by ``model.program_leaf``.  A leaf the
    benchmark cannot name, or one whose shape differs, raises: the
    program's parameter layout has changed and this mapping must follow
    it."""
    import jax
    import jax.numpy as jnp
    from jax.tree_util import DictKey, SequenceKey

    def name_of(path):
        keys = [k.key if isinstance(k, DictKey) else k.idx for k in path
                if isinstance(k, (DictKey, SequenceKey))]
        layer = 0
        if keys and keys[0] == "layers":
            layer, keys = keys[1] + 1, keys[2:]
        if keys[0] == "cls":
            return flat["cls"]
        if keys[0] == "fp" and layer == 0:
            return flat[f"fp.{keys[1]}"]
        return model.program_leaf(flat, layer, keys)

    def fill(path, leaf):
        try:
            v = name_of(path)
        except (KeyError, IndexError) as e:
            raise LayoutChanged(f"no benchmark weight for program leaf "
                                f"{jax.tree_util.keystr(path)}") from e
        if tuple(v.shape) != tuple(leaf.shape) or v.dtype != leaf.dtype:
            raise LayoutChanged(f"program leaf {jax.tree_util.keystr(path)} "
                                f"is {leaf.dtype}{list(leaf.shape)}, the "
                                f"benchmark's {v.dtype}{list(v.shape)}")
        return jnp.asarray(v)

    return jax.tree_util.tree_map_with_path(fill, like)
