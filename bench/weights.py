"""The benchmark's weights: made on the device in one jitted call from the
seed, in the benchmark's own layout, then laid into the program's
parameter tree (:func:`to_program`)."""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def _rel_keys(cfg: Dict) -> List[Tuple[str, str, str]]:
    keys = []
    for s, r, d, _n, rev in cfg["graph"]["relations"]:
        keys += [(s, r, d), (d, rev, s)]
    return sorted(keys)


def shapes(cfg: Dict) -> Dict:
    """``{name: shape}`` of every weight, in the benchmark's layout."""
    g, d = cfg["graph"], cfg["hidden"]
    out = {f"fp.{t}": (int(g["dims"][t]), d) for t in sorted(g["dims"])}
    out["cls"] = (d, cfg["n_classes"])
    for l in range(cfg["layers"]):
        if cfg["model"] == "han":
            p, heads = len(g["metapaths"]), cfg["n_heads"]
            if l > 0:
                out[f"{l}.fp"] = (d, d)
            out[f"{l}.gat_dst"] = (p, heads, d // heads)
            out[f"{l}.gat_src"] = (p, heads, d // heads)
            out[f"{l}.sem_W"] = (d, cfg["attn_hidden"])
            out[f"{l}.sem_b"] = (cfg["attn_hidden"],)
            out[f"{l}.sem_q"] = (cfg["attn_hidden"],)
        else:
            for key in _rel_keys(cfg):
                out[f"{l}.w_rel.{'|'.join(key)}"] = (d, d)
            for t in sorted(g["counts"]):
                out[f"{l}.w_self.{t}"] = (d, d)
    return out


def make(cfg: Dict, seed: int) -> Dict:
    """Flat ``{name: device array}``, float32, N(0, 1/fan_in) (biases
    N(0, 0.01)), from ``seed`` in one jitted call."""
    import jax
    import jax.numpy as jnp

    sh = shapes(cfg)
    names = sorted(sh)

    def draw(key):
        out = {}
        for name, k in zip(names, jax.random.split(key, len(names))):
            shape = sh[name]
            scale = 0.1 if name.endswith("sem_b") else 1.0 / np.sqrt(
                shape[-1] if name.endswith(("gat_dst", "gat_src", "sem_q"))
                else shape[0])
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


def to_program(cfg: Dict, flat: Dict, like):
    """The program's parameter pytree (``like``, as its ``init`` made it)
    with every leaf taken from ``flat``.  A leaf the benchmark cannot name,
    or one whose shape differs, raises: the program's parameter layout has
    changed and this mapping must follow it."""
    import jax
    import jax.numpy as jnp
    from jax.tree_util import DictKey, SequenceKey

    def name_of(path, leaf):
        keys = [k.key if isinstance(k, DictKey) else k.idx for k in path
                if isinstance(k, (DictKey, SequenceKey))]
        layer = 0
        if keys and keys[0] == "layers":
            layer, keys = keys[1] + 1, keys[2:]
        head = keys[0]
        if head == "cls":
            return flat["cls"]
        if head == "fp":
            if layer == 0:
                return flat[f"fp.{keys[1]}"]
            return flat[f"{layer}.fp"]
        if head == "gat":
            if len(keys) == 2:  # stacked [P, H, Dh]
                return flat[f"{layer}.gat_{keys[1][2:]}"]
            return flat[f"{layer}.gat_{keys[2][2:]}"][keys[1]]
        if head == "sem":
            return flat[f"{layer}.sem_{keys[1]}"]
        if head == "w_rel":
            return flat[f"{layer}.w_rel.{'|'.join(keys[1])}"]
        if head == "w_self":
            return flat[f"{layer}.w_self.{keys[1]}"]
        raise KeyError(path)

    def fill(path, leaf):
        try:
            v = name_of(path, leaf)
        except (KeyError, IndexError) as e:
            raise LayoutChanged(f"no benchmark weight for program leaf "
                                f"{jax.tree_util.keystr(path)}") from e
        if tuple(v.shape) != tuple(leaf.shape) or v.dtype != leaf.dtype:
            raise LayoutChanged(f"program leaf {jax.tree_util.keystr(path)} "
                                f"is {leaf.dtype}{list(leaf.shape)}, the "
                                f"benchmark's {v.dtype}{list(v.shape)}")
        return jnp.asarray(v)

    return jax.tree_util.tree_map_with_path(fill, like)
