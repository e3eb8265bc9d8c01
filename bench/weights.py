"""Scoring weights made from the seed, on the device, in one jitted call.

The tree has the layout the program's ``core.model.perona_init`` gives
(a dict of ``{"w", "b"}`` linear layers, MLPs as lists), in float32,
the type the model is served in. Weights are N(0, 1/fan_in) and biases
N(0, 0.1^2): random, so that a fault in any term moves the scores.
The decoder is included because the program's forward expects it.
"""

from __future__ import annotations

from typing import Dict, List, Tuple


def layout(feature_dim: int, edge_dim: int, model: dict
           ) -> Dict[str, object]:
    """Name -> (fan_in, fan_out), or a list of them for an MLP."""
    f, a, k, h, t = (feature_dim, edge_dim, model["code_dim"],
                     model["hidden"], model["n_types"])
    out: Dict[str, object] = {
        "enc": [(f, h), (h, k)], "dec": [(k, h), (h, f)],
        "wq": (k, k), "wk": (k, k), "wv": (k, k),
        "we_k": (a, k), "we_v": (a, k),
        "tag": [(k, k)] * (model["tag_hops"] + 1),
        "root": (k, k), "out": (k, k),
        "f1": [(k, h), (h, 1)], "cls": (k, t)}
    return out


def _flat(lay) -> List[Tuple[str, int, Tuple[int, int]]]:
    items = []
    for name in sorted(lay):
        v = lay[name]
        if isinstance(v, list):
            items.extend((name, i, s) for i, s in enumerate(v))
        else:
            items.append((name, -1, v))
    return items


def make_params(seed: int, feature_dim: int, edge_dim: int, model: dict):
    """The weight tree, made on the default device from ``seed``."""
    import jax
    import jax.numpy as jnp

    items = _flat(layout(feature_dim, edge_dim, model))

    def build(key):
        keys = jax.random.split(key, 2 * len(items))
        out: Dict[str, object] = {}
        for j, (name, i, (fi, fo)) in enumerate(items):
            w = jax.random.normal(keys[2 * j], (fi, fo), jnp.float32)
            b = jax.random.normal(keys[2 * j + 1], (fo,), jnp.float32)
            layer = {"w": w / jnp.sqrt(jnp.float32(fi)), "b": 0.1 * b}
            if i < 0:
                out[name] = layer
            else:
                out.setdefault(name, []).append(layer)
        return out

    import numpy as np

    def from_seed(lo, hi):
        return build(jax.random.fold_in(jax.random.key(lo), hi))

    return jax.jit(from_seed)(np.uint32(seed % 2**32),
                              np.uint32(seed // 2**32 % 2**32))
