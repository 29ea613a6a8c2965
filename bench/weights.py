"""Seeded weights of a dense pre-norm language model, made on the device.

The benchmark makes the weights itself, so that the reference takes nothing
that the program made.  They are named as the reference names them (see
``reference/dense_lm.py``); each driver maps them into the program's own
storage layout inside one jitted call.

    embed, head     [V, D]        normal(0, 0.02)
    final_scale     [D]           1 + normal(0, 0.05)
    final_bias      [D]           normal(0, 0.02)
    layers/ln{1,2}_scale, ln{1,2}_bias   [L, D]   as the final norm
    layers/wq, wk, wv   [L, D, H*hd]      normal(0, 1/sqrt(D))
    layers/wo           [L, H*hd, D]      normal(0, 1/sqrt(H*hd))
    layers/w_up         [L, D, F]         normal(0, 1/sqrt(D))
    layers/w_down       [L, F, D]         normal(0, 1/sqrt(F))

All float32, the master precision the configurations state.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("ln1_scale", "ln1_bias", "wq", "wk", "wv", "wo",
                "ln2_scale", "ln2_bias", "w_up", "w_down")
OUTER_LEAVES = ("embed", "head", "final_scale", "final_bias")


def key_of(seed: int, stream: int):
    """A JAX key for one use of ``seed``; any whole number >= 0 is a seed."""
    v = np.random.SeedSequence([seed, stream]).generate_state(1)[0]
    return jax.random.PRNGKey(np.uint32(v))


def shapes(cfg: dict) -> dict:
    L, D, F, V = cfg["num_layers"], cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    hq = cfg["num_heads"] * cfg["head_dim"]
    hkv = cfg["num_kv_heads"] * cfg["head_dim"]
    layers = {"ln1_scale": (L, D), "ln1_bias": (L, D),
              "wq": (L, D, hq), "wk": (L, D, hkv), "wv": (L, D, hkv),
              "wo": (L, hq, D), "ln2_scale": (L, D), "ln2_bias": (L, D),
              "w_up": (L, D, F), "w_down": (L, F, D)}
    return {"embed": (V, D), "head": (V, D), "final_scale": (D,),
            "final_bias": (D,), "layers": layers}


def _leaf(key, name: str, shape: tuple) -> jnp.ndarray:
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_scale"):
        return 1.0 + 0.05 * z
    if name.endswith("_bias") or name in ("embed", "head"):
        return 0.02 * z
    return z / math.sqrt(shape[-2])          # fan-in of one layer's matrix


def make(cfg: dict, key) -> dict:
    """All weights from one key (traceable: call it inside ``jax.jit``)."""
    sh = shapes(cfg)
    names = OUTER_LEAVES + LAYER_LEAVES
    keys = dict(zip(names, jax.random.split(key, len(names))))
    out = {n: _leaf(keys[n], n, sh[n]) for n in OUTER_LEAVES}
    out["layers"] = {n: _leaf(keys[n], n, sh["layers"][n])
                     for n in LAYER_LEAVES}
    return out


def leaf_norms(tree: dict) -> dict:
    """{leaf name: L2 norm} of a weight-shaped tree (traceable)."""
    out = {n: jnp.sqrt(jnp.sum(jnp.square(tree[n]))) for n in OUTER_LEAVES}
    for n in LAYER_LEAVES:
        x = tree["layers"][n]
        out[n] = jnp.sqrt(jnp.sum(jnp.square(x),
                                  axis=tuple(range(1, x.ndim))))
    return out


def flatten_norms(norms: dict) -> dict:
    """Per-layer norm vectors -> {"wq[3]": float, ...} on the host."""
    out = {}
    for n, v in norms.items():
        v = np.asarray(v, np.float64)
        if n in OUTER_LEAVES:
            out[n] = float(v)
        else:
            out.update({f"{n}[{i}]": float(x) for i, x in enumerate(v)})
    return out
