"""Dense feed-forward blocks (SwiGLU / GeGLU / plain), tensor-parallel aware.

Column-parallel up/gate projections (hidden dim sharded over `model`) followed
by a row-parallel down projection and a single ``psum`` over `model`.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models.common import AxisCtx, ModelConfig, activation, dense_init

PyTree = Any


def init_mlp(cfg: ModelConfig, key, *, d_ff: int | None = None) -> PyTree:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    kg, ku, kd = jax.random.split(key, 3)
    p = {
        "w_up": dense_init(ku, (d, f), dt),
        "w_down": dense_init(kd, (f, d), dt),
    }
    if cfg.glu:
        p["w_gate"] = dense_init(kg, (d, f), dt)
    return p


def apply_mlp(cfg: ModelConfig, p: PyTree, x: jnp.ndarray, axis: AxisCtx) -> jnp.ndarray:
    dt = x.dtype
    act = activation(cfg.hidden_act)
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
    if cfg.glu:
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
        h = act(gate) * up
    else:
        h = act(up)
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt))
    return axis.psum_model(out)
