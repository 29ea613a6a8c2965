"""Mixture-of-experts feed-forward with expert parallelism over `model`.

TPU-native dispatch: GShard-style capacity-bounded one-hot dispatch/combine
einsums (dense dispatch).  Under tensor parallelism the token activations are
already replicated across the `model` axis, so expert parallelism needs no
all_to_all in the baseline: each shard evaluates its local experts on the
tokens routed to them and the combine is folded into the block's existing
output ``psum``.  An all_to_all token-sharded variant is provided as a
beyond-paper optimisation for the data axis (see EXPERIMENTS §Perf).

Router aux (load-balance) loss follows Switch/GShard: E * sum_e f_e * p_e.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.common import AxisCtx, ModelConfig, activation, dense_init
from repro.models.mlp import apply_mlp, init_mlp

PyTree = Any


def init_moe(cfg: ModelConfig, key) -> PyTree:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    dt = jnp.dtype(cfg.param_dtype)
    kr, kg, ku, kd, kdense = jax.random.split(key, 5)
    p = {
        "router": dense_init(kr, (d, e), jnp.float32, scale=0.02),
        "w_up": dense_init(ku, (e, d, f), dt, scale=1.0 / math.sqrt(d)),
        "w_down": dense_init(kd, (e, f, d), dt, scale=1.0 / math.sqrt(f)),
    }
    if cfg.glu:
        p["w_gate"] = dense_init(kg, (e, d, f), dt, scale=1.0 / math.sqrt(d))
    if cfg.moe_dense_residual:
        p["dense"] = init_mlp(cfg, kdense, d_ff=cfg.moe_dense_ff or cfg.d_ff)
    return p


def expert_capacity(cfg: ModelConfig, num_tokens: int, *, factor: float = 1.25) -> int:
    cap = int(math.ceil(num_tokens * cfg.experts_per_token * factor / cfg.num_experts))
    return max(cap, 4)


def _router(cfg: ModelConfig, p: PyTree, x: jnp.ndarray):
    """x: [T, D] -> (combine weights [T, k], expert ids [T, k], aux loss)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = lax.top_k(probs, cfg.experts_per_token)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # Switch-style load balance aux: E * sum_e (fraction routed) * (mean prob)
    e = cfg.num_experts
    f_e = jnp.mean(jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32), axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e)
    return weights, ids, aux


def _slots(cfg: ModelConfig, ids: jnp.ndarray, cap: int):
    """Capacity slot of each (token, k) assignment within its expert."""
    T, k = ids.shape
    e = cfg.num_experts
    onehot = jax.nn.one_hot(ids, e, dtype=jnp.int32)                 # [T, k, E]
    pos = jnp.cumsum(onehot.reshape(T * k, e), axis=0)
    pos = pos.reshape(T, k, e) - 1
    slot = jnp.sum(pos * onehot, axis=-1)                            # [T, k]
    return slot, slot < cap


def _expert_ffn(cfg: ModelConfig, p: PyTree, xe: jnp.ndarray) -> jnp.ndarray:
    """xe: [E_l, C, D] -> [E_l, C, D] (hidden dim possibly model-sharded)."""
    dt = xe.dtype
    act = activation(cfg.hidden_act)
    up = jnp.einsum("ecd,edf->ecf", xe, p["w_up"].astype(dt))
    if cfg.glu:
        gate = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(dt))
        h = act(gate) * up
    else:
        h = act(up)
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt))


def _apply_moe_a2a(cfg: ModelConfig, p: PyTree, xt: jnp.ndarray, axis: AxisCtx,
                   weights, ids, *, capacity_factor: float,
                   chunk: int = 8192) -> jnp.ndarray:
    """Expert-parallel dispatch over ``axis.expert`` via all_to_all.

    Serving layout: experts sharded over `data` (tokens are data-local), the
    expert hidden dim over `model`.  Dispatch/combine use gather/scatter
    (linear cost) instead of one-hot einsums, processed in token chunks so
    the in-flight [E, cap, D] buffers stay small.
    """
    T, D = xt.shape
    dt = xt.dtype
    E = cfg.num_experts
    k = cfg.experts_per_token
    e_local = p["w_up"].shape[0]
    n_sh = E // e_local
    chunk = min(chunk, T)
    pad = (-T) % chunk
    if pad:
        xt = jnp.concatenate([xt, jnp.zeros((pad, D), dt)])
        weights = jnp.concatenate([weights, jnp.zeros((pad, k), weights.dtype)])
        ids = jnp.concatenate([ids, jnp.zeros((pad, k), ids.dtype)])
    n_chunks = (T + pad) // chunk
    cap = expert_capacity(cfg, chunk, factor=capacity_factor)

    def one_chunk(_, inp):
        xc, wc, ic = inp                                   # [C,D], [C,k], [C,k]
        slot, keep = _slots(cfg, ic, cap)
        # scatter token row index into [E, cap] (sentinel C -> zero row)
        C = xc.shape[0]
        xz = jnp.concatenate([xc, jnp.zeros((1, D), dt)])  # [C+1, D]
        tok = jnp.full((E, cap + 1), C, jnp.int32)
        e_idx = ic.reshape(-1)
        s_idx = jnp.where(keep.reshape(-1), slot.reshape(-1), cap)
        t_idx = jnp.broadcast_to(jnp.arange(C)[:, None], (C, k)).reshape(-1)
        tok = tok.at[e_idx, s_idx].set(t_idx, mode="drop")
        tok = tok[:, :cap]
        xe = xz[tok]                                       # [E, cap, D]
        xe = lax.all_to_all(xe, axis.expert, split_axis=0, concat_axis=1,
                            tiled=True)                    # [E_l, n*cap, D]
        ye = _expert_ffn(cfg, p, xe)
        ye = lax.all_to_all(ye, axis.expert, split_axis=1, concat_axis=0,
                            tiled=True)                    # [E, cap, D]
        # combine: gather each assignment's output back
        yk = ye[ic, jnp.clip(slot, 0, cap - 1)]            # [C, k, D]
        yk = yk * (wc * keep.astype(wc.dtype))[..., None].astype(dt)
        return None, jnp.sum(yk, axis=1)

    xs = (xt.reshape(n_chunks, chunk, D),
          weights.reshape(n_chunks, chunk, k),
          ids.reshape(n_chunks, chunk, k))
    _, yt = lax.scan(one_chunk, None, xs)
    yt = yt.reshape(-1, D)
    return yt[:T] if pad else yt


def apply_moe(cfg: ModelConfig, p: PyTree, x: jnp.ndarray, axis: AxisCtx,
              *, capacity_factor: float = 1.25) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, D] -> ([B, S, D], aux scalar).

    Training layout: expert dim sharded over `model` (tokens replicated
    there under TP), capacity-bounded one-hot dispatch, combine completed by
    the block's output psum.  Serving layout (``axis.expert`` set): experts
    over `data` with all_to_all dispatch (see _apply_moe_a2a).
    """
    B, S, D = x.shape
    T = B * S
    dt = x.dtype
    xt = x.reshape(T, D)
    weights, ids, aux = _router(cfg, p, xt)

    e_total = cfg.num_experts
    e_local = p["w_up"].shape[0]
    if (axis.expert is not None and axis.expert != axis.model
            and e_total > e_local):
        yt = _apply_moe_a2a(cfg, p, xt, axis, weights, ids,
                            capacity_factor=capacity_factor)
        y = yt.reshape(B, S, D)
        if cfg.moe_dense_residual:
            y = y + apply_mlp(cfg, p["dense"], x, AxisCtx())
        return axis.psum_model(y), aux

    cap = expert_capacity(cfg, T, factor=capacity_factor)
    slot, keep = _slots(cfg, ids, cap)

    if axis.model and e_total > e_local:
        e_lo = lax.axis_index(axis.model) * e_local
    else:
        e_lo = 0

    local_eid = ids - e_lo
    local = (local_eid >= 0) & (local_eid < e_local) & keep
    # dispatch one-hots: [T, k, E_local] x [T, k, cap] -> [T, E_local, cap]
    oh_e = jax.nn.one_hot(local_eid, e_local, dtype=dt) * local[..., None].astype(dt)
    oh_c = jax.nn.one_hot(slot, cap, dtype=dt)
    # a token never holds two slots of the same expert, so summing over k is exact
    disp = jnp.einsum("tke,tkc->tec", oh_e, oh_c)                    # [T, E_l, cap]
    comb = jnp.einsum("tke,tkc,tk->tec", oh_e, oh_c,
                      weights.astype(dt))                            # weighted combine
    xe = jnp.einsum("td,tec->ecd", xt, disp)                         # [E_l, cap, D]
    ye = _expert_ffn(cfg, p, xe)
    yt = jnp.einsum("ecd,tec->td", ye, comb)
    y = yt.reshape(B, S, D)

    if cfg.moe_dense_residual:
        # arctic: dense FFN runs in parallel; its hidden dim is sharded over
        # `model` too, so the partial sums fold into the same psum.
        y = y + apply_mlp(cfg, p["dense"], x, AxisCtx())
    return axis.psum_model(y), aux
