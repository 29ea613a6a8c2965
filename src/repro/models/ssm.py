"""Linear-recurrence (SSM) blocks: Mamba-2 (SSD) and RWKV-6 (Finch).

Both are expressed through one chunked linear-attention engine:

    S_t = diag(d_t) . S_{t-1} + k_t v_t^T          (S in R^{dk x dv} per head)
    o_t = q_t . S_t                                (inclusive, Mamba-2)
    o_t = q_t . (S_{t-1} + diag(u) k_t v_t^T)      (bonus form, RWKV-6)

with per-step decay d_t either a vector over dk (RWKV-6, data-dependent) or a
scalar per head (Mamba-2).  The sequence is processed in chunks: a
``lax.scan`` carries the inter-chunk state while the intra-chunk part is an
attention-like einsum with pairwise decay ratios computed in log space —
TPU-friendly (MXU einsums instead of a length-S sequential scan) and
numerically stable since all exponents are <= 0.

Tensor parallelism: recurrence heads are sharded over `model`; only the
output projections psum.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.compat import vma_of
from repro.models.common import (AxisCtx, ModelConfig, dense_init,
                                 pvary_missing, rms_norm)

PyTree = Any


# ---------------------------------------------------------------------------
# Chunked linear-attention engine
# ---------------------------------------------------------------------------
def linear_attention_chunked(q, k, v, log_decay, state0, *, chunk: int = 64,
                             bonus: jnp.ndarray | None = None):
    """q,k: [B,S,H,dk]; v: [B,S,H,dv]; log_decay: [B,S,H,dk] or [B,S,H,1].

    state0: [B,H,dk,dv].  bonus: [H,dk] (RWKV u) -> the output reads S_{t-1}
    plus the bonus term for the current token; bonus=None -> inclusive q_t.S_t.
    Returns (o [B,S,H,dv], state_end [B,H,dk,dv]).
    """
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, S)
    if S % chunk:
        pad = chunk - S % chunk
        padded = [jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                  for a in (q, k, v, log_decay)]
        o, st = linear_attention_chunked(*padded, state0, chunk=chunk, bonus=bonus)
        return o[:, :S], st
    n = S // chunk
    f32 = jnp.float32
    scalar_decay = log_decay.shape[-1] == 1

    def split(x):  # [B,S,H,d] -> [n,B,chunk,H,d]
        return jnp.moveaxis(x.astype(f32).reshape(B, n, chunk, H, x.shape[-1]), 1, 0)

    qc, kc, vc, ldc = split(q), split(k), split(v), split(log_decay)
    tri = jnp.tril(jnp.ones((chunk, chunk), f32), k=(-1 if bonus is not None else 0))

    def step(S0, inputs):
        qi, ki, vi, ldi = inputs                                   # [B,chunk,H,d]
        lc = jnp.cumsum(ldi, axis=1)                               # inclusive cumsum
        lc_tot = lc[:, -1:]                                        # [B,1,H,dk']
        # output contribution of the carried state
        qs = qi * jnp.exp(lc if bonus is None else lc - ldi)
        o = jnp.einsum("bthk,bhkv->bthv", qs, S0)
        # intra-chunk pairwise term: A[t,s] = sum_dk q_t k_s exp(lc_t - lc_s)
        # (bonus form reads S_{t-1}: the t-th decay is excluded via lc - ldi)
        lct = lc if bonus is None else lc - ldi
        ld_pair = lct[:, :, None] - lc[:, None, :, :]              # [B,t,s,H,dk']
        mask = tri[None, :, :, None, None] > 0
        dec = jnp.exp(jnp.where(mask, ld_pair, -jnp.inf))
        if scalar_decay:
            A = jnp.einsum("bthk,bshk->bhts", qi, ki) * jnp.moveaxis(dec[..., 0], 3, 1)
        else:
            A = jnp.einsum("bthk,bshk,btshk->bhts", qi, ki, dec)
        o = o + jnp.einsum("bhts,bshv->bthv", A, vi)
        if bonus is not None:
            ob = jnp.einsum("bthk,hk,bthk->bth", qi, bonus.astype(f32), ki)
            o = o + ob[..., None] * vi
        # state update: S1 = exp(lc_tot) * S0 + sum_s exp(lc_tot - lc_s) k_s v_s
        kdec = ki * jnp.exp(lc_tot - lc)
        S1 = S0 * jnp.exp(lc_tot)[:, 0, :, :, None]
        S1 = S1 + jnp.einsum("bshk,bshv->bhkv", kdec, vi)
        return S1, o

    vma = set()
    for a in (qc, kc, vc, ldc):
        vma |= set(vma_of(a))
    state_end, o = lax.scan(step, pvary_missing(state0.astype(f32), tuple(vma)),
                            (qc, kc, vc, ldc))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H, dv)
    return o.astype(v.dtype), state_end


def linear_attention_step(q, k, v, log_decay, state, *, bonus=None):
    """Single-token decode step.  q,k:[B,H,dk]; v:[B,H,dv]; state:[B,H,dk,dv]."""
    f32 = jnp.float32
    out_dtype = v.dtype
    q, k, v, ld = (a.astype(f32) for a in (q, k, v, log_decay))
    kv = jnp.einsum("bhk,bhv->bhkv", k, v)
    if bonus is None:
        state = state * jnp.exp(ld)[..., None] + kv
        o = jnp.einsum("bhk,bhkv->bhv", q, state)
    else:
        o = jnp.einsum("bhk,bhkv->bhv", q, state + bonus.astype(f32)[None, :, :, None] * kv)
        state = state * jnp.exp(ld)[..., None] + kv
    return o.astype(out_dtype), state


# ---------------------------------------------------------------------------
# Mamba-2 (SSD) block — returns a residual delta (pre-norm handled by caller)
# ---------------------------------------------------------------------------
def init_mamba(cfg: ModelConfig, key) -> PyTree:
    d, f, st = cfg.d_model, cfg.d_ff, cfg.ssm_state
    heads = f // cfg.ssm_head_dim
    dt = jnp.dtype(cfg.param_dtype)
    kx, kz, kb, kc, kdt, ko = jax.random.split(key, 6)
    return {
        "w_x": dense_init(kx, (d, f), dt),
        "w_z": dense_init(kz, (d, f), dt),
        "w_B": dense_init(kb, (d, st), dt),        # shared across heads (ngroups=1)
        "w_C": dense_init(kc, (d, st), dt),
        "w_dt": dense_init(kdt, (d, heads), dt),
        "dt_bias": jnp.zeros((heads,), dt),
        "A_log": jnp.zeros((heads,), dt),           # A = -exp(A_log)
        "D_skip": jnp.ones((heads,), dt),
        "w_out": dense_init(ko, (f, d), dt),
    }


def mamba_state_shape(cfg: ModelConfig, batch: int, tp: int = 1) -> tuple[int, ...]:
    heads = cfg.d_ff // cfg.ssm_head_dim // tp
    return (batch, heads, cfg.ssm_state, cfg.ssm_head_dim)


def apply_mamba(cfg: ModelConfig, p: PyTree, x: jnp.ndarray, axis: AxisCtx, *,
                state: jnp.ndarray | None = None, decode: bool = False,
                chunk: int = 64):
    """x: [B,S,D] -> (delta [B,S,D], state_end [B,H_l,dk,hd])."""
    B, S, _ = x.shape
    hd = cfg.ssm_head_dim
    dt_ = x.dtype
    xs = jnp.einsum("bsd,df->bsf", x, p["w_x"].astype(dt_))
    z = jnp.einsum("bsd,df->bsf", x, p["w_z"].astype(dt_))
    Bm = jnp.einsum("bsd,dk->bsk", x, p["w_B"].astype(dt_))
    Cm = jnp.einsum("bsd,dk->bsk", x, p["w_C"].astype(dt_))
    heads = xs.shape[-1] // hd
    dt_t = jax.nn.softplus(
        jnp.einsum("bsd,dh->bsh", x, p["w_dt"].astype(dt_)).astype(jnp.float32)
        + p["dt_bias"].astype(jnp.float32))                          # [B,S,H]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    log_decay = (A * dt_t)[..., None]                                # [B,S,H,1]
    v = (xs.reshape(B, S, heads, hd).astype(jnp.float32)
         * dt_t[..., None]).astype(dt_)                              # dt-scaled input
    k = jnp.broadcast_to(Bm[:, :, None, :], (B, S, heads, Bm.shape[-1]))
    q = jnp.broadcast_to(Cm[:, :, None, :], (B, S, heads, Cm.shape[-1]))
    if state is None:
        state = jnp.zeros((B, heads, cfg.ssm_state, hd), jnp.float32)
    if decode:
        o, state = linear_attention_step(q[:, 0], k[:, 0], v[:, 0],
                                         log_decay[:, 0], state)
        o = o[:, None]
    else:
        o, state = linear_attention_chunked(q, k, v, log_decay, state, chunk=chunk)
    o = o + xs.reshape(B, S, heads, hd) * p["D_skip"].astype(dt_)[None, None, :, None]
    o = o.reshape(B, S, -1) * jax.nn.silu(z.astype(jnp.float32)).astype(dt_)
    out = jnp.einsum("bsf,fd->bsd", o, p["w_out"].astype(dt_))
    return axis.psum_model(out), state


# ---------------------------------------------------------------------------
# RWKV-6 (Finch) block — self-contained (own norms + residuals)
# ---------------------------------------------------------------------------
def init_rwkv(cfg: ModelConfig, key) -> PyTree:
    d, f = cfg.d_model, cfg.d_ff
    hd = cfg.ssm_head_dim
    heads = cfg.rwkv_heads                  # may be TP-padded (> d_model/hd)
    inner = cfg.rwkv_inner
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 10)
    return {
        "ln1": jnp.ones((d,), dt),
        "ln2": jnp.ones((d,), dt),
        "w_r": dense_init(ks[0], (d, inner), dt),
        "w_k": dense_init(ks[1], (d, inner), dt),
        "w_v": dense_init(ks[2], (d, inner), dt),
        "w_g": dense_init(ks[3], (d, inner), dt),
        "w_w": dense_init(ks[4], (d, inner), dt, scale=0.01),  # data-dep. decay
        "w_bias": jnp.full((inner,), -2.0, dt),
        "u_bonus": dense_init(ks[5], (heads, hd), dt, scale=0.5),
        "mix": jnp.full((5, d), 0.5, dt),                   # token-shift mixes (r,k,v,g,w)
        "w_time_out": dense_init(ks[6], (inner, d), dt),
        "cm_mix": jnp.full((2, d), 0.5, dt),
        "cm_k": dense_init(ks[7], (d, f), dt),
        "cm_v": dense_init(ks[8], (f, d), dt),
        "cm_r": dense_init(ks[9], (d, d), dt),
    }


def rwkv_state_shape(cfg: ModelConfig, batch: int, tp: int = 1) -> dict:
    hd = cfg.ssm_head_dim
    heads = cfg.rwkv_heads // tp
    return {
        "S": (batch, heads, hd, hd),
        "x_tm": (batch, cfg.d_model),
        "x_cm": (batch, cfg.d_model),
    }


def _token_shift(x: jnp.ndarray, prev: jnp.ndarray | None) -> jnp.ndarray:
    """x: [B,S,D] -> x shifted right by one (``prev`` fills position 0)."""
    first = jnp.zeros_like(x[:, :1]) if prev is None else prev[:, None].astype(x.dtype)
    return jnp.concatenate([first, x[:, :-1]], axis=1)


def apply_rwkv(cfg: ModelConfig, p: PyTree, x: jnp.ndarray, axis: AxisCtx, *,
               state: PyTree | None = None, decode: bool = False,
               chunk: int = 64):
    """Full RWKV layer.  x: [B,S,D] -> (new x [B,S,D], state).

    state: {"S": [B,H_l,hd,hd], "x_tm": [B,D], "x_cm": [B,D]}.
    """
    B, S, D = x.shape
    hd = cfg.ssm_head_dim
    heads_l = p["w_r"].shape[-1] // hd  # local heads under tensor parallelism
    dt_ = x.dtype
    have_state = state is not None
    if not have_state:
        state = {
            "S": jnp.zeros((B, heads_l, hd, hd), jnp.float32),
            "x_tm": jnp.zeros((B, D), dt_),
            "x_cm": jnp.zeros((B, D), dt_),
        }
    # ---- time mix ----------------------------------------------------------
    a = rms_norm(x, p["ln1"])
    aprev = _token_shift(a, state["x_tm"] if (decode or have_state) else None)
    mix = p["mix"].astype(dt_)
    xr, xk, xv, xg, xw = (a + mix[i] * (aprev - a) for i in range(5))
    r = jnp.einsum("bsd,de->bse", xr, p["w_r"].astype(dt_)).reshape(B, S, heads_l, hd)
    k = jnp.einsum("bsd,de->bse", xk, p["w_k"].astype(dt_)).reshape(B, S, heads_l, hd)
    v = jnp.einsum("bsd,de->bse", xv, p["w_v"].astype(dt_)).reshape(B, S, heads_l, hd)
    g = jnp.einsum("bsd,de->bse", xg, p["w_g"].astype(dt_))
    wraw = jnp.einsum("bsd,de->bse", xw, p["w_w"].astype(dt_)).astype(jnp.float32)
    log_decay = -jnp.exp(wraw + p["w_bias"].astype(jnp.float32))     # < 0
    log_decay = log_decay.reshape(B, S, heads_l, hd)
    if decode:
        o, S1 = linear_attention_step(r[:, 0], k[:, 0], v[:, 0], log_decay[:, 0],
                                      state["S"], bonus=p["u_bonus"])
        o = o[:, None]
    else:
        o, S1 = linear_attention_chunked(r, k, v, log_decay, state["S"],
                                         chunk=chunk, bonus=p["u_bonus"])
    # per-head groupnorm
    o32 = o.astype(jnp.float32)
    mu = jnp.mean(o32, axis=-1, keepdims=True)
    var = jnp.var(o32, axis=-1, keepdims=True)
    o = ((o32 - mu) * lax.rsqrt(var + 1e-5)).astype(dt_)
    o = o.reshape(B, S, -1) * jax.nn.silu(g.astype(jnp.float32)).astype(dt_)
    y = jnp.einsum("bsd,de->bse", o, p["w_time_out"].astype(dt_))
    x = x + axis.psum_model(y)
    # ---- channel mix ---------------------------------------------------------
    b = rms_norm(x, p["ln2"])
    bprev = _token_shift(b, state["x_cm"] if (decode or have_state) else None)
    cmix = p["cm_mix"].astype(dt_)
    xk2 = b + cmix[0] * (bprev - b)
    xr2 = b + cmix[1] * (bprev - b)
    kk = jnp.square(jax.nn.relu(jnp.einsum("bsd,df->bsf", xk2, p["cm_k"].astype(dt_))))
    vv = axis.psum_model(jnp.einsum("bsf,fd->bsd", kk, p["cm_v"].astype(dt_)))
    rr = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", xr2, p["cm_r"].astype(dt_))
                        .astype(jnp.float32)).astype(dt_)
    x = x + rr * vv
    new_state = {"S": S1, "x_tm": a[:, -1], "x_cm": b[:, -1]}
    return x, new_state
