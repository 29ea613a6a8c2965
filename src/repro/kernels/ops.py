"""Jitted, differentiable public wrappers for the Pallas kernels.

On CPU (this container) the kernels execute in ``interpret=True`` mode —
the kernel body runs as traced JAX ops, validating the logic the TPU target
will compile.  On a real TPU backend ``interpret`` defaults off.

The custom-VJP contract: ``flash_attention`` and ``rmsnorm`` are
``jax.custom_vjp`` primitives whose forward saves only O(S·D) residuals
(q/k/v/out + per-row lse; x + scale) and whose backward runs the fused
Pallas backward kernels — ``jax.grad`` through them never materialises an
O(S²) logits tensor or an unfused norm chain.  Padding happens *outside*
the custom_vjp (plain concatenate/slice, transposed by JAX itself), so the
kernels always see block-aligned shapes plus the true ``kv_len``/row count
for in-kernel masking.  ``fused_adamw`` has no VJP (nothing differentiates
through the optimizer); it is the one-pass chunk update dispatched from
optim/adam.py.

Everything here is toggleable: model code consults ``ModelConfig.kernels``
(default on) and falls back to the pure-jnp reference paths when it is off —
the debugging escape hatch (see README §Pallas kernels).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import adamw as _aw
from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _pa
from repro.kernels import rmsnorm as _rn


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Flash attention (differentiable)
# ---------------------------------------------------------------------------
# spec = (causal, window, softcap, kv_len, block_q, block_k, interpret)
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(spec, q, k, v):
    causal, window, softcap, kv_len, bq, bk, interpret = spec
    out, _ = _fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                     softcap=softcap, kv_len=kv_len,
                                     block_q=bq, block_k=bk,
                                     interpret=interpret)
    return out


def _flash_fwd(spec, q, k, v):
    causal, window, softcap, kv_len, bq, bk, interpret = spec
    out, lse = _fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                       softcap=softcap, kv_len=kv_len,
                                       block_q=bq, block_k=bk,
                                       interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(spec, res, g):
    causal, window, softcap, kv_len, bq, bk, interpret = spec
    q, k, v, out, lse = res
    return _fa.flash_attention_bwd(q, k, v, out, lse, g, causal=causal,
                                   window=window, softcap=softcap,
                                   kv_len=kv_len, block_q=bq, block_k=bk,
                                   interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int | None = None,
                    block_k: int | None = None, interpret: bool | None = None):
    """q: [B, S, Hq, D]; k, v: [B, S, Hkv, D] -> [B, S, Hq, D].

    Differentiable (custom VJP; flash-style recomputing backward).  Block
    sizes left None are ``tile_plan``'s for the shape.  Pads S up to a
    common block multiple; padded key rows are masked in-kernel via the true
    ``kv_len`` (not just causality), padded query rows are dropped.
    """
    if interpret is None:
        interpret = _interpret_default()
    B, S, Hq, D = q.shape
    bq, bk = _fa.block_sizes(S, D, q.dtype, Hq // k.shape[2], block_q,
                             block_k)
    mult = bq * bk // math.gcd(bq, bk)     # lcm: must divide both block sizes
    pad = (-S) % mult
    if pad:
        zq = jnp.zeros((B, pad, Hq, D), q.dtype)
        zk = jnp.zeros((B, pad, k.shape[2], D), k.dtype)
        q = jnp.concatenate([q, zq], axis=1)
        k = jnp.concatenate([k, zk], axis=1)
        v = jnp.concatenate([v, zk], axis=1)
    spec = (bool(causal), int(window), float(softcap), S, bq, bk,
            bool(interpret))
    out = _flash(spec, q, k, v)
    return out[:, :S] if pad else out


# ---------------------------------------------------------------------------
# RMSNorm (differentiable)
# ---------------------------------------------------------------------------
# spec = (eps, plus_one, interpret)
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rmsnorm(spec, x, scale):
    eps, plus_one, interpret = spec
    return _rn.rmsnorm(x, scale, eps=eps, plus_one=plus_one,
                       interpret=interpret)


def _rmsnorm_fwd(spec, x, scale):
    return _rmsnorm(spec, x, scale), (x, scale)


def _rmsnorm_bwd(spec, res, g):
    eps, plus_one, interpret = spec
    x, scale = res
    dx, dscale = _rn.rmsnorm_bwd(x, scale, g, eps=eps, plus_one=plus_one,
                                 interpret=interpret)
    return dx, dscale.astype(scale.dtype)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def rmsnorm(x, scale, *, eps: float = 1e-6, plus_one: bool = False,
            interpret: bool | None = None):
    """Fused RMSNorm with a fused single-pass VJP.  ``plus_one`` is the
    ``rmsnorm_p1`` (gemma ``1 + scale``) variant."""
    if interpret is None:
        interpret = _interpret_default()
    return _rmsnorm((float(eps), bool(plus_one), bool(interpret)), x, scale)


# ---------------------------------------------------------------------------
# Paged attention decode (no VJP — inference territory)
# ---------------------------------------------------------------------------
def paged_attention(q, k_pool, v_pool, block_tables, context_lens, *,
                    window: int = 0, softcap: float = 0.0,
                    interpret: bool | None = None):
    """Single-token decode attention over a paged KV cache.

    q: [R, Hq, D]; pools: [N, Hkv, block_size, D]; block_tables: [R,
    max_blocks] pool indices; context_lens: [R] live tokens per request.
    Causal by construction (only the blocks covering the live context are
    gathered); ``window``/``softcap`` as in ``flash_attention``.  Rows with
    ``context_lens == 0`` return zeros (idle serving slots).
    """
    if interpret is None:
        interpret = _interpret_default()
    return _pa.paged_attention_decode(q, k_pool, v_pool, block_tables,
                                      context_lens, window=window,
                                      softcap=softcap, interpret=interpret)


# ---------------------------------------------------------------------------
# Fused AdamW chunk update (no VJP — optimizer territory)
# ---------------------------------------------------------------------------
def fused_adamw(p, m, v, g, scalars, *, b1: float, b2: float, eps: float,
                wd: float, interpret: bool | None = None):
    """One-pass AdamW on a state leaf.  ``scalars`` fp32 [4] = (lr, 1-b1^t,
    1-b2^t, grad scale).  Returns (p', mu', nu') computing the exact float
    ops of optim/adam.py's tree-map update (equal to within FMA
    contraction)."""
    if interpret is None:
        interpret = _interpret_default()
    return _aw.adamw_update(p, m, v, g, scalars, b1=b1, b2=b2, eps=eps,
                            wd=wd, interpret=interpret)
