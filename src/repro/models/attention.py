"""Grouped-query attention: training (full-sequence causal) and cached decode.

Tensor-parallel mapping (Megatron-style, adapted to the TPU `model` mesh axis):
  * query heads are sharded over `model`; if ``num_heads % tp != 0`` the config
    is head-padded beforehand (see ``ModelConfig.padded_for_tp``);
  * KV heads are sharded when ``num_kv_heads % tp == 0``, otherwise the KV
    projections are replicated and each shard slices the single KV-head group
    its local query heads attend to (standard GQA replication treatment);
  * the output projection is a row-parallel matmul followed by a ``psum`` over
    `model` — the only tensor-parallel collective of the block.

Long-context decode (``long_500k``) additionally supports a sequence-parallel
KV cache: the cache is sharded over a `seq` axis and the softmax is made exact
with a flash-style three-term (max, sum, weighted-value) ``psum`` reduction.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.common import AxisCtx, ModelConfig, apply_rope, dense_init, softcap

PyTree = Any
NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def init_attention(cfg: ModelConfig, key) -> PyTree:
    d, hd = cfg.d_model, cfg.head_dim
    dt = jnp.dtype(cfg.param_dtype)
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, (d, cfg.num_heads * hd), dt),
        "wk": dense_init(kk, (d, cfg.num_kv_heads * hd), dt),
        "wv": dense_init(kv, (d, cfg.num_kv_heads * hd), dt),
        "wo": dense_init(ko, (cfg.num_heads * hd, d), dt),
    }


def _local_counts(cfg: ModelConfig, axis: AxisCtx) -> tuple[int, int, bool]:
    """(local q heads, local kv heads, kv_replicated)."""
    tp = axis.tp
    if tp == 1:
        return cfg.num_heads, cfg.num_kv_heads, False
    assert cfg.num_heads % tp == 0, (cfg.name, cfg.num_heads, tp)
    hq_l = cfg.num_heads // tp
    if cfg.num_kv_heads % tp == 0:
        return hq_l, cfg.num_kv_heads // tp, False
    assert tp % cfg.num_kv_heads == 0, (cfg.name, cfg.num_kv_heads, tp)
    return hq_l, 1, True


def _project_qkv(cfg: ModelConfig, p: PyTree, x: jnp.ndarray, axis: AxisCtx):
    """Returns q:[B,S,Hq_l,hd], k/v:[B,S,Hkv_l,hd] (local shards)."""
    hd = cfg.head_dim
    hq_l, hkv_l, kv_rep = _local_counts(cfg, axis)
    dt = x.dtype
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"].astype(dt))
    B, S = x.shape[:2]
    q = q.reshape(B, S, hq_l, hd)
    if kv_rep:
        # replicated KV projection: slice the group our local q heads map to.
        k = k.reshape(B, S, cfg.num_kv_heads, hd)
        v = v.reshape(B, S, cfg.num_kv_heads, hd)
        group = cfg.num_heads // cfg.num_kv_heads  # q heads per kv head
        shard = lax.axis_index(axis.model)
        kv_idx = (shard * hq_l) // group
        k = lax.dynamic_slice_in_dim(k, kv_idx, 1, axis=2)
        v = lax.dynamic_slice_in_dim(v, kv_idx, 1, axis=2)
    else:
        k = k.reshape(B, S, hkv_l, hd)
        v = v.reshape(B, S, hkv_l, hd)
    return q, k, v


# public alias: the serving decode path (repro/serving/steps.py) projects
# q/k/v itself and runs attention through the paged kernel instead of the
# dense cache math below.
project_qkv = _project_qkv


def _expand_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    if n_rep == 1:
        return k
    B, S, H, D = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (B, S, H, n_rep, D)).reshape(B, S, H * n_rep, D)


# ---------------------------------------------------------------------------
# Training: full-sequence causal attention
# ---------------------------------------------------------------------------
def _attend_dense(q, k, v, positions, window, cap):
    """Materialised [S, S] logits (fine for short sequences)."""
    hd = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * hd ** -0.5
    logits = softcap(logits, cap)
    qi = positions[:, None, :, None]
    kj = positions[:, None, None, :]
    w = jnp.asarray(window)
    mask = (qi >= kj) & ((w <= 0) | (qi - kj < w))
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _attend_chunked(q, k, v, positions, window, cap, *, block_q: int,
                    kv_positions=None):
    """Query-chunked attention: peak logits memory O(block_q * S) instead of
    O(S^2) — the pure-JAX long-sequence path (32k prefill).  Exact.
    ``kv_positions`` defaults to ``positions``; pass it separately when the
    queries/positions are padded to a block_q multiple but keys are not."""
    B, S, H, hd = q.shape
    nq = S // block_q
    qc = jnp.moveaxis(q.reshape(B, nq, block_q, H, hd), 1, 0)
    pc = jnp.moveaxis(positions.reshape(B, nq, block_q), 1, 0)
    w = jnp.asarray(window)
    if kv_positions is None:
        kv_positions = positions
    kj = kv_positions[:, None, None, :]                   # [B,1,1,S_kv]

    def chunk(_, inp):
        qi_, pi_ = inp                                    # [B,block_q,H,hd]
        logits = jnp.einsum("bqhd,bkhd->bhqk", qi_, k).astype(jnp.float32)             * hd ** -0.5
        logits = softcap(logits, cap)
        qi = pi_[:, None, :, None]
        mask = (qi >= kj) & ((w <= 0) | (qi - kj < w))
        logits = jnp.where(mask, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(qi_.dtype)
        return None, jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    _, out = lax.scan(chunk, None, (qc, pc))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, hd)


CHUNKED_THRESHOLD = 8192


def attention_train(cfg: ModelConfig, p: PyTree, x: jnp.ndarray, *,
                    positions: jnp.ndarray, window: jnp.ndarray | int,
                    axis: AxisCtx, use_pallas: bool | None = None,
                    return_kv: bool = False):
    """x: [B, S, D] -> [B, S, D].  ``window``: 0 = global, >0 = sliding window.

    ``window`` may be a traced scalar, in which case it MUST be the per-layer
    table scalar from ``cfg.layer_windows()`` (values in {0,
    cfg.sliding_window}) — the Pallas path specializes on exactly those two
    static values.  Arbitrary traced window values are only honored by the
    non-Pallas paths (``use_pallas=False``).

    ``use_pallas=None`` inherits ``cfg.kernels`` (default on).  Sequences
    past CHUNKED_THRESHOLD use the query-chunked plain-JAX path regardless
    (the flash BlockSpecs stage whole-S K/V per head, which does not fit
    VMEM at 32k).  ``return_kv`` additionally returns the rope'd local K/V
    ([B, Hkv_l, S, hd]) for prefill cache building.
    """
    B, S, _ = x.shape
    hd = cfg.head_dim
    if use_pallas is None:
        use_pallas = cfg.kernels
    if S > CHUNKED_THRESHOLD:
        use_pallas = False
    q, k, v = _project_qkv(cfg, p, x, axis)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    n_rep = q.shape[2] // k.shape[2]
    kv_out = (jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)) if return_kv else None

    if use_pallas and not isinstance(window, jnp.ndarray):
        from repro.kernels import ops as kops
        y = kops.flash_attention(q, k, v, causal=True, window=int(window),
                                 softcap=cfg.attn_logit_softcap)
    elif use_pallas:
        # ``window`` is the traced per-layer table scalar (indexed inside the
        # layer scan).  The Pallas kernel needs a *static* window to prune
        # k-blocks, but ModelConfig.layer_windows only ever emits the two
        # values {0, cfg.sliding_window} — so specialize one kernel per value
        # outside the data path and select on the traced flag.  HLO stays
        # depth-independent (both specializations live in the one scan body).
        from repro.kernels import ops as kops

        def _specialized(w: int):
            return lambda qkv: kops.flash_attention(
                *qkv, causal=True, window=w, softcap=cfg.attn_logit_softcap)

        y = lax.cond(jnp.asarray(window) > 0,
                     _specialized(int(cfg.sliding_window)), _specialized(0),
                     (q, k, v))
    else:
        ke, ve = _expand_kv(k, n_rep), _expand_kv(v, n_rep)
        if S > CHUNKED_THRESHOLD:
            # always chunk past the threshold: the old `S % 512 == 0` guard
            # silently fell back to the dense path for ragged long sequences,
            # materializing exactly the O(S^2) logits the threshold exists to
            # avoid.  Pad queries/positions up to a block_q multiple instead
            # (keys stay un-padded; the pad rows are discarded after).
            block_q = 512
            pad = (-S) % block_q
            if pad:
                q_p = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
                pos_p = jnp.pad(positions, ((0, 0), (0, pad)), mode="edge")
            else:
                q_p, pos_p = q, positions
            y = _attend_chunked(q_p, ke, ve, pos_p, window,
                                cfg.attn_logit_softcap, block_q=block_q,
                                kv_positions=positions)
            y = y[:, :S] if pad else y
        else:
            y = _attend_dense(q, ke, ve, positions, window,
                              cfg.attn_logit_softcap)

    y = y.reshape(B, S, -1)
    out = jnp.einsum("bsh,hd->bsd", y, p["wo"].astype(y.dtype))
    out = axis.psum_model(out)
    return (out, *kv_out) if return_kv else out


# ---------------------------------------------------------------------------
# Decode: one new token against a KV cache
# ---------------------------------------------------------------------------
def attention_decode(cfg: ModelConfig, p: PyTree, x: jnp.ndarray, *,
                     k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     pos: jnp.ndarray, window: jnp.ndarray | int,
                     axis: AxisCtx, ring: bool = False
                     ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: [B, 1, D]; caches: [B, Hkv_l, S_cache_local, hd]; pos: scalar position.

    Returns (y [B,1,D], new k_cache, new v_cache).  When ``axis.seq`` is set
    the cache sequence dim is sharded and the softmax reduces over that axis.
    ``ring``: the cache is a circular window buffer (sliding-window layers);
    ring slot i holds absolute position pos - ((pos - i) mod W).
    """
    B = x.shape[0]
    hd = cfg.head_dim
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(cfg, p, x, axis)
    q = apply_rope(q, positions, cfg.rope_theta)
    k_new = apply_rope(k_new, positions, cfg.rope_theta)

    S_local = k_cache.shape[2]
    if ring:
        W = S_local
        local_start = 0
        slot_c, owns = pos % W, jnp.asarray(True)
    elif axis.seq:
        seq_axes = axis.seq if isinstance(axis.seq, tuple) else (axis.seq,)
        seq_shard = jnp.zeros((), jnp.int32)
        for a in seq_axes:
            seq_shard = seq_shard * lax.psum(1, a) + lax.axis_index(a)
        local_start = seq_shard * S_local
        # the new token's KV is written by the shard owning position `pos`
        slot = pos - local_start
        owns = (slot >= 0) & (slot < S_local)
        slot_c = jnp.clip(slot, 0, S_local - 1)
    else:
        local_start = 0
        slot_c, owns = pos, jnp.asarray(True)

    def write(cache, new):  # new: [B, 1, H, hd] -> cache [B, H, S_local, hd]
        upd = jnp.swapaxes(new, 1, 2)  # [B, H, 1, hd]
        written = lax.dynamic_update_slice_in_dim(cache, upd.astype(cache.dtype), slot_c, axis=2)
        return jnp.where(owns, written, cache)

    k_cache = write(k_cache, k_new)
    v_cache = write(v_cache, v_new)

    n_rep = q.shape[2] // k_cache.shape[1]
    kk = jnp.repeat(k_cache, n_rep, axis=1) if n_rep > 1 else k_cache  # [B, Hq_l, S, hd]
    vv = jnp.repeat(v_cache, n_rep, axis=1) if n_rep > 1 else v_cache
    scale = hd ** -0.5
    logits = jnp.einsum("bqhd,bhkd->bhk", q, kk).astype(jnp.float32) * scale  # q len 1
    logits = softcap(logits, cfg.attn_logit_softcap)
    if ring:
        kpos = pos - (pos - jnp.arange(S_local)) % S_local
    else:
        kpos = local_start + jnp.arange(S_local)
    w = jnp.asarray(window)
    valid = (kpos >= 0) & (kpos <= pos) & ((w <= 0) | (pos - kpos < w))
    logits = jnp.where(valid[None, None, :], logits, NEG_INF)

    if (axis.seq is not None) and not ring:
        m = lax.pmax(jnp.max(logits, axis=-1), axis.seq)                      # [B, H]
        e = jnp.exp(logits - m[..., None])
        denom = lax.psum(jnp.sum(e, axis=-1), axis.seq)                       # [B, H]
        num = lax.psum(jnp.einsum("bhk,bhkd->bhd", e, vv.astype(jnp.float32)), axis.seq)
    else:
        m = jnp.max(logits, axis=-1)
        e = jnp.exp(logits - m[..., None])
        denom = jnp.sum(e, axis=-1)
        num = jnp.einsum("bhk,bhkd->bhd", e, vv.astype(jnp.float32))
    y = (num / denom[..., None]).astype(x.dtype)                              # [B, H, hd]
    y = y.reshape(B, 1, -1)
    out = jnp.einsum("bsh,hd->bsd", y, p["wo"].astype(y.dtype))
    return axis.psum_model(out), k_cache, v_cache
