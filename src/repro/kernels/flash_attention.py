"""Flash attention for TPU in Pallas: causal GQA with sliding-window and
logit-softcap support — forward AND backward.

TPU adaptation of the (GPU-origin) flash algorithm:
  * tiling is chosen for the MXU and VMEM, not for SM shared memory: the
    query tile is ``(block_q, head_dim)`` with block_q a multiple of the
    128-lane register layout, and head_dim padded to 128 lanes by the caller;
  * one grid step owns a whole (batch, head, q-block); K/V for that head are
    staged into VMEM once per grid step via their BlockSpec and the k-loop
    walks VMEM tiles — HBM→VMEM traffic is O(S·D) per head rather than
    O(S²), which is the flash insight restated for the TPU memory hierarchy;
  * the running (max, sum) softmax rescaling is carried in fp32 vector
    registers; matmuls hit the MXU via ``jnp.dot`` on (block_q, D)x(D,
    block_k) tiles;
  * causal + window masking prunes blocks *in the grid* (no wasted MXU work
    on fully-masked tiles): loop bounds are derived from the block index.

Backward (the custom-VJP contract, exposed via kernels/ops.py):
  * the forward additionally emits the per-row log-sum-exp ``lse = m +
    log(l)`` — the only residual beyond (q, k, v, out) the backward needs;
  * ``dq`` re-walks K/V tiles per q-block (same bounds as the forward) and
    recomputes the [block_q, block_k] probability tile from (s, lse) — the
    flash-style recomputation that keeps the backward free of any O(S²)
    intermediate;
  * ``dk``/``dv`` walk q-tiles per k-block; GQA is handled in-kernel: the
    grid runs over KV heads and each step reduces over its ``rep``
    replicated query heads (no materialised KV repeat, no post-hoc
    head-sum);
  * softcap backward applies the tanh chain rule on the recomputed raw
    logits; masked probabilities are rebuilt with the exact forward mask
    (causal, window, and the true ``kv_len`` so padded key rows never leak).

Validated in interpret mode on CPU against kernels/ref.py autodiff;
tests/test_tpu_compile.py compiles it for a TPU v5e chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat

NEG_INF = -1.0e38


def _block_mask(q_pos, k_pos, *, causal: bool, window: int, kv_len: int,
                seq_len: int):
    """The forward/backward-shared mask for one [block_q, block_k] tile."""
    mask = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window > 0:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    if kv_len < seq_len:
        # padded key rows: without this they are only excluded by causality,
        # which does not hold for the non-causal / windowed cases
        mask &= (k_pos < kv_len)[None, :]
    return mask


def _attn_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
                     block_q: int, block_k: int, seq_len: int, kv_len: int,
                     causal: bool, window: int, softcap: float):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale       # [block_q, D]
    D = q.shape[-1]
    q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)

    n_k = (kv_len + block_k - 1) // block_k           # valid k-blocks only
    if causal:
        # highest k-block that any row of this q-block can see
        hi = (qi * block_q + block_q - 1) // block_k + 1
        hi = min(hi, n_k) if isinstance(hi, int) else jnp.minimum(hi, n_k)
    else:
        hi = n_k
    if window > 0:
        lo = jnp.maximum((qi * block_q - window + 1) // block_k, 0)
    else:
        lo = 0

    def body(kb, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[0, 0, pl.ds(kb * block_k, block_k)].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(kb * block_k, block_k)].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [bq, bk]
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = kb * block_k + jax.lax.iota(jnp.int32, block_k)
        mask = _block_mask(q_pos, k_pos, causal=causal, window=window,
                           kv_len=kv_len, seq_len=seq_len)
        s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_cur = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
        return acc, m_cur, l_cur

    # the running (max, sum) are [block_q, 1] columns: one value per row, in
    # the sublane orientation the row reductions produce
    acc0 = jnp.zeros((block_q, D), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    l = jnp.where(l == 0.0, 1.0, l)                    # fully-masked rows
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)


def _recompute_p(q, k, lse, q_pos, k_pos, *, scale, causal, window, kv_len,
                 seq_len, softcap):
    """(p, softcap tanh term) for one tile, from the raw logits and the
    ``[block_q, 1]`` lse column.

    Rows whose forward was fully masked carry ``lse = NEG_INF`` (they only
    exist in the pad region); their probabilities are forced to zero rather
    than letting ``exp(s - NEG_INF)`` overflow.
    """
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        t = jnp.tanh(s / softcap)
        s = softcap * t
    else:
        t = None
    mask = _block_mask(q_pos, k_pos, causal=causal, window=window,
                       kv_len=kv_len, seq_len=seq_len)
    dead = lse <= 0.5 * NEG_INF
    lse_safe = jnp.where(dead, 0.0, lse)
    p = jnp.where(mask & ~dead, jnp.exp(s - lse_safe), 0.0)
    return p, t


def _attn_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, *, scale: float, block_q: int, block_k: int,
                        seq_len: int, kv_len: int, causal: bool, window: int,
                        softcap: float):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)               # [block_q, D]
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]                               # [block_q, 1] fp32
    delta = delta_ref[0, 0]                           # [block_q, 1] fp32
    D = q.shape[-1]
    q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)

    n_k = (kv_len + block_k - 1) // block_k
    if causal:
        hi = (qi * block_q + block_q - 1) // block_k + 1
        hi = min(hi, n_k) if isinstance(hi, int) else jnp.minimum(hi, n_k)
    else:
        hi = n_k
    lo = jnp.maximum((qi * block_q - window + 1) // block_k, 0) if window > 0 \
        else 0

    def body(kb, acc):
        k = k_ref[0, 0, pl.ds(kb * block_k, block_k)].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(kb * block_k, block_k)].astype(jnp.float32)
        k_pos = kb * block_k + jax.lax.iota(jnp.int32, block_k)
        p, t = _recompute_p(q, k, lse, q_pos, k_pos, scale=scale,
                            causal=causal, window=window, kv_len=kv_len,
                            seq_len=seq_len, softcap=softcap)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        if softcap > 0:
            ds = ds * (1.0 - t * t)                   # tanh chain rule
        return acc + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(lo, hi, body, jnp.zeros((block_q, D), jnp.float32))
    dq_ref[0, 0] = (acc * scale).astype(dq_ref.dtype)


def _attn_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, *, scale: float, block_q: int,
                         block_k: int, seq_len: int, kv_len: int, causal: bool,
                         window: int, softcap: float, rep: int):
    kb = pl.program_id(2)
    k = k_ref[0, 0].astype(jnp.float32)               # [block_k, D]
    v = v_ref[0, 0].astype(jnp.float32)
    D = k.shape[-1]
    k_pos = kb * block_k + jax.lax.iota(jnp.int32, block_k)

    n_q = seq_len // block_q
    lo = (kb * block_k) // block_q if causal else 0
    if window > 0:
        # largest q any row of this k-block reaches: k_max + window - 1
        hi = jnp.minimum((kb * block_k + block_k + window - 2) // block_q + 1,
                         n_q)
    else:
        hi = n_q

    dk = jnp.zeros((block_k, D), jnp.float32)
    dv = jnp.zeros((block_k, D), jnp.float32)
    for r in range(rep):                               # GQA: replicated q heads
        def body(qb, carry):
            dk, dv = carry
            q = q_ref[0, 0, r, pl.ds(qb * block_q, block_q)].astype(jnp.float32)
            do = do_ref[0, 0, r, pl.ds(qb * block_q, block_q)].astype(jnp.float32)
            lse = lse_ref[0, 0, r, pl.ds(qb * block_q, block_q)]
            delta = delta_ref[0, 0, r, pl.ds(qb * block_q, block_q)]
            q_pos = qb * block_q + jax.lax.iota(jnp.int32, block_q)
            p, t = _recompute_p(q, k, lse, q_pos, k_pos, scale=scale,
                                causal=causal, window=window, kv_len=kv_len,
                                seq_len=seq_len, softcap=softcap)
            dv = dv + jnp.dot(p.T, do, preferred_element_type=jnp.float32)
            dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            ds = p * (dp - delta)
            if softcap > 0:
                ds = ds * (1.0 - t * t)
            dk = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
            return dk, dv

        dk, dv = jax.lax.fori_loop(lo, hi, body, (dk, dv))
    dk_ref[0, 0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


_STATICS = ("causal", "window", "softcap", "kv_len", "block_q", "block_k",
            "interpret")


@functools.partial(jax.jit, static_argnames=_STATICS)
def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        softcap: float = 0.0, kv_len: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """q: [B, S, Hq, D]; k, v: [B, S, Hkv, D] -> (out [B, S, Hq, D],
    lse [B, Hq, S, 1] fp32).

    GQA is handled by head-index mapping in the BlockSpec (no KV materialised
    repeat).  S must be a multiple of the block sizes (the ops wrapper pads);
    ``kv_len`` (0 = S) is the true pre-pad length — padded key rows are
    masked in-kernel.
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    rep = Hq // Hkv
    scale = D ** -0.5
    kv_len = kv_len or S
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    # the per-row lse is stored as a [S, 1] column: its blocks are
    # (block_q, 1), whose last dim is the array's own, which the TPU's
    # (8, 128) tiling rule accepts where a (1, block_q) row block is refused

    # layout: [B, H, S, D] so the grid walks (batch, head, q-block)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, Hq, S // block_q)
    kernel = functools.partial(_attn_fwd_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, seq_len=S, kv_len=kv_len,
                               causal=causal, window=window, softcap=softcap)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h // rep, 0, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h // rep, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[compat.out_struct((B, Hq, S, D), q.dtype, q, k, v),
                   compat.out_struct((B, Hq, S, 1), jnp.float32, q, k, v)],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, kv_len: int = 0, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """Forward only (back-compat entry; the lse residual is discarded)."""
    out, _ = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                 softcap=softcap, kv_len=kv_len,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret)
    return out


@functools.partial(jax.jit, static_argnames=_STATICS)
def flash_attention_bwd(q, k, v, out, lse, do, *, causal: bool = True,
                        window: int = 0, softcap: float = 0.0, kv_len: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """(dq, dk, dv) by re-walking K/V (resp. Q) tiles — no O(S²) intermediate.

    ``out``/``lse`` are the forward's output and per-row log-sum-exp; ``do``
    the output cotangent in [B, S, Hq, D] layout.
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    rep = Hq // Hkv
    scale = D ** -0.5
    kv_len = kv_len or S
    block_q = min(block_q, S)
    block_k = min(block_k, S)

    qt = q.transpose(0, 2, 1, 3)                       # [B, Hq, S, D]
    kt = k.transpose(0, 2, 1, 3)                       # [B, Hkv, S, D]
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    # delta = rowsum(dO * O): O(S·D) elementwise prologue (plain JAX), kept
    # as the same [B, Hq, S, 1] column layout as lse
    delta = jnp.sum(dot.astype(jnp.float32)
                    * out.transpose(0, 2, 1, 3).astype(jnp.float32), axis=-1,
                    keepdims=True)

    statics = dict(scale=scale, block_q=block_q, block_k=block_k, seq_len=S,
                   kv_len=kv_len, causal=causal, window=window,
                   softcap=softcap)

    dq = pl.pallas_call(
        functools.partial(_attn_bwd_dq_kernel, **statics),
        grid=(B, Hq, S // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h // rep, 0, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h // rep, 0, 0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=compat.out_struct((B, Hq, S, D), q.dtype, q, k, v, do, lse),
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)

    # GQA: group the query heads of each KV head so the k-block grid reduces
    # over its `rep` replicated heads in-kernel.
    q5 = qt.reshape(B, Hkv, rep, S, D)
    do5 = dot.reshape(B, Hkv, rep, S, D)
    lse5 = lse.reshape(B, Hkv, rep, S, 1)
    delta5 = delta.reshape(B, Hkv, rep, S, 1)
    dk, dv = pl.pallas_call(
        functools.partial(_attn_bwd_dkv_kernel, rep=rep, **statics),
        grid=(B, Hkv, S // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, rep, S, D), lambda b, h, i: (b, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, rep, S, D), lambda b, h, i: (b, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, rep, S, 1), lambda b, h, i: (b, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, rep, S, 1), lambda b, h, i: (b, h, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[compat.out_struct((B, Hkv, S, D), x.dtype, q, k, v, do, lse)
                   for x in (k, v)],
        interpret=interpret,
    )(q5, kt, vt, do5, lse5, delta5)

    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))
