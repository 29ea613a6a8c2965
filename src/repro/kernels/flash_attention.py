"""Flash attention for TPU in Pallas: causal GQA with sliding-window and
logit-softcap support — forward AND backward.

TPU adaptation of the (GPU-origin) flash algorithm:
  * one grid step owns a whole (batch, head, q-block); K/V for that head are
    staged into VMEM once per grid step via their BlockSpec and the k-loop
    walks VMEM tiles — HBM→VMEM traffic is O(S·D) per head rather than
    O(S²), which is the flash insight restated for the TPU memory hierarchy.
    Only S is padded (by ``ops.flash_attention``, to a block multiple);
    head_dim is whatever the model has, and VMEM pads it to 128 lanes;
  * q, k, v and dO are upcast to fp32 as they are loaded, the softmax scale
    is folded into the resident q (or, in dkv, k) once per grid step, and
    every product is fp32 x fp32 into fp32 through ``lax.dot_general``
    dimension numbers that contract the right axes (q·kᵀ, dO·vᵀ, pᵀ·dO,
    dSᵀ·q): no tile is transposed in the loop.  (bf16 operands, with p and
    dS cast for their products, measured slower on a v5e: the loop is bound
    by its vector work, not the MXU);
  * causal + window masking prunes blocks *in the grid* (no MXU work on
    fully-masked tiles): loop bounds are derived from the block index.  Of
    the tiles walked, only those that touch an edge — the diagonal, the
    window's lower edge, the padded ``kv_len`` tail — build the mask and
    select; the band's interior runs a second, mask-free loop body
    (``_walk``: three ``fori_loop``s over [lo, a), [a, b), [b, hi));
  * tiles are sized from the shape: ``tile_plan(S, head_dim, dtype, rep)``
    picks (block_q, block_k) up to 512 keys by 512 queries, as large as the
    padding of S and the default 16 MiB of scoped VMEM allow (the dkv
    kernel's whole-S staging of q, dO and the lse/delta columns of its
    ``rep`` query heads is the largest claim); ``tile_counts`` gives the
    masked and mask-free tiles a head walks.  ``block_sizes`` is the one
    place a call's sizes are decided: the plan where none is given, else
    the caller's, which win.

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
VMEM_LIMIT = 16 * 2**20        # a Pallas kernel's default scoped VMEM
_NT = (((1,), (1,)), ((), ()))  # a · bᵀ
_TN = (((0,), (0,)), ((), ()))  # aᵀ · b
_NN = (((1,), (0,)), ((), ()))  # a · b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _min(a, b):
    return min(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.minimum(a, b)


def _max(a, b):
    return max(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.maximum(a, b)


def _cdiv(a, b):
    return -(-a // b)


def _split(lo, hi, a, b):
    """[lo, hi) cut at the interior [a, b), clamped so lo <= a <= b <= hi."""
    a = _min(_max(a, lo), hi)
    return lo, a, _min(_max(b, a), hi), hi


def _walk(ranges, body, carry):
    """``body(masked)``'s loop over the edge tiles [lo, a) and [b, hi) and
    ``body(False)``'s over the interior [a, b), in order."""
    lo, a, b, hi = ranges
    carry = jax.lax.fori_loop(lo, a, body(True), carry)
    carry = jax.lax.fori_loop(a, b, body(False), carry)
    return jax.lax.fori_loop(b, hi, body(True), carry)


def _k_ranges(qi, *, block_q, block_k, kv_len, causal, window):
    """(lo, a, b, hi): the k-blocks q-block ``qi`` walks, [lo, hi), and
    within them the interior [a, b) whose tiles every row sees whole."""
    q0 = qi * block_q
    q1 = q0 + block_q - 1
    n_k = _cdiv(kv_len, block_k)                      # valid k-blocks only
    # highest k-block that any row of this q-block can see
    hi = _min(q1 // block_k + 1, n_k) if causal else n_k
    lo = _max((q0 - window + 1) // block_k, 0) if window > 0 else 0
    b = kv_len // block_k                             # unpadded k-blocks
    if causal:                                        # last key <= first row
        b = _min(b, (q0 + 1) // block_k)
    # window: the last row still sees the tile's first key
    a = _cdiv(q1 - window + 1, block_k) if window > 0 else lo
    return _split(lo, hi, a, b)


def _q_ranges(kb, *, block_q, block_k, seq_len, kv_len, causal, window):
    """(lo, a, b, hi): the q-blocks k-block ``kb`` is seen from, [lo, hi),
    and within them the interior [a, b) whose rows see the tile whole."""
    k0 = kb * block_k
    k1 = k0 + block_k - 1
    n_q = seq_len // block_q
    lo = k0 // block_q if causal else 0
    # largest q any row of this k-block reaches: k1 + window - 1
    hi = _min((k1 + window - 1) // block_q + 1, n_q) if window > 0 else n_q
    a = _cdiv(k1, block_q) if causal else lo          # first row >= last key
    b = (k0 + window) // block_q if window > 0 else hi
    if kv_len < seq_len:
        # a tile with padded keys is masked; one of padding alone is skipped
        hi = jnp.where(k0 >= kv_len, lo, hi)
        b = jnp.where(k1 >= kv_len, lo, b)
    return _split(lo, hi, a, b)


def tile_counts(seq_len: int, block_q: int, block_k: int, *,
                causal: bool = True, window: int = 0,
                kv_len: int = 0) -> tuple[int, int]:
    """(masked, mask-free) tiles one head walks in a call of each kernel, for
    the padded ``seq_len`` and the true ``kv_len`` (0 = seq_len)."""
    kv_len = kv_len or seq_len
    masked = free = 0
    for qi in range(seq_len // block_q):
        lo, a, b, hi = _k_ranges(qi, block_q=block_q, block_k=block_k,
                                 kv_len=kv_len, causal=causal, window=window)
        masked += (a - lo) + (hi - b)
        free += b - a
    return masked, free


def _cover(n):
    """The power of two that covers ``n`` rows, at least 16."""
    return max(16, 1 << (n - 1).bit_length())


def tile_plan(seq_len: int, head_dim: int, dtype,
              rep: int = 1) -> tuple[int, int]:
    """(block_q, block_k) for sequences of ``seq_len`` (before padding) at
    ``head_dim`` in ``dtype``, ``rep`` query heads to a KV head.

    A sequence of at most 128 is one tile, the power of two that covers it
    (at least 16 rows).  Longer ones take the largest tiles of 128, 256 or
    512 that pad S no further than 128-row tiles would and whose dkv kernel
    fits ``VMEM_LIMIT``.
    """
    if seq_len <= 128:
        return _cover(seq_len), _cover(seq_len)
    S = _cdiv(seq_len, 128) * 128
    itemsize = jnp.dtype(dtype).itemsize
    for bk in (512, 256):
        for bq in (512, 256):
            if S % bk or S % bq:
                continue
            if _dkv_vmem(S, head_dim, itemsize, rep, bq, bk) <= VMEM_LIMIT:
                return bq, bk
    return 128, 128


def _dkv_vmem(S, D, itemsize, rep, bq, bk):
    """Scoped VMEM of the dkv kernel, the largest of the three, in bytes,
    estimated from above: the ``rep`` heads' whole-S q and dO (lane-padded
    to 128) and lse/delta columns (128 bytes a row), double-buffered, and
    eight fp32 [block_q, block_k] tiles for the loop body.  Checked against
    the v5e compiler: X_32's backward at S = 8192 is refused at 512 x 512
    and compiles at the planned 256 x 512; 32 q / 8 kv heads of 128 at S =
    2048 compile at the planned 256 x 512."""
    lanes = _cdiv(D, 128) * 128
    return 2 * 2 * rep * S * (lanes * itemsize + 128) + 8 * bq * bk * 4


def block_sizes(seq_len: int, head_dim: int, dtype, rep: int = 1,
                block_q: int | None = None,
                block_k: int | None = None) -> tuple[int, int]:
    """(block_q, block_k) of a call: ``tile_plan``'s where None, else the
    caller's, cut to the power of two that covers the sequence."""
    plan_q, plan_k = tile_plan(seq_len, head_dim, dtype, rep)
    cut = _cover(seq_len)
    return (min(block_q, cut) if block_q else plan_q,
            min(block_k, cut) if block_k else plan_k)


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


def _scores(q, k, *, softcap):
    """fp32 (softcapped) logits of one tile, the scale already in q or k.
    Returns (s, tanh term or None)."""
    s = _dot(q, k, _NT)
    if softcap > 0:
        t = jnp.tanh(s / softcap)
        return softcap * t, t
    return s, None


def _f32(x):
    return x.astype(jnp.float32)


def _attn_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
                     block_q: int, block_k: int, seq_len: int, kv_len: int,
                     causal: bool, window: int, softcap: float):
    qi = pl.program_id(2)
    q = _f32(q_ref[0, 0]) * scale                     # [block_q, D]
    D = q.shape[-1]
    q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)
    mask_of = functools.partial(_block_mask, causal=causal, window=window,
                                kv_len=kv_len, seq_len=seq_len)

    def body(masked):
        def step(kb, carry):
            acc, m_prev, l_prev = carry
            k = _f32(k_ref[0, 0, pl.ds(kb * block_k, block_k)])
            v = _f32(v_ref[0, 0, pl.ds(kb * block_k, block_k)])
            s, _ = _scores(q, k, softcap=softcap)     # [bq, bk]
            if masked:
                k_pos = kb * block_k + jax.lax.iota(jnp.int32, block_k)
                s = jnp.where(mask_of(q_pos, k_pos), s, NEG_INF)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)
            l_cur = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + _dot(p, v, _NN)
            return acc, m_cur, l_cur
        return step

    # the running (max, sum) are [block_q, 1] columns: one value per row, in
    # the sublane orientation the row reductions produce
    carry = (jnp.zeros((block_q, D), jnp.float32),
             jnp.full((block_q, 1), NEG_INF, jnp.float32),
             jnp.zeros((block_q, 1), jnp.float32))
    acc, m, l = _walk(_k_ranges(qi, block_q=block_q, block_k=block_k,
                                kv_len=kv_len, causal=causal, window=window),
                      body, carry)
    l = jnp.where(l == 0.0, 1.0, l)                    # fully-masked rows
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)


def _recompute_p(q, k, lse, q_pos, k_pos, *, masked, causal, window, kv_len,
                 seq_len, softcap):
    """(p, softcap tanh term) for one tile, from the raw logits and the
    ``[block_q, 1]`` lse column.

    Rows whose forward was fully masked carry ``lse = NEG_INF`` (they only
    exist in the pad region, and only in tiles that touch an edge); their
    probabilities are forced to zero rather than letting ``exp(s -
    NEG_INF)`` overflow.
    """
    s, t = _scores(q, k, softcap=softcap)
    if not masked:
        return jnp.exp(s - lse), t
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
    q = _f32(q_ref[0, 0]) * scale                     # [block_q, D]
    do = _f32(do_ref[0, 0])
    lse = lse_ref[0, 0]                               # [block_q, 1] fp32
    delta = delta_ref[0, 0]                           # [block_q, 1] fp32
    D = q.shape[-1]
    q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)

    def body(masked):
        def step(kb, acc):
            k = _f32(k_ref[0, 0, pl.ds(kb * block_k, block_k)])
            v = _f32(v_ref[0, 0, pl.ds(kb * block_k, block_k)])
            k_pos = kb * block_k + jax.lax.iota(jnp.int32, block_k)
            p, t = _recompute_p(q, k, lse, q_pos, k_pos, masked=masked,
                                causal=causal, window=window, kv_len=kv_len,
                                seq_len=seq_len, softcap=softcap)
            ds = p * (_dot(do, v, _NT) - delta)
            if softcap > 0:
                ds = ds * (1.0 - t * t)               # tanh chain rule
            return acc + _dot(ds, k, _NN)
        return step

    acc = _walk(_k_ranges(qi, block_q=block_q, block_k=block_k,
                          kv_len=kv_len, causal=causal, window=window),
                body, jnp.zeros((block_q, D), jnp.float32))
    dq_ref[0, 0] = (acc * scale).astype(dq_ref.dtype)


def _attn_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, *, scale: float, block_q: int,
                         block_k: int, seq_len: int, kv_len: int, causal: bool,
                         window: int, softcap: float, rep: int):
    kb = pl.program_id(2)
    k = _f32(k_ref[0, 0]) * scale                     # scores only: q·(k/√D)ᵀ
    v = _f32(v_ref[0, 0])
    D = k.shape[-1]
    k_pos = kb * block_k + jax.lax.iota(jnp.int32, block_k)
    ranges = _q_ranges(kb, block_q=block_q, block_k=block_k, seq_len=seq_len,
                       kv_len=kv_len, causal=causal, window=window)

    carry = (jnp.zeros((block_k, D), jnp.float32),
             jnp.zeros((block_k, D), jnp.float32))
    for r in range(rep):                               # GQA: replicated q heads
        def body(masked, r=r):
            def step(qb, carry):
                dk, dv = carry
                rows = pl.ds(qb * block_q, block_q)
                q = _f32(q_ref[0, 0, r, rows])
                do = _f32(do_ref[0, 0, r, rows])
                lse = lse_ref[0, 0, r, rows]
                delta = delta_ref[0, 0, r, rows]
                q_pos = qb * block_q + jax.lax.iota(jnp.int32, block_q)
                p, t = _recompute_p(q, k, lse, q_pos, k_pos, masked=masked,
                                    causal=causal, window=window,
                                    kv_len=kv_len, seq_len=seq_len,
                                    softcap=softcap)
                dv = dv + _dot(p, do, _TN)
                ds = p * (_dot(do, v, _NT) - delta)
                if softcap > 0:
                    ds = ds * (1.0 - t * t)
                dk = dk + _dot(ds, q, _TN)
                return dk, dv
            return step

        carry = _walk(ranges, body, carry)
    dk, dv = carry
    dk_ref[0, 0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


_STATICS = ("causal", "window", "softcap", "kv_len", "block_q", "block_k",
            "interpret")


@functools.partial(jax.jit, static_argnames=_STATICS)
def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        softcap: float = 0.0, kv_len: int = 0,
                        block_q: int | None = None,
                        block_k: int | None = None,
                        interpret: bool = False):
    """q: [B, S, Hq, D]; k, v: [B, S, Hkv, D] -> (out [B, S, Hq, D],
    lse [B, Hq, S, 1] fp32).

    GQA is handled by head-index mapping in the BlockSpec (no KV materialised
    repeat).  S must be a multiple of the block sizes (the ops wrapper pads;
    None takes ``tile_plan``'s); ``kv_len`` (0 = S) is the true pre-pad
    length — padded key rows are masked in-kernel.
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    rep = Hq // Hkv
    scale = D ** -0.5
    kv_len = kv_len or S
    block_q, block_k = block_sizes(S, D, q.dtype, rep, block_q, block_k)
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
                    softcap: float = 0.0, kv_len: int = 0,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool = False):
    """Forward only (back-compat entry; the lse residual is discarded)."""
    out, _ = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                 softcap=softcap, kv_len=kv_len,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret)
    return out


@functools.partial(jax.jit, static_argnames=_STATICS)
def flash_attention_bwd(q, k, v, out, lse, do, *, causal: bool = True,
                        window: int = 0, softcap: float = 0.0, kv_len: int = 0,
                        block_q: int | None = None,
                        block_k: int | None = None,
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
    block_q, block_k = block_sizes(S, D, q.dtype, rep, block_q, block_k)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)

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
