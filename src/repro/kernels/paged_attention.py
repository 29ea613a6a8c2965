"""Paged-attention decode kernel in Pallas: one query token per request,
K/V gathered through a block table from a paged pool.

The serving-side analogue of kernels/flash_attention.py: where training
tiles a contiguous [B, S] cache, serving stores K/V as fixed-size blocks in
a shared pool (serving/cache.py) and each request owns an ordered *block
table* of pool indices.  The kernel walks that table:

  * the grid runs over (request, query head); each step owns one request's
    single decode token against one head;
  * the K/V pools are staged per KV head via their BlockSpec (GQA by
    head-index mapping, ``h // rep`` — no materialised repeat, same
    treatment as the flash kernel) and the k-loop *gathers* one
    ``[block_size, head_dim]`` tile per block-table entry with a dynamic
    ref index — the data movement pattern the block table exists to enable;
  * the walk is causal by construction: only the ``ceil(ctx/bs)`` table
    entries covering the request's live context are visited, and the tail
    block's padded rows are masked by the true context length;
  * sliding windows prune the loop's lower bound exactly like the flash
    kernel prunes k-blocks; logit softcap applies the gemma2 tanh cap;
  * the running (max, sum) softmax rescaling is carried in fp32, one
    vector register row per request.

Decode is memory-bound, not MXU-bound: the tile shapes here ([bs, D] x
[D]) are chosen for gather locality, not matmul occupancy.  Validated in
interpret mode on CPU against kernels/ref.py (compiled-TPU validation of
the gather DMA pattern is the ROADMAP's open follow-on).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat

NEG_INF = -1.0e38


def _paged_decode_kernel(q_ref, k_ref, v_ref, bt_ref, len_ref, o_ref, *,
                         scale: float, block_size: int, window: int,
                         softcap: float):
    q = q_ref[0, 0].astype(jnp.float32) * scale          # [D]
    D = q.shape[-1]
    ctx = len_ref[0]                                     # live tokens (incl. q)
    pos = ctx - 1                                        # query position
    n_b = (ctx + block_size - 1) // block_size           # blocks to visit
    if window > 0:
        lo = jnp.maximum((pos - window + 1) // block_size, 0)
    else:
        lo = 0

    def body(b, carry):
        acc, m_prev, l_prev = carry
        bid = bt_ref[0, b]                               # gather via the table
        k = k_ref[bid, 0].astype(jnp.float32)            # [bs, D]
        v = v_ref[bid, 0].astype(jnp.float32)
        s = jnp.dot(k, q, preferred_element_type=jnp.float32)   # [bs]
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = b * block_size + jax.lax.iota(jnp.int32, block_size)
        valid = k_pos <= pos                             # causal tail mask
        if window > 0:
            valid &= pos - k_pos < window
        s = jnp.where(valid, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(s))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_cur = l_prev * alpha + jnp.sum(p)
        acc = acc * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
        return acc, m_cur, l_cur

    acc0 = jnp.zeros((D,), jnp.float32)
    m0 = jnp.asarray(NEG_INF, jnp.float32)
    l0 = jnp.zeros((), jnp.float32)
    acc, _, l = jax.lax.fori_loop(lo, n_b, body, (acc0, m0, l0))
    l = jnp.where(l == 0.0, 1.0, l)                      # empty context rows
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)


_STATICS = ("window", "softcap", "interpret")


@functools.partial(jax.jit, static_argnames=_STATICS)
def paged_attention_decode(q, k_pool, v_pool, block_tables, context_lens, *,
                           window: int = 0, softcap: float = 0.0,
                           interpret: bool = False):
    """q: [R, Hq, D]; pools: [N, Hkv, bs, D]; block_tables: [R, max_blocks]
    int32 pool indices; context_lens: [R] int32 live tokens per request
    (the query sits at position ``ctx - 1``; its K/V must already be
    written to the pool).  Returns [R, Hq, D].

    Rows with ``context_lens == 0`` produce zeros (idle engine slots).
    """
    R, Hq, D = q.shape
    N, Hkv, bs, _ = k_pool.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    rep = Hq // Hkv
    max_blocks = block_tables.shape[1]
    kernel = functools.partial(_paged_decode_kernel, scale=D ** -0.5,
                               block_size=bs, window=int(window),
                               softcap=float(softcap))
    return pl.pallas_call(
        kernel,
        grid=(R, Hq),
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda r, h: (r, h, 0)),
            pl.BlockSpec((N, 1, bs, D), lambda r, h: (0, h // rep, 0, 0)),
            pl.BlockSpec((N, 1, bs, D), lambda r, h: (0, h // rep, 0, 0)),
            pl.BlockSpec((1, max_blocks), lambda r, h: (r, 0)),
            pl.BlockSpec((1,), lambda r, h: (r,)),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda r, h: (r, h, 0)),
        out_shape=compat.out_struct((R, Hq, D), q.dtype, q, k_pool, v_pool,
                                    block_tables, context_lens),
        interpret=interpret,
    )(q, k_pool, v_pool, block_tables.astype(jnp.int32),
      context_lens.astype(jnp.int32))
