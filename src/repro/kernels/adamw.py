"""Fused AdamW update Pallas kernel for the ZeRO-partitioned flat chunks.

The unfused tree-map update (optim/adam.py) stages each state tensor through
separate elementwise ops — on a backend that does not fuse the whole chain
into one multi-output loop that is ~6 HBM round-trips per state tensor
(read p/mu/nu/g, write p/mu/nu, plus the intermediates).  This kernel makes
the whole AdamW step one blocked pass: each grid step pulls a
``(block_rows, 128)`` tile of (p, mu, nu, g) into VMEM, computes the new
moments and parameter in registers, and writes the three outputs — one HBM
read and one write per state tensor, which is the Megatron-LM-style fusion
budget (arXiv 2104.04473) applied to the optimizer.

The flat fp32 partition chunks of core/partition.py (``[L?, 1, 1, chunk]``
per-device inside shard_map) are exactly the layout this wants: the kernel is
shape-agnostic (everything is flattened and padded to the tile), so it also
serves the per-layer fused update of the layered schedule (§C.3).

Hyper-parameters that are static per training run (b1, b2, eps, weight
decay) are baked into the kernel; the four step-dependent scalars (lr, the
two bias corrections, and the grad-clip scale) arrive as a packed length-4
fp32 operand so the jitted step never recompiles across steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat

_LANES = 128


def _adamw_kernel(sc_ref, p_ref, m_ref, v_ref, g_ref, po_ref, mo_ref, vo_ref,
                  *, b1: float, b2: float, eps: float, wd: float):
    lr, b1c, b2c, gscale = (sc_ref[0], sc_ref[1], sc_ref[2], sc_ref[3])
    p = p_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32) * gscale
    m32 = b1 * m + (1 - b1) * g
    v32 = b2 * v + (1 - b2) * jnp.square(g)
    mh = m32 / b1c
    vh = v32 / b2c
    po_ref[...] = (p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)).astype(
        po_ref.dtype)
    mo_ref[...] = m32.astype(mo_ref.dtype)
    vo_ref[...] = v32.astype(vo_ref.dtype)


def _flatten_pad(x, block_elems: int):
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % block_elems
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, _LANES)


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd",
                                             "block_rows", "interpret"))
def adamw_update(p, m, v, g, scalars, *, b1: float, b2: float, eps: float,
                 wd: float, block_rows: int | None = None,
                 interpret: bool = False):
    """One fused AdamW step on a single state leaf (any shape).

    ``scalars``: fp32 [4] = (lr, 1-b1^t, 1-b2^t, grad-clip scale).  Returns
    (new p [p.dtype], new mu [m.dtype], new nu [v.dtype]) — the same float
    ops as the unfused tree-map update in optim/adam.py (equal to within
    FMA contraction, which may differ between lowerings).

    ``block_rows=None`` picks the tile: the VMEM-sized 256-row tile on the
    compiled TPU path; in interpret mode (CPU validation) one whole-leaf
    tile, where XLA elides the full-extent block copies so the grid scan
    costs nothing and the body compiles to a single multi-output loop.  Pass
    an explicit ``block_rows`` to exercise the tiled path anywhere.
    """
    shape, n = p.shape, p.size
    if block_rows is None:
        block_rows = max((n + _LANES - 1) // _LANES, 1) if interpret else 256
    block_elems = block_rows * _LANES
    if n < block_elems:                      # small leaf: one whole-leaf tile
        block_rows = max((n + _LANES - 1) // _LANES, 1)
        block_elems = block_rows * _LANES
    p2 = _flatten_pad(p, block_elems)
    m2 = _flatten_pad(m, block_elems)
    v2 = _flatten_pad(v, block_elems)
    g2 = _flatten_pad(g, block_elems)
    grid = (p2.shape[0] // block_rows,)
    blk = lambda i: (i, 0)
    spec = pl.BlockSpec((block_rows, _LANES), blk)
    po, mo, vo = pl.pallas_call(
        functools.partial(_adamw_kernel, b1=b1, b2=b2, eps=eps, wd=wd),
        grid=grid,
        in_specs=[pl.BlockSpec((4,), lambda i: (0,)), spec, spec, spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[compat.out_struct(p2.shape, x.dtype, p, m, v, g, scalars)
                   for x in (p, m, v)],
        interpret=interpret,
    )(scalars.astype(jnp.float32), p2, m2, v2, g2)
    unflat = lambda x: x.reshape(-1)[:n].reshape(shape)
    return unflat(po), unflat(mo), unflat(vo)
