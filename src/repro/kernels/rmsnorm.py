"""Fused RMSNorm Pallas kernel — forward and single-pass VJP.

One grid step normalises a ``(block_rows, D)`` tile held in VMEM: the mean
square, rsqrt and scale multiply are fused into a single VMEM-resident pass
(vs three HBM round-trips unfused).  D is expected to be a multiple of the
128-lane layout (all assigned architectures satisfy this).

The backward is one fused pass as well: each tile recomputes its rstd from x
(cheaper than storing it) and emits both dx and its partial dscale — the
row-reduction for dscale is finished by a tiny cross-block sum outside the
kernel, so one HBM read of (x, g) yields both cotangents.  ``plus_one``
implements the ``rmsnorm_p1`` variant (gemma-style ``1 + scale``), whose
dscale is unchanged (d(1+s)/ds = 1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float, plus_one: bool):
    x = x_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    if plus_one:
        s = 1.0 + s
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * s).astype(o_ref.dtype)


def _rmsnorm_bwd_kernel(x_ref, s_ref, g_ref, dx_ref, ds_ref, *, eps: float,
                        plus_one: bool):
    x = x_ref[...].astype(jnp.float32)                 # [block_rows, D]
    g = g_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    se = (1.0 + s) if plus_one else s
    D = x.shape[-1]
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)
    gs = g * se
    dx = (gs - x * (r * r / D) * jnp.sum(gs * x, axis=-1, keepdims=True)) * r
    dx_ref[...] = dx.astype(dx_ref.dtype)
    ds_ref[...] = jnp.sum(g * x * r, axis=0, keepdims=True)  # [1, D] partial


def _to_rows(x, block_rows: int | None, interpret: bool):
    D = x.shape[-1]
    rows = 1
    for d in x.shape[:-1]:
        rows *= d
    if block_rows is None:
        # interpret mode: one whole tile (XLA elides the full-extent block
        # copies); compiled TPU path: tiles of at most 512K elements, which
        # keeps the backward's double-buffered (x, g, dx) blocks and fp32
        # temporaries inside the 16 MiB scoped VMEM (256 rows of 4096 are
        # refused there)
        block_rows = rows if interpret else max(8, min(256, (1 << 19) // D))
    x2 = x.reshape(rows, D)
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, rows, block_rows


@functools.partial(jax.jit, static_argnames=("eps", "plus_one", "block_rows",
                                             "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-6, plus_one: bool = False,
            block_rows: int | None = None, interpret: bool = False):
    """x: [..., D]; scale: [D] -> same shape/dtype as x."""
    orig_shape = x.shape
    D = x.shape[-1]
    x2, rows, block_rows = _to_rows(x, block_rows, interpret)
    grid = (x2.shape[0] // block_rows,)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps, plus_one=plus_one),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
                  pl.BlockSpec((D,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=compat.out_struct(x2.shape, x.dtype, x, scale),
        interpret=interpret,
    )(x2, scale)
    if x2.shape[0] != rows:
        out = out[:rows]
    return out.reshape(orig_shape)


@functools.partial(jax.jit, static_argnames=("eps", "plus_one", "block_rows",
                                             "interpret"))
def rmsnorm_bwd(x, scale, g, *, eps: float = 1e-6, plus_one: bool = False,
                block_rows: int | None = None, interpret: bool = False):
    """(dx like x, dscale [D] fp32) in one fused pass over (x, g)."""
    orig_shape = x.shape
    D = x.shape[-1]
    x2, rows, block_rows = _to_rows(x, block_rows, interpret)
    g2, _, _ = _to_rows(g, block_rows, interpret)
    n_blocks = x2.shape[0] // block_rows
    # dscale partials are [n_blocks, 1, D]: each grid step writes a (1, D)
    # block whose second-to-last dim is the array's own (the TPU's (8, 128)
    # tiling rule refuses a (1, D) block of an [n_blocks, D] array)
    dx, ds_part = pl.pallas_call(
        functools.partial(_rmsnorm_bwd_kernel, eps=eps, plus_one=plus_one),
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
                  pl.BlockSpec((D,), lambda i: (0,)),
                  pl.BlockSpec((block_rows, D), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
                   pl.BlockSpec((None, 1, D), lambda i: (i, 0, 0))],
        out_shape=[compat.out_struct(x2.shape, x.dtype, x, scale, g),
                   compat.out_struct((n_blocks, 1, D), jnp.float32, x, scale,
                                     g)],
        interpret=interpret,
    )(x2, scale, g2)
    if x2.shape[0] != rows:
        dx = dx[:rows]
    return dx.reshape(orig_shape), jnp.sum(ds_part, axis=(0, 1))
