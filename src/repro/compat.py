"""The one seam between the distributed runtime and JAX's shard_map/vma surface.

The collective-explicit programs in this repo are written against the
vma-typed shard_map of the installed JAX (0.9): ``jax.shard_map`` with
``check_vma=True``, ``lax.pcast(..., to="varying")`` (the former
``lax.pvary``) and ``jax.typeof(x).vma``.  Everything
under ``src/`` reaches those symbols through this module only, so a JAX
upgrade touches exactly one file (``tools/check_compat.py`` enforces it).

``out_struct`` is the piece the Pallas kernels need: inside a
``check_vma=True`` shard_map a ``pallas_call`` output must state which mesh
axes it varies over, and a kernel's output varies wherever its inputs do.
"""
from __future__ import annotations

import jax
from jax import lax
from jax._src.lax.parallel import all_gather_invariant as _agi

__all__ = [
    "shard_map", "pvary", "vma_of", "pvary_missing", "match_vma",
    "out_struct", "all_gather_invariant", "make_mesh",
]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map``.  Call sites that pass ``check_vma=False`` are pure
    data movement with no AD inside."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def vma_of(x) -> frozenset:
    """Mesh axes ``x`` is typed as varying over (empty outside shard_map)."""
    return frozenset(getattr(jax.typeof(x), "vma", ()) or ())


def pvary(x, axes):
    """Mark ``x`` varying (``lax.pcast(..., to="varying")``) over the axes of
    ``axes`` it does not already vary over; ``None``/empty names are
    dropped.  Needed wherever fresh zeros meet mesh-varying values in a scan
    carry under shard_map's vma typing."""
    axes = tuple(a for a in axes if a) if isinstance(axes, (tuple, list)) \
        else ((axes,) if axes else ())
    need = tuple(a for a in axes if a not in vma_of(x))
    return lax.pcast(x, need, to="varying") if need else x


pvary_missing = pvary     # the name most call sites use


def match_vma(value, ref):
    """Give ``value`` the same varying-manual-axes typing as ``ref``."""
    return pvary_missing(value, tuple(vma_of(ref)))


def out_struct(shape, dtype, *like) -> jax.ShapeDtypeStruct:
    """A ``pallas_call`` out_shape that varies over every mesh axis any of
    ``like`` varies over (a kernel is collective-free per-device compute)."""
    vma = frozenset().union(*(vma_of(x) for x in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _lower_cpu_pallas_without_vma_check() -> None:
    """Let interpret-mode kernels lower inside a ``check_vma=True`` shard_map.

    On the CPU a ``pallas_call`` lowers through Pallas' HLO interpreter,
    which re-traces the kernel body on the enclosing shard_map's vma-typed
    operands while its own grid indices carry no vma; the first ref slice
    then fails the vma match.  vma is typing only, so the interpreter is
    handed the same avals without it and emits the same HLO.  The rule is
    registered for the CPU platform alone: the TPU lowering (Mosaic) is
    untouched.
    """
    from jax._src.interpreters import mlir
    from jax._src.pallas import pallas_call as _pc

    def strip(avals):
        return [a.update(vma=frozenset()) if getattr(a, "vma", None) else a
                for a in avals]

    def rule(ctx, *args, **params):
        ctx = ctx.replace(avals_in=strip(ctx.avals_in),
                          avals_out=strip(ctx.avals_out))
        return _pc._pallas_call_lowering(ctx, *args, **params)

    mlir.register_lowering(_pc.pallas_call_p, rule, platform="cpu")


_lower_cpu_pallas_without_vma_check()


def all_gather_invariant(x, axis_name, *, axis: int = 0, tiled: bool = False):
    """Varying -> invariant all-gather (transposes to a slice, no psum)."""
    return _agi(x, axis_name, axis=axis, tiled=tiled)


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis Auto (shard_map handles the manual
    axes; JAX's own default is Explicit)."""
    auto = (jax.sharding.AxisType.Auto,) * len(tuple(axis_names))
    return jax.make_mesh(axis_shapes, axis_names, axis_types=auto,
                         devices=devices)
