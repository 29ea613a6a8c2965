"""AdamW on the training-state storage layout (replicated or ZeRO-partitioned).

The optimizer is strictly element-wise, so it runs unchanged on either
storage layout: full fp32 master leaves, or the partitioned flat chunks of
core/partition.py.  With the partition, each device updates only its own
1/n_data shard of the state — ZeRO stage 3 semantics; there is no optimizer
collective at all (the gradients already arrived reduce-scattered).

Global-norm clipping needs one scalar reduction; which axes to sum over is
layout-dependent, so the caller passes a ``sq_reduce`` callback.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.obs.trace import phase

PyTree = Any


@dataclasses.dataclass(frozen=True)
class AdamConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0        # 0 disables clipping
    warmup_steps: int = 100
    decay_steps: int = 10_000
    min_lr_ratio: float = 0.1
    moment_dtype: str = "float32"   # "bfloat16" halves optimizer-state memory


def schedule(c: AdamConfig, step: jnp.ndarray) -> jnp.ndarray:
    """Linear warmup + cosine decay."""
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(c.warmup_steps, 1), 1.0)
    t = jnp.clip((step - c.warmup_steps) / jnp.maximum(c.decay_steps, 1), 0.0, 1.0)
    cos = c.min_lr_ratio + (1 - c.min_lr_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * t))
    return c.lr * warm * cos


def adam_init(storage: PyTree, *, moment_dtype="float32") -> PyTree:
    """Zero moments.  Where the storage is sharded on a mesh, each moment is
    made directly in its storage leaf's sharding and the step counter
    replicated on that mesh, so no device ever holds a whole moment."""
    dt = jnp.dtype(moment_dtype)

    def placed(l):
        s = getattr(l, "sharding", None)
        return s if isinstance(s, NamedSharding) else None

    zeros = lambda t: jax.tree.map(
        lambda l: jnp.zeros(l.shape, dt, device=placed(l)), t)
    where = [s for s in map(placed, jax.tree.leaves(storage)) if s]
    step_at = NamedSharding(where[0].mesh, P()) if where else None
    return {"mu": zeros(storage), "nu": zeros(storage),
            "step": jnp.zeros((), jnp.int32, device=step_at)}


@phase("optimizer")
def adam_step(c: AdamConfig, storage: PyTree, opt: PyTree, grads: PyTree, *,
              sq_reduce: Callable[[PyTree], jnp.ndarray] | None = None,
              fused: bool | Callable = False) -> tuple[PyTree, PyTree, dict]:
    """One AdamW update.  All trees share the storage layout (fp32).

    ``fused=True`` dispatches each leaf to the one-pass Pallas chunk-update
    kernel (kernels/adamw.py) — intended for the ZeRO-partitioned flat-chunk
    layout (incl. the pipeline's ``[S, K, n_model, n_data, chunk]`` stage
    stacks), where it turns the ~6 HBM round-trips of the tree-map update
    into one read + one write per state tensor.  ``fused`` may also be a
    ``path -> bool`` predicate for mixed storage (e.g. chunked layer stacks
    alongside full replicated outer leaves).  The grad-clip scale is folded
    into the kernel instead of materialising a scaled gradient tree.  Runs
    the exact float ops of the unfused path (equal to within FMA
    contraction).
    """
    step = opt["step"] + 1
    lr = schedule(c, step)
    any_fused = bool(fused) if isinstance(fused, bool) else True
    if c.grad_clip > 0 and sq_reduce is not None:
        gnorm = jnp.sqrt(sq_reduce(grads) + 1e-16)
        gscale = jnp.minimum(1.0, c.grad_clip / gnorm)
        if not any_fused:
            grads = jax.tree.map(lambda g: g * gscale, grads)
    else:
        gnorm = jnp.zeros(())
        gscale = jnp.ones(())
    b1c = 1 - c.b1 ** step.astype(jnp.float32)
    b2c = 1 - c.b2 ** step.astype(jnp.float32)

    mdt = jnp.dtype(c.moment_dtype)
    pre_scaled = any_fused and c.grad_clip > 0 and sq_reduce is not None

    def upd_unfused(p, m, v, g):
        if pre_scaled:
            # clip not folded into a tree-wide grad scale above (the fused
            # leaves take it via the kernel operand); apply it per leaf here
            g = g * gscale
        m32 = c.b1 * m.astype(jnp.float32) + (1 - c.b1) * g
        v32 = c.b2 * v.astype(jnp.float32) + (1 - c.b2) * jnp.square(g)
        mh = m32 / b1c
        vh = v32 / b2c
        p = p - lr * (mh / (jnp.sqrt(vh) + c.eps) + c.weight_decay * p)
        return p, m32.astype(mdt), v32.astype(mdt)

    if any_fused:
        from repro.kernels import ops as kops
        scalars = jnp.stack([lr, b1c, b2c, gscale])

        def upd_fused(p, m, v, g):
            return kops.fused_adamw(p, m, v, g, scalars, b1=c.b1, b2=c.b2,
                                    eps=c.eps, wd=c.weight_decay)

    def upd_for(path):
        use = fused if isinstance(fused, bool) else fused(path)
        return upd_fused if use else upd_unfused

    flat_pp, treedef = jax.tree_util.tree_flatten_with_path(storage)
    paths = [p for p, _ in flat_pp]
    flat_p = [l for _, l in flat_pp]
    flat_m = treedef.flatten_up_to(opt["mu"])
    flat_v = treedef.flatten_up_to(opt["nu"])
    flat_g = treedef.flatten_up_to(grads)
    out = [upd_for(path)(p, m, v, g)
           for path, p, m, v, g in zip(paths, flat_p, flat_m, flat_v, flat_g)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    return new_p, {"mu": new_m, "nu": new_v, "step": step}, \
        {"lr": lr, "grad_norm": gnorm}
