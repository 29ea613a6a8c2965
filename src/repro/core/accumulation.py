"""Gradient-accumulation schedules: standard (batch-major) vs layered (§3).

Both schedules compute *identical* gradients (tested to tolerance); they
differ in loop order and therefore in where the ZeRO-3 collectives land:

  standard   scan over micro-batches { scan over layers { all_gather(w_l);
             compute } + backward { all_gather(w_l); psum_scatter(dw_l) } }
             -> 3 * L * M data-axis collectives per step.

  layered    scan over layers { all_gather(w_l); scan over micro-batches
             { compute } } + reverse scan { all_gather(w_l); scan over
             micro-batches { vjp } ; psum_scatter(dw_l) }
             -> 3 * L data-axis collectives per step (the paper's n_mu x
             reduction, fig. 2), at the cost of keeping the per-(layer,
             micro-batch) boundary activation checkpoints.

Without the ZeRO partition the same loop inversion spreads the gradient
psum evenly over the backward pass (fig. 1) instead of concentrating it
at the end of the last micro-batch.

Everything here runs INSIDE shard_map: parameters arrive as local shards
(partitioned chunks or model-local tensors), the batch arrives micro-batched
``[M, mb_local, ...]``, and all collectives are explicit.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import partition as zp
from repro.models import transformer as T
from repro.models.common import AxisCtx, ModelConfig, apply_norm
from repro.obs.trace import phase

PyTree = Any

OUTER_KEYS = ("embed", "shared", "final_norm", "head")


@dataclasses.dataclass(frozen=True)
class AccumConfig:
    method: str = "layered"        # "standard" | "layered"
    partitioned: bool = True       # ZeRO-3 partition over `data`
    n_microbatches: int = 1
    remat: bool = True
    # None = inherit ModelConfig.kernels (default on): the attention blocks
    # run the differentiable Pallas flash kernel; True/False force it.
    use_pallas: bool | None = None
    # TPU adaptation of the paper's checkpoint offload (§2.5/§8.2): the layered
    # schedule must keep every (layer x micro-batch) boundary activation; the
    # paper offloads them to CPU, here they are instead sharded over the
    # `model` axis along the sequence dim (Megatron-style sequence-parallel
    # activations) and re-gathered per layer in the backward pass.
    seq_shard_ckpt: bool = True
    # partition the state over ("pod", "data") instead of "data" alone — the
    # paper's slow-interconnect (§8.3) scenario; halves per-device state on
    # the multi-pod mesh at the cost of per-layer cross-pod gathers, which is
    # exactly the traffic layered accumulation makes affordable.
    span_pods: bool = False
    # beyond-paper (EXPERIMENTS §Perf): keep MoE expert weights RESIDENT in
    # their compute layout (expert dim over `data`) instead of ZeRO chunks:
    # no per-layer expert gathers and no expert-grad reduction at all —
    # tokens travel to experts via all_to_all instead of weights to tokens.
    expert_parallel: bool = False
    # beyond-paper: reduce-scatter gradients in bf16 (halves the wire bytes
    # of the data-axis reduction; Adam still accumulates fp32 in storage)
    reduce_dtype: str = "float32"


def split_tree(params: PyTree) -> tuple[PyTree, PyTree]:
    outer = {k: v for k, v in params.items() if k != "layers"}
    return outer, params["layers"]


# ---------------------------------------------------------------------------
# Gather / reduce adapters (partitioned vs replicated storage)
# ---------------------------------------------------------------------------
def _tree_specs_layer(cfg: ModelConfig, tp: int) -> PyTree:
    return T.layer_specs(cfg, tp)


def make_adapters(cfg: ModelConfig, axis: AxisCtx, acc: AccumConfig,
                  full_template: PyTree):
    """Returns (gather_outer, gather_layer, reduce_outer_grad,
    reduce_layer_grad, layer_storage_of, outer_specs, layer_specs_tree)."""
    tp = axis.tp
    specs = T.param_specs(cfg, tp)
    outer_specs = {k: v for k, v in specs.items() if k != "layers"}
    lspecs = _tree_specs_layer(cfg, tp)
    outer_tmpl = {k: v for k, v in full_template.items() if k != "layers"}
    # per-layer local template (strip the stacking dim)
    layer_tmpl_full = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
        full_template["layers"])

    def lshape(tmpl, spec):
        return zp.local_shape(tmpl.shape, spec, tp)

    dtype = jnp.dtype(cfg.dtype)
    dp_axes = (axis.data, axis.pod)
    span = acc.span_pods and axis.pod is not None
    part_axes = ("pod", "data") if span else axis.data
    n_part = axis.dp if span else axis.ndata

    def vary(x):
        return zp.pvary_missing(x, dp_axes)

    ep = acc.expert_parallel and cfg.is_moe

    if acc.partitioned:
        def gather_outer(outer):
            return jax.tree.map(
                lambda s, t, sp: vary(zp.gather_local(
                    s, part_axes, lshape(t, sp), dtype, stacked=False)),
                outer, outer_tmpl, outer_specs)

        def gather_layer(layer_sliced):
            def g(path, s, t, sp):
                if ep and zp.is_expert_path(path):
                    return vary(s.astype(dtype))     # resident — no gather
                return vary(zp.gather_local(s, part_axes, lshape(t, sp),
                                            dtype, stacked=False))
            return jax.tree_util.tree_map_with_path(
                g, layer_sliced, layer_tmpl_full, lspecs)

        def _scatter(gg):
            return zp.scatter_grad_local(gg, part_axes, n_part, stacked=False,
                                         pod_axis=None if span else axis.pod,
                                         wire_dtype=jnp.dtype(acc.reduce_dtype))

        def reduce_outer_grad(g):
            return jax.tree.map(_scatter, g)

        def reduce_layer_grad(g):
            def r(path, gg):
                if ep and zp.is_expert_path(path):
                    return gg.astype(jnp.float32)    # data-local — no collective
                return _scatter(gg)
            return jax.tree_util.tree_map_with_path(r, g)
    else:
        def gather_outer(outer):
            return jax.tree.map(lambda s: vary(s.astype(dtype)), outer)

        def gather_layer(layer_sliced):
            return jax.tree.map(lambda s: vary(s.astype(dtype)), layer_sliced)

        def _reduce(gg):
            gg = gg.astype(jnp.float32)
            if axis.data:
                gg = lax.psum(gg, axis.data)
            if axis.pod:
                gg = lax.psum(gg, axis.pod)
            return gg

        def reduce_outer_grad(g):
            return jax.tree.map(_reduce, g)

        def reduce_layer_grad(g):
            return jax.tree.map(_reduce, g)

    return (phase("zero_gather")(gather_outer),
            phase("zero_gather")(gather_layer),
            phase("zero_reduce")(reduce_outer_grad),
            phase("zero_reduce")(reduce_layer_grad), outer_specs, lspecs)


# ---------------------------------------------------------------------------
# The gradient functions
# ---------------------------------------------------------------------------
def make_grad_fn(cfg: ModelConfig, axis: AxisCtx, acc: AccumConfig,
                 full_template: PyTree, *,
                 layer_update: Callable | None = None) -> Callable:
    """Returns grad_fn(storage, batch) -> (grads_like_storage, metrics);
    call INSIDE shard_map.  ``batch`` leaves are [M, mb_local, ...].

    ``layer_update`` (layered method only): the paper's §C.3 "update the
    weights as soon as possible" — called as
    ``layer_update(p_slice, mu_slice, nu_slice, dw_slice) -> (p', mu', nu')``
    right after each layer's gradient is reduce-scattered in the backward
    scan, so the full-size fp32 gradient staging buffer never exists.  The
    grad_fn then takes (storage, opt_layers, batch) and returns
    ((outer_grads, new_layers, new_opt_layers), metrics).
    """
    (gather_outer, gather_layer, reduce_outer_grad, reduce_layer_grad,
     outer_specs, lspecs) = make_adapters(cfg, axis, acc, full_template)
    windows, flags, _ = T.layer_tables(cfg)
    M = acc.n_microbatches
    aux_w = cfg.router_aux_weight
    dp_axes = (axis.data, axis.pod)

    def vary_dp(x):
        return zp.pvary_missing(x, dp_axes)

    def grad_zeros(tree, specs):
        """f32 zero accumulators whose vma matches the gradient leaves:
        varying over data/pod always; over model iff the leaf is sharded."""
        def z(leaf, sp):
            axes = list(dp_axes)
            if axis.model and not zp.model_replicated(sp):
                axes.append(axis.model)
            return zp.pvary_missing(jnp.zeros(leaf.shape, jnp.float32), axes)
        return jax.tree.map(z, tree, specs)

    def n_global_tokens(batch):
        n = jnp.sum(batch["mask"].astype(jnp.float32))
        if axis.data:
            n = lax.psum(n, axis.data)
        if axis.pod:
            n = lax.psum(n, axis.pod)
        return n

    def dp_total():
        n = 1.0
        if axis.data:
            n = n * lax.psum(1.0, axis.data)
        if axis.pod:
            n = n * lax.psum(1.0, axis.pod)
        return n

    @phase("fwd")
    def mb_loss(outer_g, layers_storage, mb, inv_n, aux_scale):
        """One micro-batch forward + loss given gathered outer params and the
        *storage-layout* layer params (gathered inside, per layer)."""
        x, positions = T.embed_inputs(cfg, outer_g, mb, axis)

        def body(carry, xs):
            x, aux = carry
            lp_store, w, fl = xs
            lp = gather_layer(lp_store)
            x, a = T.apply_layer(cfg, lp, outer_g.get("shared", {}), x,
                                 positions=positions, window=w, shared_flag=fl,
                                 axis=axis, use_pallas=acc.use_pallas)
            return (x, aux + a), None

        if acc.remat:
            body = jax.checkpoint(body)
        aux0 = zp.pvary_missing(jnp.zeros((), jnp.float32),
                                (axis.data, axis.pod))
        (x, aux), _ = lax.scan(body, (x, aux0),
                               (layers_storage, windows, flags))
        x = apply_norm(cfg, outer_g["final_norm"], x)
        nll = T.head_loss(cfg, outer_g, x, mb, axis)
        loss = nll * inv_n + aux_w * aux * aux_scale
        return loss, (nll, aux)

    # ------------------------------------------------------------------
    # standard (batch-major) gradient accumulation
    # ------------------------------------------------------------------
    # Phases: all "fwd" but the gradient sums ("bwd") and the ZeRO adapters;
    # under jax.grad the backward reads transpose(...) and the recomputed
    # forward rematted_computation in op_name.
    @phase("fwd")
    def standard_grad(storage, batch):
        inv_n = 1.0 / n_global_tokens(batch)
        aux_scale = 1.0 / (M * cfg.num_layers * dp_total())
        if not acc.partitioned:
            # mark the master copies data-varying so per-micro-batch grads stay
            # local partials; the single explicit reduction happens at the end
            storage = jax.tree.map(vary_dp, storage)

        def loss_one(storage, mb):
            outer_s, layers_s = split_tree(storage)
            outer_g = gather_outer(outer_s)   # per micro-batch (standard ZeRO)
            return mb_loss(outer_g, layers_s, mb, inv_n, aux_scale)

        gfun = jax.grad(loss_one, has_aux=True)

        def body(gacc, mb):
            g, (nll, aux) = gfun(storage, mb)
            with phase("bwd"):
                gacc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                    gacc, g)
            return gacc, (nll, aux)

        sspecs = dict(
            {k: v for k, v in T.param_specs(cfg, axis.tp).items() if k != "layers"},
            layers=T.param_specs(cfg, axis.tp)["layers"])
        with phase("bwd"):
            zeros = grad_zeros(storage, sspecs)
        grads, (nlls, auxs) = lax.scan(body, zeros, batch)
        if not acc.partitioned:
            outer_grads, layer_grads = split_tree(grads)
            grads = dict(reduce_outer_grad(outer_grads),
                         layers=reduce_layer_grad(layer_grads))
        return grads, _metrics(nlls, auxs, batch)

    # ------------------------------------------------------------------
    # layered (layer-major) gradient accumulation — the paper's §3
    # ------------------------------------------------------------------
    # Phases: "fwd" but for the reverse layer scan and the embed backward
    # ("bwd": jax.vjp's recomputed forward reads jvp(...) and its backward
    # transpose(jvp(...)) in op_name), the head's gradient sums ("bwd") and
    # the ZeRO adapters.
    @phase("fwd")
    def layered_grad(storage, batch, opt_layers=None):
        inv_n = 1.0 / n_global_tokens(batch)
        aux_scale = 1.0 / (M * cfg.num_layers * dp_total())
        outer_s, layers_s = split_tree(storage)
        outer_g = gather_outer(outer_s)          # gathered ONCE per step
        shared_g = outer_g.get("shared", {})

        # ---- forward: embed each micro-batch ------------------------------
        def embed_one(_, mb):
            return None, T.embed_inputs(cfg, outer_g, mb, axis)

        _, (X, POS) = lax.scan(embed_one, None, batch)  # [M,mb,S,D], [M,mb,S]

        # ---- forward: layer-major scan, keep boundary checkpoints ---------
        seq_len = X.shape[-2]
        shard_ckpt = (acc.seq_shard_ckpt and axis.model and axis.tp > 1
                      and seq_len % axis.tp == 0)

        def ckpt_slice(x_all):
            """Shard the kept checkpoint over `model` along the seq dim."""
            if not shard_ckpt:
                return x_all
            chunk = seq_len // axis.tp
            start = lax.axis_index(axis.model) * chunk
            return lax.dynamic_slice_in_dim(x_all, start, chunk, axis=-2)

        def ckpt_restore(ck):
            if not shard_ckpt:
                return ck
            # Varying -> Invariant gather: transposes to a dynamic_slice, so
            # backward typing matches the unsharded path exactly (no psum).
            return compat.all_gather_invariant(ck, axis.model,
                                               axis=ck.ndim - 2, tiled=True)

        def fwd_layer(carry, xs):
            x_all, aux = carry                    # [M, mb, S, D]
            lp_store, w, fl = xs
            lp = gather_layer(lp_store)           # all_gather once per layer

            def one_mb(carry2, xp):
                x, pos = xp
                x2, a = T.apply_layer(cfg, lp, shared_g, x, positions=pos,
                                      window=w, shared_flag=fl, axis=axis,
                                      use_pallas=acc.use_pallas)
                return carry2 + a, x2

            aux_l, x_new = lax.scan(one_mb, vary_dp(jnp.zeros((), jnp.float32)),
                                    (x_all, POS))
            return (x_new, aux + aux_l), ckpt_slice(x_all)  # ys: checkpoint

        (xL, aux_total), CKPT = lax.scan(
            fwd_layer, (X, vary_dp(jnp.zeros((), jnp.float32))),
            (layers_s, windows, flags))

        # ---- head: loss + dx per micro-batch -------------------------------
        tied = cfg.tie_embeddings

        def head_one(mb, x):
            def f(fn_p, head_p, embed_p, x):
                og = dict(outer_g, final_norm=fn_p, embed=embed_p)
                if not tied:
                    og["head"] = head_p
                h = apply_norm(cfg, fn_p, x)
                nll = T.head_loss(cfg, og, h, mb, axis)
                return nll * inv_n, nll

            if tied:
                loss, vjp, nll = jax.vjp(
                    lambda fn_p, embed_p, x: f(fn_p, None, embed_p, x),
                    outer_g["final_norm"], outer_g["embed"], x, has_aux=True)
                dfn, demb, dx = vjp(zp.match_vma(jnp.ones((), loss.dtype), loss))
                dhead = None
            else:
                loss, vjp, nll = jax.vjp(
                    f, outer_g["final_norm"], outer_g["head"], outer_g["embed"],
                    x, has_aux=True)
                dfn, dhead, demb, dx = vjp(zp.match_vma(jnp.ones((), loss.dtype), loss))
            return (dfn, dhead, demb, dx), nll

        def head_body(acc2, xs):
            mb, x = xs
            (dfn, dhead, demb, dx), nll = head_one(mb, x)
            dfn_a, dhead_a, demb_a = acc2
            add = lambda a, b: jax.tree.map(
                lambda u, v: u + v.astype(jnp.float32), a, b)
            with phase("bwd"):
                return (add(dfn_a, dfn),
                        add(dhead_a, dhead) if dhead is not None else None,
                        add(demb_a, demb)), (dx.astype(jnp.dtype(cfg.dtype)),
                                             nll)

        with phase("bwd"):
            head_acc0 = (
                grad_zeros(outer_g["final_norm"], outer_specs["final_norm"]),
                None if tied else grad_zeros(outer_g["head"],
                                             outer_specs["head"]),
                grad_zeros(outer_g["embed"], outer_specs["embed"]))
        (dfn, dhead, demb), (dX, nlls) = lax.scan(head_body, head_acc0,
                                                  (batch, xL))

        # ---- backward: reverse layer-major scan -----------------------------
        aux_ct = jnp.asarray(aux_w * aux_scale, jnp.float32)

        def bwd_layer(carry, xs):
            dx_all, dshared_acc = carry
            if layer_update is not None:
                lp_store, w, fl, ck, mu_l, nu_l = xs
            else:
                lp_store, w, fl, ck = xs
            x_in_all = ckpt_restore(ck)
            lp = gather_layer(lp_store)           # all_gather once per layer

            def one_mb(dw_acc, xs2):
                x_in, pos, dx = xs2

                def f(lp, shared, x):
                    return T.apply_layer(cfg, lp, shared, x, positions=pos,
                                         window=w, shared_flag=fl, axis=axis,
                                         use_pallas=acc.use_pallas)

                (_, aux_p), vjp = jax.vjp(f, lp, shared_g, x_in)
                dlp, dsh, dxin = vjp((dx.astype(jnp.dtype(cfg.dtype)),
                                      zp.match_vma(aux_ct, aux_p)))
                dw_l, dsh_acc = dw_acc
                add = lambda a, b: jax.tree.map(
                    lambda u, v: u + v.astype(jnp.float32), a, b)
                return (add(dw_l, dlp), add(dsh_acc, dsh)), dxin

            (dw_l, dshared_acc), dx_prev = lax.scan(
                one_mb, (grad_zeros(lp, lspecs), dshared_acc),
                (x_in_all, POS, dx_all))
            dw_store = reduce_layer_grad(dw_l)    # psum_scatter once per layer
            if layer_update is not None:
                # fused optimizer: consume the layer gradient immediately
                ys = jax.tree.map(layer_update, lp_store, mu_l, nu_l, dw_store)
                new_p = jax.tree.map(lambda t: t[0], ys,
                                     is_leaf=lambda x: isinstance(x, tuple))
                new_mu = jax.tree.map(lambda t: t[1], ys,
                                      is_leaf=lambda x: isinstance(x, tuple))
                new_nu = jax.tree.map(lambda t: t[2], ys,
                                      is_leaf=lambda x: isinstance(x, tuple))
                return (dx_prev, dshared_acc), (new_p, new_mu, new_nu)
            return (dx_prev, dshared_acc), dw_store

        with phase("bwd"):
            shared_zero = grad_zeros(shared_g, outer_specs.get("shared", {}))
            if layer_update is not None:
                mu_l, nu_l = opt_layers
                (dX0, dshared), (new_layers, new_mu, new_nu) = lax.scan(
                    bwd_layer, (dX, shared_zero),
                    (layers_s, windows, flags, CKPT, mu_l, nu_l), reverse=True)
            else:
                (dX0, dshared), layer_grads = lax.scan(
                    bwd_layer, (dX, shared_zero),
                    (layers_s, windows, flags, CKPT), reverse=True)

        # ---- embed backward -------------------------------------------------
        def emb_body(demb_acc, xs):
            mb, dx = xs

            def f(embed_p):
                x, _ = T.embed_inputs(cfg, dict(outer_g, embed=embed_p), mb, axis)
                return x

            _, vjp = jax.vjp(f, outer_g["embed"])
            (de,) = vjp(dx.astype(jnp.dtype(cfg.dtype)))
            return jax.tree.map(lambda u, v: u + v.astype(jnp.float32),
                                demb_acc, de), None

        with phase("bwd"):
            demb, _ = lax.scan(emb_body, demb, (batch, dX0))

        outer_grads = {"embed": demb, "final_norm": dfn, "shared": dshared}
        if dhead is not None:
            outer_grads["head"] = dhead
        outer_grads = {k: v for k, v in outer_grads.items()
                       if k in outer_s}
        metrics = _metrics(nlls, aux_total / cfg.num_layers, batch)
        if layer_update is not None:
            return (reduce_outer_grad(outer_grads),
                    new_layers, (new_mu, new_nu)), metrics
        grads = dict(reduce_outer_grad(outer_grads), layers=layer_grads)
        return grads, metrics

    def _metrics(nlls, auxs, batch):
        nll = jnp.sum(nlls)
        ntok = jnp.sum(batch["mask"].astype(jnp.float32))
        aux = jnp.mean(jnp.asarray(auxs))
        if axis.data:
            nll, ntok = lax.psum(nll, axis.data), lax.psum(ntok, axis.data)
            aux = lax.psum(aux, axis.data)
        if axis.pod:
            nll, ntok = lax.psum(nll, axis.pod), lax.psum(ntok, axis.pod)
            aux = lax.psum(aux, axis.pod)
        return {"loss": nll / ntok, "ntok": ntok, "aux": aux / axis.dp}

    return layered_grad if acc.method == "layered" else standard_grad
