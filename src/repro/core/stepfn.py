"""Builds the jitted, shard_map'd train / serve steps for a given mesh.

This is the glue between the mesh-level world (jit, shardings, device arrays)
and the collective-explicit world inside shard_map (core/accumulation.py,
the model code).  The dry-run (launch/dryrun.py) lowers exactly these steps.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import partition as zp
from repro.core.accumulation import AccumConfig, make_grad_fn, split_tree
from repro.models import transformer as T
from repro.models.common import AxisCtx, ModelConfig
from repro.obs.trace import phase
from repro.optim.adam import AdamConfig, adam_init, adam_step

PyTree = Any


# ---------------------------------------------------------------------------
# Mesh <-> axis context
# ---------------------------------------------------------------------------
def axis_ctx(mesh: Mesh, *, seq: str | None = None) -> AxisCtx:
    names = mesh.axis_names
    shape = dict(zip(names, mesh.devices.shape))
    data = "data" if "data" in names else None
    model = "model" if "model" in names else None
    pod = "pod" if "pod" in names else None
    tp = shape.get("model", 1)
    ndata = shape.get("data", 1)
    dp = ndata * shape.get("pod", 1)
    return AxisCtx(data=data, model=model, pod=pod, seq=seq,
                   tp=tp, dp=dp, ndata=ndata)


def batch_specs(cfg: ModelConfig, axis: AxisCtx, *, microbatched: bool) -> PyTree:
    """PartitionSpecs for a batch dict (leading micro-batch dim optional)."""
    dp_axes = tuple(a for a in (axis.pod, axis.data) if a)
    b = P(*((None,) if microbatched else ()), dp_axes)
    specs = {"labels": b, "mask": b}
    if cfg.input_mode == "embeddings":
        specs["embeds"] = b
    elif cfg.input_mode == "vlm":
        specs["tokens"] = b
        specs["vision_embeds"] = b
    else:
        specs["tokens"] = b
    return specs


def storage_specs(cfg: ModelConfig, axis: AxisCtx, partitioned: bool,
                  *, span_pods: bool = False,
                  expert_resident: bool = False) -> PyTree:
    full = T.param_specs(cfg, axis.tp)
    if not partitioned:
        return full
    return zp.partitioned_specs(full,
                                span_pods=span_pods and axis.pod is not None,
                                expert_resident=expert_resident)


# ---------------------------------------------------------------------------
# State construction
# ---------------------------------------------------------------------------
def full_template(cfg: ModelConfig) -> PyTree:
    return jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))


def init_storage(cfg: ModelConfig, mesh: Mesh, key, *, partitioned: bool,
                 span_pods: bool = False, expert_resident: bool = False) -> PyTree:
    """Materialise the training-state storage on the mesh (test/train scale)."""
    axis = axis_ctx(mesh)
    span = span_pods and axis.pod is not None
    ep = expert_resident and cfg.is_moe
    n_part = axis.dp if span else axis.ndata
    fspecs = T.param_specs(cfg, axis.tp)
    if ep:
        # expert weights materialise directly in the resident EP layout
        def respec(path, sp):
            if zp.is_expert_path(path):
                return zp.expert_resident_spec(path)
            return sp
        fspecs = jax.tree_util.tree_map_with_path(
            respec, fspecs, is_leaf=lambda x: isinstance(x, P))
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), fspecs,
                             is_leaf=lambda x: isinstance(x, P))
    params = jax.jit(functools.partial(T.init_params, cfg),
                     out_shardings=shardings)(key)
    if not partitioned:
        return params

    tmpl = full_template(cfg)
    pspecs = zp.partitioned_specs(fspecs, span_pods=span, expert_resident=ep)

    def convert(params):  # inside shard_map: local full leaves -> chunks
        di = lax.axis_index(axis.data) if axis.data else 0
        if span:
            di = lax.axis_index("pod") * axis.ndata + di

        def conv(path, leaf):
            if ep and zp.is_expert_path(path):
                return leaf.astype(jnp.float32)   # already resident-local
            return zp.partition_local(leaf, n_part, di,
                                      stacked=zp.is_stacked_path(path))
        return jax.tree_util.tree_map_with_path(conv, params)

    out_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                             is_leaf=lambda x: isinstance(x, P))
    fn = compat.shard_map(convert, mesh=mesh, in_specs=(fspecs,), out_specs=pspecs)
    return jax.jit(fn, out_shardings=out_shard)(params)


def gather_params(cfg: ModelConfig, mesh: Mesh, storage: PyTree) -> PyTree:
    """Partitioned storage -> full (model-sharded) bf16 params, for eval."""
    axis = axis_ctx(mesh)
    fspecs = T.param_specs(cfg, axis.tp)
    pspecs = zp.partitioned_specs(fspecs)
    tmpl = full_template(cfg)

    def gather(storage):
        def conv(path, leaf, t, sp):
            shape = zp.local_shape(t.shape, sp, axis.tp)
            return zp.gather_local(leaf, axis.data, shape, jnp.dtype(cfg.dtype),
                                   stacked=zp.is_stacked_path(path))
        return jax.tree_util.tree_map_with_path(conv, storage, tmpl, fspecs)

    # values are replicated after the all_gather but stay typed "varying";
    # this is pure data movement (no AD), so the vma check is waived.
    fn = compat.shard_map(gather, mesh=mesh, in_specs=(pspecs,), out_specs=fspecs,
                       check_vma=False)
    return jax.jit(fn)(storage)


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------
def make_sq_reduce(cfg: ModelConfig, axis: AxisCtx, partitioned: bool,
                   *, span_pods: bool = False, expert_resident: bool = False):
    """Global sum-of-squares over a gradient tree in storage layout."""
    fspecs = T.param_specs(cfg, axis.tp)

    def sq_reduce(grads):
        shard_tot = jnp.zeros((), jnp.float32)   # needs psum over model
        repl_tot = jnp.zeros((), jnp.float32)
        data_tot = jnp.zeros((), jnp.float32)    # resident EP: psum data+model
        flat_g = jax.tree_util.tree_leaves_with_path(grads)
        flat_s = jax.tree.leaves(fspecs, is_leaf=lambda x: isinstance(x, P))
        for (path, g), sp in zip(flat_g, flat_s):
            s = jnp.sum(jnp.square(g.astype(jnp.float32)))
            if expert_resident and zp.is_expert_path(path):
                data_tot += s
            elif zp.model_replicated(sp) or not axis.model:
                repl_tot += s
            else:
                shard_tot += s
        tot = (lax.psum(shard_tot, axis.model) if axis.model else shard_tot) + repl_tot
        if axis.model and axis.data:
            tot = tot + lax.psum(lax.psum(data_tot, axis.model), axis.data)                 / (1.0 if partitioned else axis.ndata)
        else:
            tot = tot + data_tot
        if partitioned and axis.data:
            tot = lax.psum(tot, axis.data)
            if span_pods and axis.pod:
                tot = lax.psum(tot, axis.pod)
        return tot

    return sq_reduce


def build_train_step(cfg: ModelConfig, mesh: Mesh, acc: AccumConfig,
                     opt_cfg: AdamConfig, *, donate: bool = True):
    """Returns jitted ``step(storage, opt_state, batch) -> (storage, opt,
    metrics)``.  ``batch`` leaves: [M, B_global/M, ...] sharded over batch."""
    axis = axis_ctx(mesh)
    ep = acc.expert_parallel and cfg.is_moe
    if ep:
        axis = dataclasses.replace(axis, expert="data")
    tmpl = full_template(cfg)
    grad_fn = make_grad_fn(cfg, axis, acc, tmpl)
    sq_reduce = make_sq_reduce(cfg, axis, acc.partitioned,
                               span_pods=acc.span_pods, expert_resident=ep)

    sspecs = storage_specs(cfg, axis, acc.partitioned,
                           span_pods=acc.span_pods, expert_resident=ep)
    ospecs = {"mu": sspecs, "nu": sspecs, "step": P()}
    bspecs = batch_specs(cfg, axis, microbatched=True)
    mspecs = {"loss": P(), "ntok": P(), "aux": P(), "lr": P(), "grad_norm": P()}

    # the fused one-pass AdamW chunk kernel targets the flat fp32 partition
    # chunks; replicated full-leaf storage keeps the tree-map update
    fused_opt = cfg.kernels and acc.partitioned

    def step(storage, opt, batch):
        grads, metrics = grad_fn(storage, batch)
        storage, opt, om = adam_step(opt_cfg, storage, opt, grads,
                                     sq_reduce=sq_reduce, fused=fused_opt)
        metrics = dict(metrics, **om)
        return storage, opt, metrics

    fn = compat.shard_map(step, mesh=mesh,
                       in_specs=(sspecs, ospecs, bspecs),
                       out_specs=(sspecs, ospecs, mspecs))
    return jax.jit(fn, donate_argnums=(0, 1) if donate else ())


# ---------------------------------------------------------------------------
# Serve (decode) step
# ---------------------------------------------------------------------------
def cache_specs(cfg: ModelConfig, axis: AxisCtx, *, seq_shard: bool) -> PyTree:
    """Sharding of the decode cache.  Batch over data(+pod); KV heads over
    model (when kv < tp, each shard caches its own duplicated KV-head group,
    so the head dim is still model-sharded); for long-context (batch 1) the
    cache sequence dim goes over `data`(+`pod`) instead (sequence-parallel
    cache)."""
    dp = tuple(a for a in (axis.pod, axis.data) if a)
    kv_model = "model" if axis.tp > 1 else None
    specs: dict[str, Any] = {"pos": P()}
    if cfg.num_attn_slots() > 0:
        if seq_shard:
            specs["k"] = P(None, None, kv_model, dp, None)
            specs["v"] = P(None, None, kv_model, dp, None)
        else:
            specs["k"] = P(None, dp, kv_model, None, None)
            specs["v"] = P(None, dp, kv_model, None, None)
        if cfg.has_window_cache:
            # ring buffers are small (W tokens): batch-sharded, never
            # sequence-sharded
            bdim = None if seq_shard else dp
            specs["kw"] = P(None, bdim, kv_model, None, None)
            specs["vw"] = P(None, bdim, kv_model, None, None)
    m = "model" if axis.tp > 1 else None
    if cfg.block_kind == "mamba":
        specs["ssm"] = P(None, None if seq_shard else dp, m, None, None)
    elif cfg.block_kind == "rwkv":
        bdim = None if seq_shard else dp
        specs["ssm"] = {"S": P(None, bdim, m, None, None),
                        "x_tm": P(None, bdim, None),
                        "x_cm": P(None, bdim, None)}
    return specs


def globalize(local_tree: PyTree, specs: PyTree, mesh: Mesh) -> PyTree:
    """Local ShapeDtypeStructs -> global SDS with NamedShardings attached."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def up(l, sp):
        shape = list(l.shape)
        for i, ax in enumerate(tuple(sp)):
            if ax is None:
                continue
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                shape[i] *= sizes[a]
        return jax.ShapeDtypeStruct(tuple(shape), l.dtype,
                                    sharding=NamedSharding(mesh, sp))

    return jax.tree.map(up, local_tree, specs,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def build_serve_step(cfg: ModelConfig, mesh: Mesh, *, seq_shard: bool = False):
    """Returns jitted ``serve(params, cache, tokens) -> (logits, cache)``.

    ``seq_shard``: shard the KV cache over `data` (and `pod` on the multi-pod
    mesh) along the sequence dim (long_500k); the decode softmax then reduces
    over those axes.
    """
    base = axis_ctx(mesh)
    if seq_shard:
        seq_axis = ("pod", "data") if base.pod else "data"
    else:
        seq_axis = None
    expert = "data" if (cfg.is_moe and base.ndata > 1) else None
    axis = dataclasses.replace(base, seq=seq_axis, expert=expert)
    fspecs = T.serve_param_specs(cfg, axis.tp)
    cspecs = cache_specs(cfg, axis, seq_shard=seq_shard)
    dp = tuple(a for a in (axis.pod, axis.data) if a)
    tok_spec = P(None) if seq_shard else P(dp)
    logit_spec = P(None, "model") if seq_shard else P(dp, "model")

    def serve(params, cache, tokens):
        return T.decode_step(cfg, params, cache, tokens, axis)

    fn = compat.shard_map(serve, mesh=mesh,
                       in_specs=(fspecs, cspecs, tok_spec),
                       out_specs=(logit_spec, cspecs))
    return jax.jit(fn, donate_argnums=(1,))


def build_prefill_step(cfg: ModelConfig, mesh: Mesh):
    """Returns jitted ``prefill(params, cache, batch) -> (logits, cache)``.

    Batch sharded over data(+pod); KV cache written for positions [0, S).
    """
    axis = axis_ctx(mesh)
    expert = "data" if (cfg.is_moe and axis.ndata > 1) else None
    axis = dataclasses.replace(axis, expert=expert)
    fspecs = T.serve_param_specs(cfg, axis.tp)
    cspecs = cache_specs(cfg, axis, seq_shard=False)
    bspecs = batch_specs(cfg, axis, microbatched=False)
    dp = tuple(a for a in (axis.pod, axis.data) if a)
    logit_spec = P(dp, "model")

    def prefill(params, cache, batch):
        return T.prefill_step(cfg, params, cache, batch, axis)

    fn = compat.shard_map(prefill, mesh=mesh,
                       in_specs=(fspecs, cspecs, bspecs),
                       out_specs=(logit_spec, cspecs))
    return jax.jit(fn, donate_argnums=(1,))


def build_pipeline_train_step(cfg: ModelConfig, mesh: Mesh, spec,
                              opt_cfg: AdamConfig, *, partitioned: bool = True,
                              donate: bool = True, remat: bool = True,
                              table=None):
    """Returns jitted ``step(storage, opt, batch) -> (storage, opt, metrics)``
    for the pipelined training path (the paper's full method when
    ``partitioned``): any executable schedule (modular/naive/1f1b/
    interleaved) over a mesh with a leading `stage` axis, optionally composed
    with `data` and `model` axes.  The schedule is data: ``table`` is the
    simulator-emitted tick table to interpret (built from ``spec`` when not
    given — pass a plan-embedded table to execute exactly what was planned).

    Storage: outer leaves stage-replicated in their full compute layout;
    layer leaves as ``[S, K, ...]`` stage stacks (replicated) or
    ``[S, K, n_model, n_data, chunk]`` fp32 ZeRO chunks (partitioned).
    ``batch`` leaves: [M, mb_local, ...] replicated over `stage`, sharded
    over `data`.  The fused one-pass AdamW chunk kernel updates the
    partitioned layer chunks; outer leaves keep the tree-map update.
    """
    from repro.core import pipeline as pp

    axis = axis_ctx(mesh)
    assert "stage" in mesh.axis_names, mesh.axis_names
    if partitioned:
        assert axis.data, "partitioned pipeline storage needs a `data` axis"
    if table is None:
        table = spec.tick_table()
    table.validate_executable()       # fail fast, before any tracing
    tmpl = full_template(cfg)
    layer_template = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), tmpl["layers"])
    if partitioned:
        grad_fn = pp.make_partitioned_pipeline_grad_fn(
            cfg, axis, spec, layer_template, remat=remat, table=table)
    else:
        grad_fn = pp.make_pipeline_grad_fn(cfg, axis, spec, remat=remat,
                                           table=table)
    sspecs = pipeline_storage_specs(cfg, axis, partitioned)
    sq_reduce = make_pipeline_sq_reduce(cfg, axis, partitioned)
    ospecs = {"mu": sspecs, "nu": sspecs, "step": P()}
    bspecs = batch_specs(cfg, axis, microbatched=True)
    mspecs = {"loss": P(), "ntok": P(), "lr": P(), "grad_norm": P()}
    # chunk leaves take the one-pass fused AdamW kernel; the small replicated
    # outer leaves keep the tree-map update (same dispatch split as the
    # non-pipeline partitioned step)
    fused = (lambda path: zp.is_stacked_path(path)) \
        if (cfg.kernels and partitioned) else False

    def step(storage, opt, batch):
        grads, metrics = grad_fn(storage, batch)
        storage, opt, om = adam_step(opt_cfg, storage, opt, grads,
                                     sq_reduce=sq_reduce, fused=fused)
        return storage, opt, dict(metrics, **om)

    fn = compat.shard_map(step, mesh=mesh,
                       in_specs=(sspecs, ospecs, bspecs),
                       out_specs=(sspecs, ospecs, mspecs))
    return jax.jit(fn, donate_argnums=(0, 1) if donate else ())


@dataclasses.dataclass
class PipelineTickProfiler:
    """Segmented (one-dispatch-per-tick) pipeline execution, for host-timed
    tick telemetry (``obs/trace.measure_tick_timeline``).

    ``init``/``gather``/``tick``/``finish`` are jitted shard_map'd functions
    over the same storage/batch layouts as ``build_pipeline_train_step``; the
    executor state crosses the per-tick jit boundary as a flat dict whose
    leaves merge `stage`(+`data`) onto dim 0.  ``gather`` takes the local
    chunk index as a TRACED scalar (pass ``np.int32(v)``) and ``tick`` takes
    the table row as [S] arrays, so one compile each serves the whole pass.
    """
    table: Any
    segments: list
    rows_np: dict
    init: Any        # (storage, batch) -> state
    gather: Any      # (state, storage, v2) -> state
    tick: Any        # (state, storage, batch, rows) -> state
    finish: Any      # (state, storage, batch) -> (grads, metrics)
    executor: Any
    last_state: Any = None


def build_pipeline_tick_profiler(cfg: ModelConfig, mesh: Mesh, spec, *,
                                 partitioned: bool = True, table=None):
    """The opt-in segmented-execution mode of the tick-table executor: the
    same ``PipelineExecutor`` pieces the one-dispatch scan step composes, but
    wrapped one-tick-per-dispatch so the host can time every tick of the
    schedule (``obs/trace.measure_tick_timeline`` drives it, ``obs/drift``
    aligns the result against the plan's predicted timeline).  ``finish``
    runs the epilogue on the final state — the parity hook: its (grads,
    metrics) must match the scan executor's bit-for-bit ordering modulo
    float reassociation."""
    from repro.core import pipeline as pp

    axis = axis_ctx(mesh)
    assert "stage" in mesh.axis_names, mesh.axis_names
    assert axis.pod is None, "tick profiler: single-pod meshes only"
    if partitioned:
        assert axis.data, "partitioned pipeline storage needs a `data` axis"
    if table is None:
        table = spec.tick_table()
    table.validate_executable()
    tmpl = full_template(cfg)
    layer_template = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), tmpl["layers"])
    ex = pp.make_pipeline_executor(
        cfg, axis, spec, layer_template if partitioned else None,
        partitioned=partitioned, table=table)

    sspecs = pipeline_storage_specs(cfg, axis, partitioned)
    bspecs = batch_specs(cfg, axis, microbatched=True)
    mspecs = {"loss": P(), "ntok": P()}
    lspecs = T.layer_specs(cfg, axis.tp)
    outer_specs = {k: v for k, v in T.param_specs(cfg, axis.tp).items()
                   if k != "layers"}
    isP = lambda x: isinstance(x, P)   # noqa: E731

    # ---- state specs: merge stage(+data) onto dim 0 of every leaf --------
    # Carry leaves are per-stage (and per-data-shard) values with no mesh
    # dims of their own, so the jit boundary stacks the shards along dim 0.
    # A PartitionSpec shorter than the leaf rank leaves trailing dims
    # unsharded, so P(merge) covers any all-replicated leaf; only the
    # model-sharded weight/grad leaves need their full specs appended.
    merge = tuple(a for a in ("stage", axis.data) if a)

    def _merge0(sp):
        t = tuple(sp)
        d0 = t[0] if t else None
        d0 = tuple(a for a in (d0 if isinstance(d0, tuple) else (d0,)) if a)
        return P((*merge, *d0), *t[1:])

    def _outer_state_specs(spec_tree, tmpl_tree):
        return jax.tree.map(
            lambda sp, t: P(merge) if t.ndim == 0 else _merge0(sp),
            spec_tree, tmpl_tree, is_leaf=isP)

    wspec = jax.tree.map(lambda sp: P(merge, *sp), lspecs, is_leaf=isP)
    otmpl = ex.outer_tmpl
    stspecs = {
        "wbuf": wspec, "dW": wspec,
        "act": P(merge), "cot": P(merge), "dX0": P(merge),
        "dsh": _outer_state_specs(outer_specs.get("shared", {}),
                                  otmpl.get("shared", {})),
        "dfn": _outer_state_specs(outer_specs["final_norm"],
                                  otmpl["final_norm"]),
        "demb": _outer_state_specs(outer_specs["embed"], otmpl["embed"]),
        "nll": P(merge), "pos": P(merge), "inv_n": P(merge),
        "n_tok": P(merge),
    }
    if not cfg.tie_embeddings:
        stspecs["dhead"] = _outer_state_specs(outer_specs["head"],
                                              otmpl["head"])
    if ex.table.is_split:
        # zero-bubble split tables carry the dgrad->wgrad residual ring
        # buffer across the per-tick dispatch boundary
        stspecs["res_x"] = P(merge)
        stspecs["res_dy"] = P(merge)

    def _init(storage, batch):
        outer_g, shared_g = ex.outer_ctx(storage)
        X0, pos, n_tok, inv_n = ex.data_ctx(outer_g, batch)
        wbuf = ex.wbuf_init(storage)
        carry = ex.init_carry(outer_g, shared_g, X0, wbuf)
        return ex.pack_state(wbuf, carry, pos, inv_n, n_tok)

    def _gather(state, storage, v2):
        wbuf, carry, pos, inv_n, n_tok = ex.unpack_state(state)
        wbuf = ex.update_wbuf(wbuf, ex.gather_chunk(storage, v2), v2)
        return ex.pack_state(wbuf, carry, pos, inv_n, n_tok)

    def _ctx(outer_g, shared_g, batch, pos, inv_n, n_tok):
        return dict(outer_g=outer_g, shared_g=shared_g, batch=batch,
                    pos=pos, inv_n=inv_n, n_tok=n_tok)

    def _tick(state, storage, batch, rows):
        wbuf, carry, pos, inv_n, n_tok = ex.unpack_state(state)
        outer_g, shared_g = ex.outer_ctx(storage)
        ctx = _ctx(outer_g, shared_g, batch, pos, inv_n, n_tok)
        carry, _ = ex.make_tick(ctx, wbuf)(carry, rows)
        return ex.pack_state(wbuf, carry, pos, inv_n, n_tok)

    def _finish(state, storage, batch):
        wbuf, carry, pos, inv_n, n_tok = ex.unpack_state(state)
        outer_g, shared_g = ex.outer_ctx(storage)
        return ex.epilogue(_ctx(outer_g, shared_g, batch, pos, inv_n, n_tok),
                           carry, storage)

    # The state leaves stay typed "varying" in ways the per-piece signatures
    # can't all express (e.g. psummed scalars round-tripping through the
    # boundary); this is the measurement path, numerics are pinned by the
    # segmented-vs-scan parity test, so the vma check is waived (same waiver
    # as gather_params).
    def sm(fn, in_specs, out_specs):
        return jax.jit(compat.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                        out_specs=out_specs, check_vma=False))

    return PipelineTickProfiler(
        table=table, segments=ex.segments, rows_np=ex.rows_np,
        init=sm(_init, (sspecs, bspecs), stspecs),
        gather=sm(_gather, (stspecs, sspecs, P()), stspecs),
        tick=sm(_tick, (stspecs, sspecs, bspecs, P()), stspecs),
        finish=sm(_finish, (stspecs, sspecs, bspecs), (sspecs, mspecs)),
        executor=ex)


def make_pipeline_sq_reduce(cfg: ModelConfig, axis: AxisCtx,
                            partitioned: bool, *, stage_axis: str = "stage"):
    """Global grad sum-of-squares over the pipeline storage layout.

    Outer leaves are stage-replicated (and, after the grad_fn's psum,
    data-replicated); layer leaves are stage-sharded, so their contribution
    is psummed over `stage` — and over `data` too when partitioned (disjoint
    ZeRO chunks).  Model-sharded leaves psum over `model`; model-replicated
    leaves (incl. their chunk stacks) must not.
    """
    fspecs = T.param_specs(cfg, axis.tp)
    outer_specs = {k: v for k, v in fspecs.items() if k != "layers"}
    lspecs = T.layer_specs(cfg, axis.tp)

    def sq_reduce(grads):
        def split(tree, specs):
            shard = jnp.zeros((), jnp.float32)
            repl = jnp.zeros((), jnp.float32)
            flat_g = jax.tree.leaves(tree)
            flat_s = jax.tree.leaves(specs,
                                     is_leaf=lambda x: isinstance(x, P))
            for g, sp in zip(flat_g, flat_s):
                s = jnp.sum(jnp.square(g.astype(jnp.float32)))
                if zp.model_replicated(sp) or not axis.model:
                    repl += s
                else:
                    shard += s
            return shard, repl

        o_shard, o_repl = split(
            {k: v for k, v in grads.items() if k != "layers"}, outer_specs)
        l_shard, l_repl = split(grads["layers"], lspecs)
        outer_tot = (lax.psum(o_shard, axis.model) if axis.model
                     else o_shard) + o_repl
        l_tot = (lax.psum(l_shard, axis.model) if axis.model
                 else l_shard) + l_repl
        if partitioned and axis.data:
            l_tot = lax.psum(l_tot, axis.data)
        l_tot = lax.psum(l_tot, stage_axis)
        return outer_tot + l_tot

    return sq_reduce


def pipeline_storage_specs(cfg: ModelConfig, axis: AxisCtx,
                           partitioned: bool) -> PyTree:
    from repro.core import pipeline as pp
    return (pp.partitioned_stage_param_specs(cfg, axis.tp) if partitioned
            else pp.stage_param_specs(cfg, axis.tp))


def init_pipeline_storage(cfg: ModelConfig, mesh: Mesh, key, spec, *,
                          partitioned: bool) -> PyTree:
    """Materialise pipeline training-state storage on the stage mesh."""
    from repro.core import pipeline as pp

    axis = axis_ctx(mesh)
    sspecs = pipeline_storage_specs(cfg, axis, partitioned)
    lspecs = T.layer_specs(cfg, axis.tp)

    def build(key):
        params = T.init_params(cfg, key)
        outer = {k: v for k, v in params.items() if k != "layers"}
        if partitioned:
            # fp32 master everywhere: chunks by construction, outer by cast
            outer = jax.tree.map(lambda x: x.astype(jnp.float32), outer)
            layers = pp.to_partitioned_stage_stack(
                params["layers"], spec, axis.ndata, lspecs=lspecs, tp=axis.tp)
        else:
            layers = pp.to_stage_stack(params["layers"], spec)
        return dict(outer, layers=layers)

    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.jit(build, out_shardings=shardings)(key)


def build_fused_train_step(cfg: ModelConfig, mesh: Mesh, acc: AccumConfig,
                           opt_cfg: AdamConfig, *, donate: bool = True):
    """Layered training with the paper's §C.3 fused per-layer optimizer
    update: each layer's Adam step runs the moment its gradient is
    reduce-scattered inside the backward scan, so the full-size fp32
    gradient buffer never materialises.  Global grad-norm clipping is
    unavailable in this mode (the norm is only known after the last layer);
    per-leaf clipping via opt_cfg.grad_clip is applied instead.
    """
    from repro.optim.adam import schedule
    assert acc.method == "layered", "fused update requires the layered schedule"
    axis = axis_ctx(mesh)
    ep = acc.expert_parallel and cfg.is_moe
    if ep:
        axis = dataclasses.replace(axis, expert="data")
    tmpl = full_template(cfg)
    sspecs = storage_specs(cfg, axis, acc.partitioned,
                           span_pods=acc.span_pods, expert_resident=ep)
    ospecs = {"mu": sspecs, "nu": sspecs, "step": P()}
    bspecs = batch_specs(cfg, axis, microbatched=True)
    mspecs = {"loss": P(), "ntok": P(), "aux": P(), "lr": P(), "grad_norm": P()}
    c = opt_cfg

    def step(storage, opt, batch):
        with phase("optimizer"):
            stp = opt["step"] + 1
            lr = schedule(c, stp)
            b1c = 1 - c.b1 ** stp.astype(jnp.float32)
            b2c = 1 - c.b2 ** stp.astype(jnp.float32)
        mdt = jnp.dtype(c.moment_dtype)

        @phase("optimizer")
        def upd(p, m, v, g):
            g = g.astype(jnp.float32)
            if c.grad_clip > 0:   # per-leaf clip (global norm unavailable)
                n = jnp.sqrt(jnp.sum(jnp.square(g)) + 1e-16)
                gs = jnp.minimum(1.0, c.grad_clip / n)
            else:
                gs = jnp.ones(())
            if cfg.kernels:
                # the same one-pass chunk kernel, applied per layer the
                # moment its gradient lands (§C.3 semantics preserved)
                from repro.kernels import ops as kops
                return kops.fused_adamw(p, m, v, g,
                                        jnp.stack([lr, b1c, b2c, gs]),
                                        b1=c.b1, b2=c.b2, eps=c.eps,
                                        wd=c.weight_decay)
            g = g * gs
            m32 = c.b1 * m.astype(jnp.float32) + (1 - c.b1) * g
            v32 = c.b2 * v.astype(jnp.float32) + (1 - c.b2) * jnp.square(g)
            p = p - lr * ((m32 / b1c) / (jnp.sqrt(v32 / b2c) + c.eps)
                          + c.weight_decay * p)
            return p, m32.astype(mdt), v32.astype(mdt)

        grad_fn = make_grad_fn(cfg, axis, acc, tmpl, layer_update=upd)
        mu_l, nu_l = opt["mu"]["layers"], opt["nu"]["layers"]
        (outer_grads, new_layers, (new_mu_l, new_nu_l)), metrics = grad_fn(
            storage, batch, (mu_l, nu_l))
        # outer leaves (embed/head/norm/shared): tiny — updated classically
        outer_s, _ = split_tree(storage)
        new_outer, new_mu_o, new_nu_o = {}, {}, {}
        for k in outer_s:
            t = jax.tree.map(upd, outer_s[k], opt["mu"][k], opt["nu"][k],
                             outer_grads[k])
            new_outer[k] = jax.tree.map(lambda x: x[0], t,
                                        is_leaf=lambda x: isinstance(x, tuple))
            new_mu_o[k] = jax.tree.map(lambda x: x[1], t,
                                       is_leaf=lambda x: isinstance(x, tuple))
            new_nu_o[k] = jax.tree.map(lambda x: x[2], t,
                                       is_leaf=lambda x: isinstance(x, tuple))
        new_storage = dict(new_outer, layers=new_layers)
        new_opt = {"mu": dict(new_mu_o, layers=new_mu_l),
                   "nu": dict(new_nu_o, layers=new_nu_l), "step": stp}
        metrics = dict(metrics, lr=lr, grad_norm=jnp.zeros(()))
        return new_storage, new_opt, metrics

    fn = compat.shard_map(step, mesh=mesh,
                       in_specs=(sspecs, ospecs, bspecs),
                       out_specs=(sspecs, ospecs, mspecs))
    return jax.jit(fn, donate_argnums=(0, 1) if donate else ())
