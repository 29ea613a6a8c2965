"""Generic tick-table pipeline executor (schedule-as-data, paper §4).

The `stage` mesh axis holds the pipeline.  A pipeline schedule is *data*: a
static tick table emitted by ``planner.simulator.build_tick_table`` (single
source of truth, derived from the same ``stage_order`` the discrete-event
simulator runs), listing for every tick and stage one (kind, layer-chunk,
micro-batch) unit plus the derived ring-recv meanings.  ONE generic
``lax.scan`` body interprets any table — modular, naive/gpipe, 1f1b and
interleaved-1f1b all execute through the same code path, with both
replicated ``[S, K, ...]`` and ZeRO-partitioned chunk storage.

Chunk placement is uniform (simulator.TickTable): stage s's local chunk v
is global chunk ``g = v*S + s`` holding layers ``[g*k_c, (g+1)*k_c)`` — for
V=1 the contiguous blocks of naive/1f1b, for V=K the paper's round-robin.
Consecutive global chunks are always one forward ring hop apart, so one
``ppermute`` ring serves every schedule.

The backward is hand-written per tick (the accumulation.py pattern), not
``jax.grad`` of the forward scan: 1f1b and interleaved run backward units
*between* forward units of the same scan, an order AD's scan transpose
cannot express.  Each tick every stage runs ONE masked chunk VJP — the
``jax.vjp`` forward doubles as the F unit's compute and the pull as the B
unit's (recompute + transposed dots, same 3x-forward bundle the remat'd AD
path paid) — plus the loss stage's masked head VJP, and exactly three ring
permutes: forward activation, head cotangent (loss ring), backward
cotangent.  Bubble ticks compute on garbage and are masked, so the bubble
shows up verbatim as wasted FLOPs in the roofline, like idle devices waste
time on real hardware.  ``TickTable.predicted_collectives`` states the
resulting op counts; the conformance tests pin the lowered jaxpr to them.

Zero-bubble split tables (``build_tick_table(split_backward=True)``) add
tick kinds 3/4: a BDGRAD tick runs the same joint VJP but keeps only the
activation-path half — its dx rides the backward ring immediately while the
weight-path half is deferred — parking the unit's (activation, cotangent)
residual in a bounded ring buffer (R = ``TickTable.residual_depth()`` slots,
the table's max outstanding dgrads); the matching BWGRAD tick replays the
VJP from that residual in a bubble slot and accumulates only the weight-path
gradient.  Every backward unit therefore lands in the ZeRO chunk grads
exactly once per pass, so the reduce-scatter frequency and all collective
counts per tick are unchanged — split tables just have more (cheaper) ticks.

Embedding / head run stage-replicated (their compute is marginal); only
stage 0's embedding feeds the pipeline, the final output wraps to stage 0
whose head VJP emits the loss AND the cotangent that rides the loss ring
back to stage S-1 in the same tick.  Gradients stay correct with one psum
over `stage` for the stage-replicated outer leaves (PR-5 invariant).

ZeRO-partitioned storage gathers each local chunk's weights ONCE per pass
(V all-gathers per leaf = the layered-accumulation frequency; modular V=K
keeps the K-gathers-per-leaf jaxpr pin) at the tick-table's gather
boundaries, and reduce-scatters each chunk's gradient once at the end of
the pass.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.compat import pvary_missing
from repro.core.schedules import PipeSpec
from repro.models import transformer as T
from repro.models.common import AxisCtx, ModelConfig, apply_norm
from repro.obs.trace import phase

PyTree = Any


# ---------------------------------------------------------------------------
# Layer-stack <-> stage-stack layout
# ---------------------------------------------------------------------------
def to_stage_stack(layers: PyTree, spec: PipeSpec) -> PyTree:
    """Global [L, ...] stacks -> [S, K, ...] (dim 0 shards over `stage`).

    Slot [s, v*k_c + j] holds global layer (v*S + s)*k_c + j — the uniform
    chunk placement of the tick tables.  For naive/1f1b (V=1) this is the
    contiguous reshape; for modular (V=K, k_c=1) the round-robin columns."""
    S, K = spec.n_stages, spec.layers_per_stage
    V, k_c = spec.n_chunks, spec.layers_per_chunk

    def conv(x):
        rest = x.shape[1:]
        return (x.reshape(V, S, k_c, *rest).swapaxes(0, 1)
                .reshape(S, K, *rest))

    return jax.tree.map(conv, layers)


def from_stage_stack(stages: PyTree, spec: PipeSpec) -> PyTree:
    S, K = spec.n_stages, spec.layers_per_stage
    V, k_c = spec.n_chunks, spec.layers_per_chunk

    def conv(x):
        rest = x.shape[2:]
        return (x.reshape(S, V, k_c, *rest).swapaxes(0, 1)
                .reshape(S * K, *rest))

    return jax.tree.map(conv, stages)


def stage_param_specs(cfg: ModelConfig, tp: int) -> PyTree:
    """Specs for pipeline storage: layers are ``[S, K, ...]`` stage stacks —
    TWO leading dims ('stage', then the within-stage layer index) before each
    per-layer spec.  (Prepending only 'stage' silently shifted the 'model'
    axis onto a weight dim for tp > 1 — invisible at tp == 1 where the
    per-layer specs are all-None, and flushed out by the stage x model
    composed-mesh tests.)"""
    base = T.param_specs(cfg, tp)
    layers = jax.tree.map(lambda s: P("stage", None, *s),
                          T.layer_specs(cfg, tp),
                          is_leaf=lambda x: isinstance(x, P))
    return dict({k: v for k, v in base.items() if k != "layers"}, layers=layers)


def partitioned_stage_param_specs(cfg: ModelConfig, tp: int) -> PyTree:
    """Specs for the ZeRO-partitioned pipeline storage: layer leaves are
    ``[S, K, n_model, n_data, chunk]`` fp32 chunk stacks — the stage-leading
    analogue of ``partition.partitioned_specs`` (every leaf carries an
    ``n_model`` dim so the layout is uniform across model-sharded and
    model-replicated leaves).  Outer leaves keep their full compute specs
    (they are small and stage-replicated)."""
    from repro.core import partition as zp

    base = T.param_specs(cfg, tp)

    def conv(s):
        m = None if zp.model_replicated(s) else "model"
        return P("stage", None, m, "data", None)

    layers = jax.tree.map(conv, T.layer_specs(cfg, tp),
                          is_leaf=lambda x: isinstance(x, P))
    return dict({k: v for k, v in base.items() if k != "layers"}, layers=layers)


def to_partitioned_stage_stack(layers: PyTree, spec: PipeSpec, n_data: int,
                               *, lspecs: PyTree | None = None,
                               tp: int = 1) -> PyTree:
    """Global [L, ...] stacks -> [S, K, n_model, n_data, chunk] fp32 ZeRO
    chunks (storage layout for the partitioned executor; shard with
    partitioned_stage_param_specs).

    ``lspecs`` (T.layer_specs(cfg, tp), no stacking dim) + ``tp`` make the
    layout tensor-parallel aware: a model-sharded leaf is split along its
    'model' spec dim first, so slot [s, k, m, d, :] holds the d-th data
    chunk of model shard m — the model-local flattening the per-chunk
    data-only all_gather restores.  tp == 1 keeps every leaf in one
    (replicated) model slot.
    """
    import math as _math

    staged = to_stage_stack(layers, spec)   # [S, K, ...]
    if lspecs is None:
        lspecs = jax.tree.map(lambda x: P(), staged)

    def conv(x, sp):
        S_, K_ = x.shape[:2]
        x = x.astype(jnp.float32)
        m_dim = next((i for i, ax in enumerate(tuple(sp)) if ax == "model"),
                     None)
        if tp > 1 and m_dim is not None:
            d = 2 + m_dim                       # past the [S, K] lead dims
            assert x.shape[d] % tp == 0, (x.shape, sp, tp)
            x = x.reshape(*x.shape[:d], tp, x.shape[d] // tp, *x.shape[d + 1:])
            x = jnp.moveaxis(x, d, 2)           # [S, K, tp, ...local dims...]
            n_model = tp
        else:
            x = x[:, :, None]                   # [S, K, 1, ...]
            n_model = 1
        flat = x.reshape(S_, K_, n_model, -1)
        c = _math.ceil(flat.shape[-1] / n_data)
        flat = jnp.pad(flat,
                       ((0, 0), (0, 0), (0, 0), (0, c * n_data - flat.shape[-1])))
        return flat.reshape(S_, K_, n_model, n_data, c)

    return jax.tree.map(conv, staged, lspecs)


def from_partitioned_stage_stack(chunks: PyTree, spec: PipeSpec,
                                 layer_template: PyTree, *,
                                 lspecs: PyTree | None = None,
                                 tp: int = 1) -> PyTree:
    """[S, K, n_model, n_data, chunk] fp32 chunks -> global [L, ...] stacks
    (the exact inverse of ``to_partitioned_stage_stack``; drops the chunk
    padding).  ``layer_template`` holds the global per-layer shapes."""
    import math as _math

    if lspecs is None:
        lspecs = jax.tree.map(lambda _: P(), layer_template)

    def conv(c, tmpl, sp):
        S_, K_, n_model = c.shape[:3]
        shape = tuple(tmpl.shape)
        m_dim = next((i for i, ax in enumerate(tuple(sp)) if ax == "model"),
                     None)
        if n_model > 1 and m_dim is not None:
            lshape = tuple(d // tp if i == m_dim else d
                           for i, d in enumerate(shape))
        else:
            lshape = shape
        numel = _math.prod(lshape)
        flat = c.reshape(S_, K_, n_model, -1)[..., :numel]
        x = flat.reshape(S_, K_, n_model, *lshape)
        if n_model > 1 and m_dim is not None:
            x = jnp.moveaxis(x, 2, 2 + m_dim)
            x = x.reshape(S_, K_, *shape)
        else:
            x = x.reshape(S_, K_, *shape)
        return x

    staged = jax.tree.map(conv, chunks, layer_template, lspecs)
    return from_stage_stack(staged, spec)


# ---------------------------------------------------------------------------
# The generic tick-table executor
# ---------------------------------------------------------------------------
def _table_rows_np(table) -> dict:
    """The tick table as [T, S] numpy arrays (host side: the segmented
    profiler slices per-tick rows from these).  ``res_slot`` is the derived
    residual ring-buffer slot of split tables (all zeros for unsplit)."""
    def arr(rows, dt=np.int32):
        return np.asarray(rows, dtype=dt)
    res_slot, _ = table.residual_slots()
    return {
        "res_slot": arr(res_slot),
        "kind": arr(table.kind),
        "v": arr(table.unit_v),
        "mb": arr(table.unit_mb),
        "fr_valid": arr(table.frecv_valid, np.bool_),
        "fr_v": arr(table.frecv_v),
        "fr_mb": arr(table.frecv_mb),
        "fr_fin": arr(table.frecv_final, np.bool_),
        "hr_valid": arr(table.hrecv_valid, np.bool_),
        "hr_mb": arr(table.hrecv_mb),
        "br_valid": arr(table.brecv_valid, np.bool_),
        "br_v": arr(table.brecv_v),
        "br_mb": arr(table.brecv_mb),
    }


def _table_rows(table) -> dict:
    """The tick table as [T, S] device arrays the scan body indexes by
    (tick, axis_index)."""
    return {k: jnp.asarray(v) for k, v in _table_rows_np(table).items()}


@dataclasses.dataclass
class PipelineExecutor:
    """The tick-table executor, split into reusable pieces.

    ``grad_fn`` composes them into the one-dispatch scan executor (the
    training hot path).  The pieces are also callable individually — the
    opt-in *segmented-execution* mode (stepfn.build_pipeline_tick_profiler)
    runs ``make_tick`` one tick per dispatch so the host can time every tick
    of the schedule, with ``pack_state``/``unpack_state`` carrying the
    executor state across the per-tick jit boundary.

    All pieces run INSIDE shard_map over a mesh containing `stage`
    (+ optionally `data`/`model`/`pod`), on the same storage layouts as
    ``grad_fn``.
    """
    grad_fn: Any          # (params, batch) -> (grads, metrics)
    outer_ctx: Any        # params -> (outer_g, shared_g)
    data_ctx: Any         # (outer_g, batch) -> (X0, pos, n_tok, inv_n)
    init_carry: Any       # (outer_g, shared_g, X0, params) -> carry
    wbuf_init: Any        # params -> wbuf (zeros when partitioned)
    gather_chunk: Any     # (params, v2) -> gathered chunk weights
    update_wbuf: Any      # (wbuf, w_v, v2) -> wbuf
    make_tick: Any        # (ctx, wbuf) -> tick(carry, xs)
    epilogue: Any         # (ctx, carry, params) -> (grads, metrics)
    pack_state: Any       # (wbuf, carry, pos, inv_n, n_tok) -> state dict
    unpack_state: Any     # state dict -> (wbuf, carry, pos, inv_n, n_tok)
    table: Any
    segments: list
    rows: dict            # [T, S] device arrays
    rows_np: dict         # [T, S] numpy arrays
    partitioned: bool
    outer_tmpl: PyTree    # outer param ShapeDtypeStructs (state spec aid)


def _make_tick_grad_fn(cfg: ModelConfig, axis: AxisCtx, spec: PipeSpec,
                       layer_template: PyTree | None, *,
                       partitioned: bool, stage_axis: str = "stage",
                       table=None) -> PipelineExecutor:
    """Build the executor pieces interpreting ``table`` (and the composed
    ``grad_fn(params, batch) -> (grads, metrics)``).

    Call the pieces INSIDE shard_map over a mesh containing `stage`
    (+ optionally `data`/`model`/`pod`).  Replicated storage:
    params["layers"] leaves are the stage-local ``[1(stage), K, ...]``
    stacks.  Partitioned storage: ``[1, K, 1(model), 1(data), chunk]`` fp32
    ZeRO chunks, with ``layer_template`` holding the global per-layer
    shapes.  Batch leaves are [M, mb_local, ...] (replicated over `stage`).
    """
    from repro.core import partition as zp
    from repro.planner import simulator as simlib

    if table is None:
        table = spec.tick_table()
    table.validate_executable()
    # zero-bubble split tables (kinds 3/4) carry a bounded residual ring
    # buffer: BDGRAD saves its (activation, cotangent) pair into the slot
    # the table derived, the matching BWGRAD replays the weight-path dots
    # from it.  R is the table's max number of outstanding dgrads.
    split_table = table.is_split
    res_depth = table.residual_slots()[1] if split_table else 0
    S, M = spec.n_stages, spec.n_microbatches
    V, k_c = table.n_chunks, table.layers_per_chunk
    assert (table.n_stages, table.n_microbatches) == (S, M), \
        (table.n_stages, table.n_microbatches, S, M)
    assert V * k_c == spec.layers_per_stage
    windows, flags, _ = T.layer_tables(cfg)
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    rev_perm = [(i, (i - 1) % S) for i in range(S)]
    dtype = jnp.dtype(cfg.dtype)
    tied = cfg.tie_embeddings
    lspecs = T.layer_specs(cfg, axis.tp)
    outer_specs = {k: v for k, v in T.param_specs(cfg, axis.tp).items()
                   if k != "layers"}
    ROWS = _table_rows(table)
    segments = (table.gather_segments() if partitioned
                else [(0, table.n_ticks, [])])
    dp_axes = (axis.data, axis.pod)
    vary_axes = (stage_axis, axis.data, axis.pod)

    if partitioned:
        assert layer_template is not None
        layer_tmpl = layer_template
    else:
        layer_tmpl = None
    full_tmpl = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    outer_tmpl = {k: v for k, v in full_tmpl.items() if k != "layers"}

    def grad_zeros(tree, specs):
        """f32 zero accumulators whose vma matches the executor's gradient
        leaves: varying over stage/data/pod always (every stage accumulates
        its own partials); over model iff the leaf is sharded."""
        def z(leaf, sp):
            axes = list(vary_axes)
            if axis.model and not zp.model_replicated(sp):
                axes.append(axis.model)
            return zp.pvary_missing(jnp.zeros(leaf.shape, jnp.float32), axes)
        return jax.tree.map(z, tree, specs)

    def outer_ctx(params):
        """Outer leaves in compute dtype; marked varying over
        stage/data/pod so the per-tick VJPs yield LOCAL partials — the
        single explicit psum in the epilogue is the only reduction."""
        outer_store = {k: v for k, v in params.items() if k != "layers"}
        outer_g = {k: jax.tree.map(
            lambda x: pvary_missing(x.astype(dtype), vary_axes), v)
            for k, v in outer_store.items()}
        return outer_g, outer_g.get("shared", {})

    # ---- layer weights: one [K, ...] compute-dtype buffer ----------------
    def wbuf_zeros():
        def z(path, tmpl, sp):
            lshape = zp.local_shape(tmpl.shape, sp, axis.tp, path=path)
            return pvary_missing(
                jnp.zeros((spec.layers_per_stage, *lshape), dtype),
                vary_axes)
        return jax.tree_util.tree_map_with_path(z, layer_tmpl, lspecs)

    def wbuf_init(params):
        """The per-pass weight buffer: zeros when partitioned (filled by
        ``gather_chunk``/``update_wbuf`` at the table's gather boundaries),
        the stage-local [K, ...] stack otherwise (data/pod-varying for
        local partials)."""
        if partitioned:
            return wbuf_zeros()
        return jax.tree.map(
            lambda p: pvary_missing(p[0].astype(dtype), dp_axes),
            params["layers"])

    @phase("zero_gather")
    def gather_chunk(params, v2):
        """all_gather local chunk v2's weights over `data`: leaves
        [k_c, 1, 1, chunk] -> [k_c, *model-local shape] bf16.  One
        all_gather per leaf per chunk per pass — V per leaf total
        (modular: V=K, the layered-accumulation frequency).  ``v2`` may be
        a python int (scan executor: static slice) or a traced scalar
        (segmented mode: one compile serves every chunk)."""
        if isinstance(v2, (int, np.integer)):
            sl = jax.tree.map(
                lambda p: p[0, v2 * k_c:(v2 + 1) * k_c], params["layers"])
        else:
            sl = jax.tree.map(
                lambda p: lax.dynamic_slice_in_dim(p[0], v2 * k_c, k_c, 0),
                params["layers"])

        def g(path, tmpl, sp, c):
            lshape = zp.local_shape(tmpl.shape, sp, axis.tp, path=path)
            full = zp.gather_local(c, axis.data, (k_c, *lshape),
                                   dtype, stacked=True)
            return pvary_missing(full, dp_axes)
        return jax.tree_util.tree_map_with_path(g, layer_tmpl, lspecs, sl)

    def update_wbuf(wbuf, w_v, v2):
        return jax.tree.map(
            lambda W, wv: lax.dynamic_update_slice_in_dim(W, wv, v2 * k_c, 0),
            wbuf, w_v)

    def data_ctx(outer_g, batch):
        """Embed the batch (stage-replicated compute; only stage 0's output
        enters the pipeline) and count loss tokens."""
        def embed_one(_, mb):
            return None, T.embed_inputs(cfg, outer_g, mb, axis)

        _, (X0, POS) = lax.scan(embed_one, None, batch)   # [M, mb, Sq, D]
        pos = POS[0]                                         # identical per mb

        n_tok = jnp.sum(batch["mask"].astype(jnp.float32))
        if axis.data:
            n_tok = lax.psum(n_tok, axis.data)
        if axis.pod:
            n_tok = lax.psum(n_tok, axis.pod)
        return X0, pos, n_tok, 1.0 / n_tok

    def init_carry(outer_g, shared_g, X0, wbuf):
        """Activation/cotangent buffers + zero gradient accumulators."""
        on_stage0 = lax.axis_index(stage_axis) == 0
        zeros_act = pvary_missing(jnp.zeros((V, M, *X0.shape[1:]), dtype),
                                  vary_axes)
        # stage 0's local chunk 0 is global chunk 0: seed its inputs with the
        # embeddings (garbage elsewhere, masked by the table)
        act_in = zeros_act.at[0].set(
            jnp.where(on_stage0, X0.astype(dtype), zeros_act[0]))
        cot = zeros_act
        dX0 = pvary_missing(jnp.zeros(X0.shape, dtype), vary_axes)

        dW = grad_zeros(wbuf, lspecs)
        dsh = grad_zeros(shared_g, outer_specs.get("shared", {}))
        dfn = grad_zeros(outer_g["final_norm"], outer_specs["final_norm"])
        demb = grad_zeros(outer_g["embed"], outer_specs["embed"])
        dhead = (None if tied
                 else grad_zeros(outer_g["head"], outer_specs["head"]))
        nll_sum = pvary_missing(jnp.zeros((), jnp.float32), vary_axes)
        # split tables: the dgrad->wgrad residual ring buffer, R per-unit
        # (activation, cotangent) pairs (None keeps unsplit carries as-is)
        res = None
        if split_table:
            res_zeros = pvary_missing(
                jnp.zeros((res_depth, *X0.shape[1:]), dtype), vary_axes)
            res = (res_zeros, res_zeros)
        return (act_in, cot, dX0, dW, dsh, dfn, dhead, demb, nll_sum, res)

    # ---- the tick body ----------------------------------------------------
    def make_tick(ctx, wbuf):
        outer_g, shared_g = ctx["outer_g"], ctx["shared_g"]
        batch, pos, inv_n = ctx["batch"], ctx["pos"], ctx["inv_n"]
        s = lax.axis_index(stage_axis)

        def head_vjp(xh, hbatch):
            """Masked head VJP at the loss stage: loss value + cotangent."""
            def f(fn_p, head_p, embed_p, x):
                og = dict(outer_g, final_norm=fn_p, embed=embed_p)
                if not tied:
                    og["head"] = head_p
                h = apply_norm(cfg, fn_p, x)
                nll = T.head_loss(cfg, og, h, hbatch, axis)
                return nll * inv_n, nll

            if tied:
                loss, vjp, nll = jax.vjp(
                    lambda fn_p, embed_p, x: f(fn_p, None, embed_p, x),
                    outer_g["final_norm"], outer_g["embed"], xh, has_aux=True)
                dfn_t, demb_t, dxh = vjp(
                    zp.match_vma(jnp.ones((), loss.dtype), loss))
                dhead_t = None
            else:
                loss, vjp, nll = jax.vjp(
                    f, outer_g["final_norm"], outer_g["head"],
                    outer_g["embed"], xh, has_aux=True)
                dfn_t, dhead_t, demb_t, dxh = vjp(
                    zp.match_vma(jnp.ones((), loss.dtype), loss))
            return nll, dfn_t, dhead_t, demb_t, dxh

        def tick(carry, xs):
            (act_in, cot, dX0, dW, dsh, dfn, dhead, demb, nll_sum,
             res) = carry
            kind = xs["kind"][s]
            v, mb = xs["v"][s], xs["mb"][s]
            is_b = kind == simlib.TICK_B
            # zero-bubble split halves: BDGRAD is the activation-path
            # transpose (emits dx, defers the weight dots), BWGRAD replays
            # the same unit's VJP from the saved residual and keeps only
            # the weight-path half
            is_bd = kind == simlib.TICK_BDGRAD
            is_bw = kind == simlib.TICK_BWGRAD
            use_w = is_b | is_bw                # weight-path accumulation
            use_dx = is_b | is_bd               # activation-path cotangent
            g = v * S + s                       # traced global chunk
            x = act_in[v, mb]
            dy = cot[v, mb]
            if split_table:
                res_x, res_dy = res
                slot = xs["res_slot"][s]
                x = jnp.where(is_bw, res_x[slot], x)
                dy = jnp.where(is_bw, res_dy[slot], dy)

            # one masked chunk VJP: the vjp forward IS the F unit's
            # compute, the pull the B unit's (recompute + transposes)
            w_chunk = jax.tree.map(
                lambda p: lax.dynamic_slice_in_dim(p, v * k_c, k_c, 0),
                wbuf)

            def chunk_f(w_c, sh, xc):
                def layer_step(xc, j):
                    lp = jax.tree.map(lambda p: p[j], w_c)
                    lid = g * k_c + j
                    x2, _aux = T.apply_layer(
                        cfg, lp, sh, xc, positions=pos,
                        window=windows[lid], shared_flag=flags[lid],
                        axis=axis)
                    return x2, None
                y, _ = lax.scan(layer_step, xc, jnp.arange(k_c))
                return y

            y, pull = jax.vjp(chunk_f, w_chunk, shared_g, x)
            dw_v, dsh_t, dx = pull(zp.match_vma(dy, y))

            # accumulate the weight-path gradient at rows [v*k_c, ...):
            # B units in full, BWGRAD units from the replayed residual —
            # BDGRAD contributes nothing here, so the ZeRO chunk grads
            # still see each unit exactly once per pass
            def acc_dw(Wl, wv):
                cur = lax.dynamic_slice_in_dim(Wl, v * k_c, k_c, 0)
                upd = cur + jnp.where(use_w, wv.astype(jnp.float32), 0.0)
                return lax.dynamic_update_slice_in_dim(Wl, upd,
                                                       v * k_c, 0)
            dW = jax.tree.map(acc_dw, dW, dw_v)
            dsh = jax.tree.map(
                lambda a, b: a + jnp.where(use_w, b.astype(jnp.float32),
                                           0.0), dsh, dsh_t)
            # backward of global chunk 0 ends the chain: its dx is the
            # embedding cotangent (only ever unmasked on stage 0)
            dX0 = dX0.at[mb].set(
                jnp.where(use_dx & (g == 0), dx.astype(dtype), dX0[mb]))
            # BDGRAD parks this unit's residual in its ring-buffer slot
            # (the matching BWGRAD tick frees it by replaying from it)
            if split_table:
                res_x = res_x.at[slot].set(
                    jnp.where(is_bd, x, res_x[slot]))
                res_dy = res_dy.at[slot].set(
                    jnp.where(is_bd, dy, res_dy[slot]))
                res = (res_x, res_dy)

            # ---- ring 1: forward activation --------------------------
            recv = lax.ppermute(y.astype(dtype), stage_axis, fwd_perm)
            fr_valid, fr_fin = xs["fr_valid"][s], xs["fr_fin"][s]
            fr_v, fr_mb = xs["fr_v"][s], xs["fr_mb"][s]
            act_in = act_in.at[fr_v, fr_mb].set(
                jnp.where(fr_valid & ~fr_fin, recv, act_in[fr_v, fr_mb]))

            # ---- head VJP on the (masked) final arrival --------------
            hbatch = jax.tree.map(lambda b: b[fr_mb], batch)
            nll, dfn_t, dhead_t, demb_t, dxh = head_vjp(recv, hbatch)
            fin = fr_valid & fr_fin
            nll_sum = nll_sum + jnp.where(fin, nll, 0.0)

            def macc(acc, gt):
                return jax.tree.map(
                    lambda a, b: a + jnp.where(fin,
                                               b.astype(jnp.float32),
                                               0.0), acc, gt)
            dfn = macc(dfn, dfn_t)
            demb = macc(demb, demb_t)
            if dhead is not None:
                dhead_new = macc(dhead, dhead_t)
            else:
                dhead_new = None

            # ---- ring 2: head cotangent to stage S-1 (loss ring) -----
            recv_h = lax.ppermute(dxh.astype(dtype), stage_axis, rev_perm)
            hr_valid, hr_mb = xs["hr_valid"][s], xs["hr_mb"][s]
            cot = cot.at[V - 1, hr_mb].set(
                jnp.where(hr_valid, recv_h, cot[V - 1, hr_mb]))

            # ---- ring 3: backward cotangent --------------------------
            recv_b = lax.ppermute(dx.astype(dtype), stage_axis, rev_perm)
            br_valid = xs["br_valid"][s]
            br_v, br_mb = xs["br_v"][s], xs["br_mb"][s]
            cot = cot.at[br_v, br_mb].set(
                jnp.where(br_valid, recv_b, cot[br_v, br_mb]))

            return (act_in, cot, dX0, dW, dsh, dfn, dhead_new, demb,
                    nll_sum, res), None
        return tick

    def epilogue(ctx, carry, params):
        """Embed backward + the single reduction pass (the pass tail, shared
        by the scan executor and the segmented profiler)."""
        outer_g, batch, n_tok = ctx["outer_g"], ctx["batch"], ctx["n_tok"]
        outer_store = {k: v for k, v in params.items() if k != "layers"}
        on_stage0 = lax.axis_index(stage_axis) == 0
        (act_in, cot, dX0, dW, dsh, dfn, dhead, demb, nll_sum,
         _res) = carry

        # ---- embed backward (accumulation.py pattern; dX0 is zero off
        # stage 0, so the garbage contributions vanish) ---------------------
        def emb_body(demb_acc, xs):
            mb, dx = xs

            def f(embed_p):
                x, _ = T.embed_inputs(cfg, dict(outer_g, embed=embed_p),
                                      mb, axis)
                return x

            _, vjp = jax.vjp(f, outer_g["embed"])
            (de,) = vjp(dx)
            return jax.tree.map(lambda u, w: u + w.astype(jnp.float32),
                                demb_acc, de), None

        demb, _ = lax.scan(emb_body, demb, (batch, dX0))

        # ---- reductions ---------------------------------------------------
        outer_grads = {"embed": demb, "final_norm": dfn, "shared": dsh}
        if dhead is not None:
            outer_grads["head"] = dhead
        outer_grads = {k: v for k, v in outer_grads.items()
                       if k in outer_store}
        @phase("zero_reduce")
        def reduce_outer(g):
            # outer leaves are stage-replicated but their partials live on
            # the stages that used them (loss stage for embed/head/norm,
            # every stage for `shared`): the stage psum completes them so all
            # stages hold identical outer grads — required for consistent
            # grad-norm clipping and replicated optimizer updates.
            g = lax.psum(g, stage_axis)
            if axis.data:
                g = lax.psum(g, axis.data)
            if axis.pod:
                g = lax.psum(g, axis.pod)
            return g

        outer_grads = {k: jax.tree.map(reduce_outer, v)
                       for k, v in outer_grads.items()}

        if partitioned:
            @phase("zero_reduce")
            def scatter_leaf(Wl):
                """Per-chunk reduce-scatter over `data`: V psum_scatters per
                leaf per pass, the explicit transpose of gather_chunk."""
                parts = [zp.scatter_grad_local(
                    Wl[v2 * k_c:(v2 + 1) * k_c], axis.data, axis.ndata,
                    stacked=True, pod_axis=axis.pod)
                    for v2 in range(V)]
                return jnp.concatenate(parts, axis=0)[None]
            layer_grads = jax.tree.map(scatter_leaf, dW)
        else:
            @phase("zero_reduce")
            def reduce_layer(g):
                if axis.data:
                    g = lax.psum(g, axis.data)
                if axis.pod:
                    g = lax.psum(g, axis.pod)
                return g[None]                     # [1(stage), K, ...]
            layer_grads = jax.tree.map(reduce_layer, dW)

        grads = dict(outer_grads, layers=layer_grads)

        nll = jnp.where(on_stage0, nll_sum, 0.0)
        nll = lax.psum(nll, stage_axis)
        if axis.data:
            nll = lax.psum(nll, axis.data)
        if axis.pod:
            nll = lax.psum(nll, axis.pod)
        return grads, {"loss": nll / n_tok, "ntok": n_tok}

    # ---- segmented-mode state packing -------------------------------------
    # The per-tick jit boundary only carries arrays; rank-0 leaves are lifted
    # to [1] so every state leaf has a dim 0 the profiler's stage/data merge
    # spec can attach to.
    def _lift(tree, tmpl):
        return jax.tree.map(lambda x, t: x[None] if t.ndim == 0 else x,
                            tree, tmpl)

    def _unlift(tree, tmpl):
        return jax.tree.map(lambda x, t: x[0] if t.ndim == 0 else x,
                            tree, tmpl)

    def pack_state(wbuf, carry, pos, inv_n, n_tok):
        (act_in, cot, dX0, dW, dsh, dfn, dhead, demb, nll_sum,
         res) = carry
        st = {"wbuf": wbuf, "act": act_in, "cot": cot, "dX0": dX0, "dW": dW,
              "dsh": _lift(dsh, outer_tmpl.get("shared", {})),
              "dfn": _lift(dfn, outer_tmpl["final_norm"]),
              "demb": _lift(demb, outer_tmpl["embed"]),
              "nll": nll_sum[None], "pos": pos, "inv_n": inv_n[None],
              "n_tok": n_tok[None]}
        if dhead is not None:
            st["dhead"] = _lift(dhead, outer_tmpl["head"])
        if res is not None:
            st["res_x"], st["res_dy"] = res
        return st

    def unpack_state(st):
        dhead = (_unlift(st["dhead"], outer_tmpl["head"])
                 if "dhead" in st else None)
        res = (st["res_x"], st["res_dy"]) if "res_x" in st else None
        carry = (st["act"], st["cot"], st["dX0"], st["dW"],
                 _unlift(st["dsh"], outer_tmpl.get("shared", {})),
                 _unlift(st["dfn"], outer_tmpl["final_norm"]), dhead,
                 _unlift(st["demb"], outer_tmpl["embed"]), st["nll"][0],
                 res)
        return (st["wbuf"], carry, st["pos"], st["inv_n"][0], st["n_tok"][0])

    # ---- the one-dispatch scan executor (training hot path) ---------------
    def grad_fn(params, batch):
        outer_g, shared_g = outer_ctx(params)
        X0, pos, n_tok, inv_n = data_ctx(outer_g, batch)
        ctx = dict(outer_g=outer_g, shared_g=shared_g, batch=batch,
                   pos=pos, inv_n=inv_n, n_tok=n_tok)
        wbuf = wbuf_init(params)
        carry = init_carry(outer_g, shared_g, X0, wbuf)
        # ---- run the tick segments (gather boundaries are static) --------
        for (t0, t1, chunks) in segments:
            if partitioned:
                for v2 in chunks:
                    wbuf = update_wbuf(wbuf, gather_chunk(params, v2), v2)
            if t1 > t0:
                xs = {k: r[t0:t1] for k, r in ROWS.items()}
                carry, _ = lax.scan(make_tick(ctx, wbuf), carry, xs)
        return epilogue(ctx, carry, params)

    return PipelineExecutor(
        grad_fn=grad_fn, outer_ctx=outer_ctx, data_ctx=data_ctx,
        init_carry=init_carry, wbuf_init=wbuf_init,
        gather_chunk=gather_chunk, update_wbuf=update_wbuf,
        make_tick=make_tick, epilogue=epilogue, pack_state=pack_state,
        unpack_state=unpack_state, table=table, segments=segments,
        rows=ROWS, rows_np=_table_rows_np(table), partitioned=partitioned,
        outer_tmpl=outer_tmpl)


# ---------------------------------------------------------------------------
# Public grad-fn makers (API preserved across the schedule-as-data refactor)
# ---------------------------------------------------------------------------
def make_pipeline_grad_fn(cfg: ModelConfig, axis: AxisCtx, spec: PipeSpec, *,
                          stage_axis: str = "stage", remat: bool = True,
                          table=None):
    """grad_fn(params, batch) -> (grads, metrics), inside shard_map, with
    replicated ``[S, K, ...]`` layer storage.  ``table`` (optional) is a
    prebuilt ``simulator.TickTable``; by default the spec's own table is
    emitted.  ``remat`` is accepted for API compatibility: the hand-written
    per-tick VJP never differentiates through the scan, so there is nothing
    to rematerialize."""
    del remat
    return _make_tick_grad_fn(cfg, axis, spec, None, partitioned=False,
                              stage_axis=stage_axis, table=table).grad_fn


def make_partitioned_pipeline_grad_fn(cfg: ModelConfig, axis: AxisCtx,
                                      spec: PipeSpec, layer_template: PyTree,
                                      *, stage_axis: str = "stage",
                                      remat: bool = True, table=None):
    """grad_fn(params, batch) -> (grads, metrics) with ZeRO-chunked layers
    ([1, K, n_model, n_data, chunk] fp32 storage; ``layer_template`` holds
    the global per-layer shapes).  Layer gradients come back reduce-
    scattered per chunk; the small stage-replicated outer leaves get the
    explicit stage+data psum (PR-5 invariants preserved by the generic
    executor)."""
    del remat
    return _make_tick_grad_fn(cfg, axis, spec, layer_template,
                              partitioned=True, stage_axis=stage_axis,
                              table=table).grad_fn


def make_pipeline_executor(cfg: ModelConfig, axis: AxisCtx, spec: PipeSpec,
                           layer_template: PyTree | None = None, *,
                           partitioned: bool = False,
                           stage_axis: str = "stage",
                           table=None) -> PipelineExecutor:
    """The executor split into its pieces (``PipelineExecutor``) — the
    segmented-execution entry point (``stepfn.build_pipeline_tick_profiler``
    wraps the pieces in per-tick jitted dispatches so ``obs/trace`` can
    host-time every tick of the schedule)."""
    if partitioned:
        assert layer_template is not None, \
            "partitioned executor needs the global layer template"
    return _make_tick_grad_fn(cfg, axis, spec, layer_template,
                              partitioned=partitioned,
                              stage_axis=stage_axis, table=table)
