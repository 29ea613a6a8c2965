"""Modular vs naive pipeline parallelism (paper §4): exact equivalence,
bubble accounting, and the p2p traffic trade-off."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import roofline
from repro.core.pipeline import (from_stage_stack, make_pipeline_grad_fn,
                                 stage_param_specs, to_stage_stack)
from repro.core.schedules import PipeSpec
from repro.models import transformer as T
from repro.models.common import AxisCtx, ModelConfig
from repro import compat

CFG = ModelConfig(name="p", arch_type="dense", num_layers=8, d_model=32,
                  num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                  dtype="float32", param_dtype="float32")
M = 8


def _setup(key):
    params = T.init_params(CFG, key)
    toks = jax.random.randint(key, (M, 2, 16), 0, 64)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, -1),
             "mask": jnp.ones_like(toks)}
    flat = {k: v.reshape(M * 2, 16) for k, v in batch.items()}

    def ref_loss(p):
        _, (nll, n) = T.loss_fn(CFG, p, flat, AxisCtx(), remat=False)
        return nll / n

    return params, batch, ref_loss


@pytest.mark.parametrize("sched", ["modular", "naive"])
def test_pipeline_equivalence(mesh_stage4, sched):
    key = jax.random.PRNGKey(0)
    params, batch, ref_loss = _setup(key)
    ref = float(ref_loss(params))
    ref_g = jax.grad(ref_loss)(params)
    spec = PipeSpec(n_stages=4, layers_per_stage=2, n_microbatches=M,
                    schedule=sched)
    pparams = dict({k: v for k, v in params.items() if k != "layers"},
                   layers=to_stage_stack(params["layers"], spec))
    specs = stage_param_specs(CFG, 1)
    bspecs = {k: P(None, None, None) for k in batch}
    grad_fn = make_pipeline_grad_fn(CFG, AxisCtx(), spec)
    fn = compat.shard_map(grad_fn, mesh=mesh_stage4, in_specs=(specs, bspecs),
                       out_specs=(specs, {"loss": P(), "ntok": P()}))
    grads, metrics = jax.jit(fn)(pparams, batch)
    np.testing.assert_allclose(float(metrics["loss"]), ref, rtol=1e-5)
    g = dict({k: v for k, v in grads.items() if k != "layers"},
             layers=from_stage_stack(grads["layers"], spec))
    for (pa, ga), (_, gb) in zip(jax.tree_util.tree_leaves_with_path(g),
                                 jax.tree_util.tree_leaves_with_path(ref_g)):
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   rtol=5e-4, atol=5e-5, err_msg=f"{sched} {pa}")


def test_bubble_and_traffic_tradeoff(mesh_stage4):
    """Modular shrinks the bubble by ~K and pays ~K x more p2p traffic."""
    key = jax.random.PRNGKey(0)
    params, batch, _ = _setup(key)
    shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}
    stats = {}
    for sched in ("naive", "modular"):
        spec = PipeSpec(n_stages=4, layers_per_stage=2, n_microbatches=M,
                        schedule=sched)
        specs = stage_param_specs(CFG, 1)
        bspecs = {k: P(None, None, None) for k in batch}
        grad_fn = make_pipeline_grad_fn(CFG, AxisCtx(), spec)
        fn = compat.shard_map(grad_fn, mesh=mesh_stage4, in_specs=(specs, bspecs),
                           out_specs=(specs, {"loss": P(), "ntok": P()}))
        ps = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          dict({k: v for k, v in params.items() if k != "layers"},
                               layers=to_stage_stack(params["layers"], spec)))
        c = roofline.analyze(fn, ps, shapes, mesh=mesh_stage4)
        stats[sched] = (spec, c)
    spec_n, c_n = stats["naive"]
    spec_m, c_m = stats["modular"]
    K = spec_n.layers_per_stage
    assert spec_n.bubble_layer_ticks == K * spec_m.bubble_layer_ticks
    assert c_m.coll_bytes["stage"] > 1.2 * c_n.coll_bytes["stage"]
    # wasted compute (bubble) shows up as extra FLOPs in the naive schedule
    assert c_n.dot_flops > c_m.dot_flops


def test_schedule_invariants():
    for sched in ("modular", "naive"):
        for S, K, M_ in [(2, 4, 4), (4, 2, 8), (8, 1, 8)]:
            spec = PipeSpec(n_stages=S, layers_per_stage=K, n_microbatches=M_,
                            schedule=sched)
            assert spec.bubble_fraction < 1.0
            assert spec.total_outer_steps >= M_
    with pytest.raises(AssertionError):
        PipeSpec(n_stages=8, layers_per_stage=1, n_microbatches=4,
                 schedule="modular")   # needs n_mu >= n_stages


def test_pipeline_composes_with_data_parallelism():
    """The paper's improved method: modular pipeline x data parallelism.
    Gradients over a (stage=2, data=2) mesh match the sequential reference."""
    import jax as _jax
    mesh = compat.make_mesh((2, 2), ("stage", "data"))
    key = jax.random.PRNGKey(3)
    params = T.init_params(CFG, key)
    toks = jax.random.randint(key, (M, 4, 16), 0, 64)   # 2 per data shard
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, -1),
             "mask": jnp.ones_like(toks)}
    flat = {k: v.reshape(M * 4, 16) for k, v in batch.items()}

    def ref_loss(p):
        _, (nll, n) = T.loss_fn(CFG, p, flat, AxisCtx(), remat=False)
        return nll / n

    ref = float(ref_loss(params))
    ref_g = jax.grad(ref_loss)(params)

    spec = PipeSpec(n_stages=2, layers_per_stage=4, n_microbatches=M,
                    schedule="modular")
    axis = AxisCtx(data="data", dp=2, ndata=2)
    pparams = dict({k: v for k, v in params.items() if k != "layers"},
                   layers=to_stage_stack(params["layers"], spec))
    specs = stage_param_specs(CFG, 1)
    bspecs = {k: P(None, "data", None) for k in batch}
    grad_fn = make_pipeline_grad_fn(CFG, axis, spec)
    fn = compat.shard_map(grad_fn, mesh=mesh, in_specs=(specs, bspecs),
                       out_specs=(specs, {"loss": P(), "ntok": P()}))
    grads, metrics = jax.jit(fn)(pparams, batch)
    np.testing.assert_allclose(float(metrics["loss"]), ref, rtol=1e-5)
    g = dict({k: v for k, v in grads.items() if k != "layers"},
             layers=from_stage_stack(grads["layers"], spec))
    for (pa, ga), (_, gb) in zip(jax.tree_util.tree_leaves_with_path(g),
                                 jax.tree_util.tree_leaves_with_path(ref_g)):
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   rtol=5e-4, atol=5e-5, err_msg=str(pa))


@pytest.mark.parametrize("sched", ["modular", "naive"])
def test_pipeline_composes_with_tensor_parallelism(sched):
    """Stage x model composed mesh (the ROADMAP's untested item): pipeline
    stages whose layers are internally tensor-parallel.  Flushed out a spec
    bug: stage stacks are [S, K, ...] and need TWO leading spec dims before
    the per-layer spec — with one, the 'model' axis landed on a weight dim
    (invisible at tp=1 where per-layer specs are all None)."""
    mesh = compat.make_mesh((2, 2), ("stage", "model"))
    key = jax.random.PRNGKey(3)
    params = T.init_params(CFG, key)
    toks = jax.random.randint(key, (M, 2, 16), 0, 64)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, -1),
             "mask": jnp.ones_like(toks)}
    flat = {k: v.reshape(M * 2, 16) for k, v in batch.items()}

    def ref_loss(p):
        _, (nll, n) = T.loss_fn(CFG, p, flat, AxisCtx(), remat=False)
        return nll / n

    ref = float(ref_loss(params))
    ref_g = jax.grad(ref_loss)(params)
    spec = PipeSpec(n_stages=2, layers_per_stage=4, n_microbatches=M,
                    schedule=sched)
    axis = AxisCtx(model="model", tp=2)
    pparams = dict({k: v for k, v in params.items() if k != "layers"},
                   layers=to_stage_stack(params["layers"], spec))
    specs = stage_param_specs(CFG, 2)
    bspecs = {k: P(None, None, None) for k in batch}
    grad_fn = make_pipeline_grad_fn(CFG, axis, spec)
    fn = compat.shard_map(grad_fn, mesh=mesh, in_specs=(specs, bspecs),
                          out_specs=(specs, {"loss": P(), "ntok": P()}))
    grads, metrics = jax.jit(fn)(pparams, batch)
    np.testing.assert_allclose(float(metrics["loss"]), ref, rtol=1e-5)
    g = dict({k: v for k, v in grads.items() if k != "layers"},
             layers=from_stage_stack(grads["layers"], spec))
    for (pa, ga), (_, gb) in zip(jax.tree_util.tree_leaves_with_path(g),
                                 jax.tree_util.tree_leaves_with_path(ref_g)):
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   rtol=5e-4, atol=5e-5,
                                   err_msg=f"{sched} {pa}")


def test_pipeline_3d_mesh_exercises_completion_psums():
    """The full 3d composition (stage x data x model) on a mamba config:
    its w_B/w_C projections are replicated INSIDE the tensor-parallel block,
    so their per-shard gradients are partials that the transpose of the
    pvary vma typing inserts completes over `model` — previously exercised
    only on stage x data meshes."""
    cfg = ModelConfig(name="m3d", arch_type="dense", num_layers=4, d_model=48,
                      d_ff=96, vocab_size=64, dtype="float32",
                      param_dtype="float32", num_heads=0, num_kv_heads=0,
                      block_kind="mamba", ssm_state=8, ssm_head_dim=16)
    Mmb = 4
    mesh = compat.make_mesh((2, 2, 2), ("stage", "data", "model"))
    key = jax.random.PRNGKey(5)
    params = T.init_params(cfg, key)
    toks = jax.random.randint(key, (Mmb, 4, 16), 0, 64)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, -1),
             "mask": jnp.ones_like(toks)}
    flat = {k: v.reshape(Mmb * 4, 16) for k, v in batch.items()}

    def ref_loss(p):
        _, (nll, n) = T.loss_fn(cfg, p, flat, AxisCtx(), remat=False)
        return nll / n

    ref = float(ref_loss(params))
    ref_g = jax.grad(ref_loss)(params)
    spec = PipeSpec(n_stages=2, layers_per_stage=2, n_microbatches=Mmb,
                    schedule="modular")
    axis = AxisCtx(data="data", model="model", tp=2, dp=2, ndata=2)
    pparams = dict({k: v for k, v in params.items() if k != "layers"},
                   layers=to_stage_stack(params["layers"], spec))
    specs = stage_param_specs(cfg, 2)
    bspecs = {k: P(None, "data", None) for k in batch}
    grad_fn = make_pipeline_grad_fn(cfg, axis, spec)
    fn = compat.shard_map(grad_fn, mesh=mesh, in_specs=(specs, bspecs),
                          out_specs=(specs, {"loss": P(), "ntok": P()}))
    grads, metrics = jax.jit(fn)(pparams, batch)
    np.testing.assert_allclose(float(metrics["loss"]), ref, rtol=1e-5)
    g = dict({k: v for k, v in grads.items() if k != "layers"},
             layers=from_stage_stack(grads["layers"], spec))
    for (pa, ga), (_, gb) in zip(jax.tree_util.tree_leaves_with_path(g),
                                 jax.tree_util.tree_leaves_with_path(ref_g)):
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   rtol=1e-3, atol=1e-4, err_msg=str(pa))


def _layer_template(cfg):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
        jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
        ["layers"])


def test_partitioned_modular_pipeline():
    """The paper's FULL improved method: modular pipeline + ZeRO-partitioned
    stage weights (gathered once per round = per layer, paper §4 last para).
    Exact grads + layered-frequency collectives."""
    from repro.core import roofline
    from repro.core.pipeline import (from_partitioned_stage_stack,
                                     make_partitioned_pipeline_grad_fn,
                                     partitioned_stage_param_specs,
                                     to_partitioned_stage_stack)

    mesh = compat.make_mesh((2, 2), ("stage", "data"))
    key = jax.random.PRNGKey(3)
    params = T.init_params(CFG, key)
    toks = jax.random.randint(key, (M, 4, 16), 0, 64)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, -1),
             "mask": jnp.ones_like(toks)}
    flat = {k: v.reshape(M * 4, 16) for k, v in batch.items()}

    def ref_loss(p):
        _, (nll, n) = T.loss_fn(CFG, p, flat, AxisCtx(), remat=False)
        return nll / n

    ref = float(ref_loss(params))
    ref_g = jax.grad(ref_loss)(params)
    K = 4
    spec = PipeSpec(n_stages=2, layers_per_stage=K, n_microbatches=M,
                    schedule="modular")
    axis = AxisCtx(data="data", dp=2, ndata=2)
    layer_template = _layer_template(CFG)
    chunks = to_partitioned_stage_stack(params["layers"], spec, 2)
    pparams = dict({k: v for k, v in params.items() if k != "layers"},
                   layers=chunks)
    specs = partitioned_stage_param_specs(CFG, 1)
    bspecs = {k: P(None, "data", None) for k in batch}
    grad_fn = make_partitioned_pipeline_grad_fn(CFG, axis, spec,
                                                layer_template)
    fn = compat.shard_map(grad_fn, mesh=mesh, in_specs=(specs, bspecs),
                       out_specs=(specs, {"loss": P(), "ntok": P()}))
    grads, metrics = jax.jit(fn)(pparams, batch)
    np.testing.assert_allclose(float(metrics["loss"]), ref, rtol=1e-5)

    g_full = dict({k: v for k, v in grads.items() if k != "layers"},
                  layers=from_partitioned_stage_stack(
                      grads["layers"], spec, layer_template))
    for (pa, ga), (_, gb) in zip(jax.tree_util.tree_leaves_with_path(g_full),
                                 jax.tree_util.tree_leaves_with_path(ref_g)):
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   rtol=5e-4, atol=5e-5, err_msg=str(pa))
    # collective frequency: gathers EXACTLY once per round (layer) per leaf —
    # the drain ticks must not re-issue the round-(K-1) gather
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          (pparams, batch))
    c = roofline.analyze(fn, *shapes, mesh=mesh)
    ag = sum(v for (ax, nm), v in c.coll_counts.items()
             if "gather" in nm and ax == "data")
    n_leaves = len(jax.tree.leaves(layer_template))
    assert ag == K * n_leaves, (ag, K * n_leaves)


def test_partitioned_pipeline_composes_with_tensor_parallelism():
    """The full-method 3d composition (ISSUE 5 tentpole): ZeRO-partitioned
    modular pipeline on a (stage=2, data=2, model=2) mesh.  Chunks store
    model-local shards ([S, K, n_model, n_data, chunk]); the per-round
    gather runs over `data` only.  Gradients must match BOTH the sequential
    reference and the model-replicated (dense-storage) modular pipeline."""
    from repro.core.pipeline import (from_partitioned_stage_stack,
                                     make_partitioned_pipeline_grad_fn,
                                     partitioned_stage_param_specs,
                                     to_partitioned_stage_stack)

    mesh = compat.make_mesh((2, 2, 2), ("stage", "data", "model"))
    key = jax.random.PRNGKey(7)
    params = T.init_params(CFG, key)
    toks = jax.random.randint(key, (M, 4, 16), 0, 64)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, -1),
             "mask": jnp.ones_like(toks)}
    flat = {k: v.reshape(M * 4, 16) for k, v in batch.items()}

    def ref_loss(p):
        _, (nll, n) = T.loss_fn(CFG, p, flat, AxisCtx(), remat=False)
        return nll / n

    ref = float(ref_loss(params))
    ref_g = jax.grad(ref_loss)(params)
    spec = PipeSpec(n_stages=2, layers_per_stage=4, n_microbatches=M,
                    schedule="modular")
    axis = AxisCtx(data="data", model="model", tp=2, dp=2, ndata=2)
    lspecs = T.layer_specs(CFG, 2)
    layer_template = _layer_template(CFG)
    bspecs = {k: P(None, "data", None) for k in batch}

    # dense (model-replicated layer storage) modular pipeline on same mesh
    dparams = dict({k: v for k, v in params.items() if k != "layers"},
                   layers=to_stage_stack(params["layers"], spec))
    dspecs = stage_param_specs(CFG, 2)
    dfn = compat.shard_map(make_pipeline_grad_fn(CFG, axis, spec), mesh=mesh,
                          in_specs=(dspecs, bspecs),
                          out_specs=(dspecs, {"loss": P(), "ntok": P()}))
    dgrads, dmetrics = jax.jit(dfn)(dparams, batch)
    dense_g = dict({k: v for k, v in dgrads.items() if k != "layers"},
                   layers=from_stage_stack(dgrads["layers"], spec))

    # partitioned storage: model-local chunks
    chunks = to_partitioned_stage_stack(params["layers"], spec, 2,
                                        lspecs=lspecs, tp=2)
    pparams = dict({k: v for k, v in params.items() if k != "layers"},
                   layers=chunks)
    specs = partitioned_stage_param_specs(CFG, 2)
    grad_fn = make_partitioned_pipeline_grad_fn(CFG, axis, spec,
                                                layer_template)
    fn = compat.shard_map(grad_fn, mesh=mesh, in_specs=(specs, bspecs),
                       out_specs=(specs, {"loss": P(), "ntok": P()}))
    grads, metrics = jax.jit(fn)(pparams, batch)
    np.testing.assert_allclose(float(metrics["loss"]), ref, rtol=1e-5)
    np.testing.assert_allclose(float(metrics["loss"]),
                               float(dmetrics["loss"]), rtol=1e-6)

    g_full = dict({k: v for k, v in grads.items() if k != "layers"},
                  layers=from_partitioned_stage_stack(
                      grads["layers"], spec, layer_template,
                      lspecs=lspecs, tp=2))
    # vs the dense modular pipeline: same tick structure -> fp32 1e-5
    for (pa, ga), (_, gb) in zip(jax.tree_util.tree_leaves_with_path(g_full),
                                 jax.tree_util.tree_leaves_with_path(dense_g)):
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   rtol=1e-5, atol=1e-5, err_msg=str(pa))
    # vs the sequential reference
    for (pa, ga), (_, gb) in zip(jax.tree_util.tree_leaves_with_path(g_full),
                                 jax.tree_util.tree_leaves_with_path(ref_g)):
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   rtol=5e-4, atol=5e-5, err_msg=str(pa))


def test_pipeline_train_step_runs_on_3d_mesh():
    """launch-layer coverage (ISSUE 5 tentpole): the jitted pipeline train
    step on the (stage=2, data=2, model=2) mesh — replicated and partitioned
    layer storage take identical optimization trajectories (same grads, same
    grad-norm clip, fused chunk kernel vs tree-map update)."""
    import math
    from repro.core import stepfn
    from repro.optim.adam import AdamConfig, adam_init

    mesh = compat.make_mesh((2, 2, 2), ("stage", "data", "model"))
    spec = PipeSpec(n_stages=2, layers_per_stage=4, n_microbatches=M,
                    schedule="modular")
    toks = jax.random.randint(jax.random.PRNGKey(1), (M, 4, 16), 0, 64)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, -1),
             "mask": jnp.ones_like(toks)}
    got = {}
    for part in (True, False):
        step = stepfn.build_pipeline_train_step(
            CFG, mesh, spec, AdamConfig(lr=1e-3), partitioned=part,
            donate=False)
        storage = stepfn.init_pipeline_storage(
            CFG, mesh, jax.random.PRNGKey(0), spec, partitioned=part)
        opt = adam_init(storage)
        losses, gnorms = [], []
        for _ in range(2):
            storage, opt, metrics = step(storage, opt, batch)
            losses.append(float(metrics["loss"]))
            gnorms.append(float(metrics["grad_norm"]))
        got[part] = (losses, gnorms)
        assert all(math.isfinite(l) for l in losses), losses
    (pl, pg), (rl, rg) = got[True], got[False]
    np.testing.assert_allclose(pl, rl, rtol=1e-5)
    np.testing.assert_allclose(pg, rg, rtol=1e-4)
    assert pl[1] < pl[0]          # it actually optimizes
