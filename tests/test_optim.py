"""Optimizer: convergence, schedule, bf16 moments, layout-agnosticism, and
the fused Pallas chunk-update dispatch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim.adam import AdamConfig, adam_init, adam_step, schedule


def test_adam_converges_quadratic():
    c = AdamConfig(lr=0.1, weight_decay=0.0, grad_clip=0, warmup_steps=0,
                   decay_steps=10_000, min_lr_ratio=1.0)
    target = jnp.array([1.0, -2.0, 3.0])
    p = {"w": jnp.zeros(3)}
    opt = adam_init(p)
    for _ in range(300):
        g = {"w": 2 * (p["w"] - target)}
        p, opt, _ = adam_step(c, p, opt, g)
    np.testing.assert_allclose(np.asarray(p["w"]), np.asarray(target), atol=1e-2)


def test_bf16_moments_still_converge():
    c = AdamConfig(lr=0.1, weight_decay=0.0, grad_clip=0, warmup_steps=0,
                   decay_steps=10_000, min_lr_ratio=1.0,
                   moment_dtype="bfloat16")
    target = jnp.array([1.0, -2.0, 3.0])
    p = {"w": jnp.zeros(3)}
    opt = adam_init(p, moment_dtype="bfloat16")
    for _ in range(300):
        g = {"w": 2 * (p["w"] - target)}
        p, opt, _ = adam_step(c, p, opt, g)
    assert opt["mu"]["w"].dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(p["w"]), np.asarray(target), atol=5e-2)


def test_schedule_shape():
    c = AdamConfig(lr=1.0, warmup_steps=10, decay_steps=100, min_lr_ratio=0.1)
    s0 = float(schedule(c, jnp.asarray(0)))
    s10 = float(schedule(c, jnp.asarray(10)))
    s110 = float(schedule(c, jnp.asarray(110)))
    assert s0 < 0.2 and abs(s10 - 1.0) < 1e-5 and abs(s110 - 0.1) < 1e-5


def test_grad_clip():
    c = AdamConfig(lr=0.0, grad_clip=1.0)
    p = {"w": jnp.zeros(4)}
    opt = adam_init(p)
    g = {"w": jnp.full(4, 100.0)}
    _, _, m = adam_step(c, p, opt, g,
                        sq_reduce=lambda t: sum(jnp.sum(jnp.square(l))
                                                for l in jax.tree.leaves(t)))
    assert float(m["grad_norm"]) > 100


@pytest.mark.parametrize("moment_dtype", ["float32", "bfloat16"])
def test_fused_adam_step_matches_treemap(moment_dtype):
    """The fused Pallas chunk-update dispatch (adam_step(fused=True)) runs
    the same float ops as the tree-map path on the partitioned flat-chunk
    layout — clip scale folded into the kernel included."""
    c = AdamConfig(lr=3e-4, grad_clip=1.0, moment_dtype=moment_dtype)
    key = jax.random.PRNGKey(0)
    storage = {"layers": {"w": jax.random.normal(key, (3, 1, 1, 500))},
               "embed": jax.random.normal(jax.random.fold_in(key, 1),
                                          (1, 1, 333))}
    opt = adam_init(storage, moment_dtype=moment_dtype)
    grads = jax.tree.map(lambda l: 0.2 * l + 0.01, storage)
    sq = lambda t: sum(jnp.sum(jnp.square(l)) for l in jax.tree.leaves(t))
    outs = {}
    for fused in (False, True):
        outs[fused] = adam_step(c, storage, opt, grads, sq_reduce=sq,
                                fused=fused)
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(outs[False][:2]),
            jax.tree_util.tree_leaves_with_path(outs[True][:2])):
        assert a.dtype == b.dtype, path
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-6, atol=1e-6, err_msg=str(path))
    assert float(outs[False][2]["grad_norm"]) == \
        float(outs[True][2]["grad_norm"])


def test_adam_init_lays_moments_out_like_sharded_storage(mesh22):
    """On a mesh, each moment is made in its storage leaf's sharding and the
    step counter replicated on the mesh: no device holds a whole moment
    (made whole on device 0, X_32's Adam moments put 3.7 GB there)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import stepfn
    from repro.models.common import ModelConfig

    cfg = ModelConfig(name="opt-place", arch_type="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=64, dtype="float32", param_dtype="float32")
    storage = stepfn.init_storage(cfg, mesh22, jax.random.PRNGKey(0),
                                  partitioned=True)
    opt = adam_init(storage, moment_dtype="bfloat16")
    for s, m, v in zip(*(jax.tree.leaves(t) for t in
                         (storage, opt["mu"], opt["nu"]))):
        assert m.sharding == s.sharding and v.sharding == s.sharding
        assert m.dtype == jnp.bfloat16 and not np.any(np.asarray(m, np.float32))
    assert opt["step"].sharding == NamedSharding(mesh22, P())
