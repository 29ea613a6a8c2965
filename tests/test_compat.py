"""The shard_map/vma seam (repro.compat) on the installed JAX."""
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.kernels import ops


def test_out_struct_carries_input_vma():
    """A kernel's out_shape varies over the union of its inputs' axes;
    outside shard_map it varies over nothing."""
    mesh = compat.make_mesh((2, 2), ("data", "model"))
    seen = {}

    def f(a, b):
        seen["both"] = compat.out_struct((2,), jnp.float32, a, b).vma
        seen["b"] = compat.out_struct((2,), jnp.float32, b).vma
        return a

    jax.jit(compat.shard_map(f, mesh=mesh, in_specs=(P("data"), P("model")),
                             out_specs=P("data")))(jnp.ones(4), jnp.ones(4))
    assert seen == {"both": frozenset({"data", "model"}),
                    "b": frozenset({"model"})}
    assert compat.out_struct((3,), jnp.float32, jnp.ones(3)).vma == frozenset()


def test_interpret_kernel_grads_inside_check_vma_shard_map():
    """An interpret-mode Pallas kernel (flash attention, custom VJP) traces,
    lowers and differentiates inside a check_vma=True shard_map on the CPU,
    and matches the same kernel outside it."""
    mesh = compat.make_mesh((2,), ("data",))
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (2, 16, 2, 8))
               for i in range(3))

    def loss(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v, block_q=8, block_k=8,
                                           interpret=True) ** 2)

    def sharded(q, k, v):
        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return tuple(lax.psum(x, "data") for x in g)

    spec = P("data")
    got = jax.jit(compat.shard_map(sharded, mesh=mesh,
                                   in_specs=(spec, spec, spec),
                                   out_specs=(P(), P(), P())))(q, k, v)
    halves = [jax.grad(loss, argnums=(0, 1, 2))(q[i:i + 1], k[i:i + 1],
                                                 v[i:i + 1]) for i in (0, 1)]
    for j in range(3):
        np.testing.assert_allclose(np.asarray(got[j]),
                                   np.asarray(halves[0][j] + halves[1][j]),
                                   rtol=1e-5, atol=1e-5)


def test_shard_map_resolves_and_runs_psum():
    """compat.shard_map runs a trivial psum program on the 8-device host."""
    mesh = compat.make_mesh((8,), ("data",))
    x = jnp.arange(8.0)

    def f(x_s):
        return lax.psum(x_s, "data")

    out = jax.jit(compat.shard_map(f, mesh=mesh, in_specs=(P("data"),),
                                   out_specs=P(None)))(x)
    np.testing.assert_allclose(np.asarray(out), np.full((1,), 28.0))


def test_shard_map_check_vma_kwarg_accepted():
    mesh = compat.make_mesh((8,), ("data",))
    x = jnp.arange(8.0)

    def f(x_s):
        return x_s * 2

    out = jax.jit(compat.shard_map(f, mesh=mesh, in_specs=(P("data"),),
                                   out_specs=P("data"), check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0) * 2)


def test_pvary_is_identity_valued():
    """compat.pvary only changes typing, never values."""
    mesh = compat.make_mesh((8,), ("data",))
    x = jnp.arange(8.0)

    def f(x_s):
        y = compat.pvary(x_s + 1.0, ("data",))
        z = compat.pvary_missing(y, ("data", None))
        return z

    out = jax.jit(compat.shard_map(f, mesh=mesh, in_specs=(P("data"),),
                                   out_specs=P("data")))(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0) + 1.0)
    # outside any mesh: plain identity
    np.testing.assert_allclose(np.asarray(compat.pvary(x, ())), np.asarray(x))
    np.testing.assert_allclose(np.asarray(compat.pvary_missing(x, (None,))),
                               np.asarray(x))


def test_vma_of_plain_array_is_empty():
    assert compat.vma_of(jnp.ones((3,))) == frozenset()


def test_make_mesh_shapes():
    mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
    assert mesh.axis_names == ("pod", "data", "model")
    assert mesh.devices.shape == (2, 2, 2)


def test_all_gather_invariant_values():
    mesh = compat.make_mesh((8,), ("data",))
    x = jnp.arange(8.0)

    def f(x_s):
        return compat.all_gather_invariant(x_s, "data", axis=0, tiled=True)

    out = jax.jit(compat.shard_map(f, mesh=mesh, in_specs=(P("data"),),
                                   out_specs=P(None)))(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0))


def test_grad_convention_row_parallel():
    """The semantic heart of the layer: Megatron-style TP gradients computed
    INSIDE shard_map match the single-device reference (psum transposing to
    the value-identity, the auto-inserted pvary supplying the f-collective's
    backward all-reduce)."""
    mesh = compat.make_mesh((2, 2), ("data", "model"))
    key = jax.random.PRNGKey(0)
    W = jax.random.normal(key, (8, 16))
    X = jax.random.normal(jax.random.fold_in(key, 1), (4, 8))
    ref = jax.grad(lambda W: jnp.sum(jnp.tanh(X @ W)))(W)

    def grad_fn(W_s, X_s):
        W_s = compat.pvary_missing(W_s, ("data",))

        def loss(W_s):
            y = lax.psum(X_s @ W_s, "model")
            return jnp.sum(jnp.tanh(y))

        return lax.psum(jax.grad(loss)(W_s), "data")

    fn = compat.shard_map(grad_fn, mesh=mesh,
                          in_specs=(P("model", None), P("data", "model")),
                          out_specs=P("model", None))
    out = jax.jit(fn)(W, X)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
