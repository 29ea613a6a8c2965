"""Pallas kernels vs the jnp oracles: forward sweeps, VJP parity, the fused
AdamW chunk update, and the no-O(S²)-backward guarantee — all in interpret
mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import flash_attention_ref, rmsnorm_ref

SHAPES = [(2, 64, 4, 2, 32), (1, 128, 8, 8, 64), (2, 48, 4, 1, 32),
          (1, 96, 6, 3, 16)]
VARIANTS = [(0, 0.0), (16, 0.0), (0, 30.0), (24, 50.0)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("window,cap", VARIANTS)
def test_flash_attention(shape, window, cap):
    B, S, Hq, Hkv, D = shape
    key = jax.random.PRNGKey(B * S + window)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    out = ops.flash_attention(q, k, v, causal=True, window=window, softcap=cap,
                              block_q=32, block_k=32)
    ref = flash_attention_ref(q, k, v, causal=True, window=window, softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (1, 64, 4, 32), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 64, 2, 32), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 64, 2, 32), dtype)
    out = ops.flash_attention(q, k, v, block_q=32, block_k=32)
    ref = flash_attention_ref(q, k, v)
    assert out.dtype == dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_flash_attention_noncausal_padding_masked():
    """Regression: padded key rows must be masked explicitly — for the
    non-causal (or windowed non-causal) case causality does not exclude
    them, and before the kv_len in-kernel mask they leaked into the
    softmax."""
    key = jax.random.PRNGKey(3)
    B, S, H, D = 1, 40, 2, 16                 # S=40 pads to 64 with 32-blocks
    q = jax.random.normal(key, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, D))
    for window in (0, 12):
        out = ops.flash_attention(q, k, v, causal=False, window=window,
                                  block_q=32, block_k=32)
        ref = flash_attention_ref(q, k, v, causal=False, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4, err_msg=f"w={window}")


# ---------------------------------------------------------------------------
# VJP parity vs reference autodiff
# ---------------------------------------------------------------------------
GRAD_CASES = [
    # (shape, window, cap, causal)
    ((2, 64, 4, 2, 32), 0, 0.0, True),
    ((1, 96, 6, 3, 16), 24, 50.0, True),      # window + softcap + GQA
    ((2, 48, 4, 1, 32), 16, 0.0, True),       # full replication (Hkv=1)
    ((1, 80, 4, 4, 32), 0, 30.0, False),      # non-causal + softcap
    ((1, 50, 2, 1, 16), 12, 0.0, False),      # odd S (padded), windowed
]


@pytest.mark.parametrize("shape,window,cap,causal", GRAD_CASES)
def test_flash_attention_grads(shape, window, cap, causal):
    B, S, Hq, Hkv, D = shape
    key = jax.random.PRNGKey(S + window)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    f = lambda q, k, v: jnp.sum(jnp.sin(ops.flash_attention(
        q, k, v, causal=causal, window=window, softcap=cap,
        block_q=32, block_k=32)))
    fr = lambda q, k, v: jnp.sum(jnp.sin(flash_attention_ref(
        q, k, v, causal=causal, window=window, softcap=cap)))
    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5, err_msg=f"d{name}")


def test_flash_attention_grads_bf16():
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(key, (1, 64, 4, 32), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 64, 2, 32),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 64, 2, 32),
                          jnp.bfloat16)
    f = lambda q, k, v: jnp.sum(ops.flash_attention(
        q, k, v, window=16, block_q=32, block_k=32).astype(jnp.float32))
    fr = lambda q, k, v: jnp.sum(flash_attention_ref(
        q, k, v, window=16).astype(jnp.float32))
    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g, gr):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-2, err_msg=f"d{name}")


# ---------------------------------------------------------------------------
# The planned tiles (no block size given): several k-tiles, edge and interior
# tiles, padded kv_len tails
# ---------------------------------------------------------------------------
PLANNED_CASES = [
    # (S, Hkv, window, cap, causal, dtype); Hq = 2
    (384, 2, 0, 0.0, True, jnp.float32),
    (640, 1, 400, 0.0, True, jnp.float32),     # GQA + window
    (600, 2, 0, 30.0, True, jnp.float32),      # softcap, padded to 640
    (1000, 1, 0, 50.0, True, jnp.float32),     # 512 tiles, padded to 1024
    (600, 1, 160, 0.0, False, jnp.float32),    # non-causal window, padded
    (640, 2, 0, 0.0, True, jnp.bfloat16),
    (1000, 1, 0, 30.0, True, jnp.bfloat16),
]


def _planned_inputs(S, Hkv, dtype):
    ks = jax.random.split(jax.random.PRNGKey(S + Hkv), 3)
    return (jax.random.normal(ks[0], (1, S, 2, 64), dtype),
            jax.random.normal(ks[1], (1, S, Hkv, 64), dtype),
            jax.random.normal(ks[2], (1, S, Hkv, 64), dtype))


def _assert_edge_and_interior(S, Hkv, window, causal, dtype):
    from repro.kernels.flash_attention import block_sizes, tile_counts
    bq, bk = block_sizes(S, 64, dtype, 2 // Hkv)
    S_pad = -(-S // max(bq, bk)) * max(bq, bk)
    masked, free = tile_counts(S_pad, bq, bk, causal=causal, window=window,
                               kv_len=S)
    assert S_pad > bk and masked > 0 and free > 0, (bq, bk, masked, free)


@pytest.mark.parametrize("S,Hkv,window,cap,causal,dtype", PLANNED_CASES)
def test_flash_attention_planned_tiles(S, Hkv, window, cap, causal, dtype):
    _assert_edge_and_interior(S, Hkv, window, causal, dtype)
    q, k, v = _planned_inputs(S, Hkv, dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=cap)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window,
                              softcap=cap)
    assert out.dtype == dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("S,Hkv,window,cap,causal,dtype", PLANNED_CASES)
def test_flash_attention_grads_planned_tiles(S, Hkv, window, cap, causal,
                                             dtype):
    q, k, v = _planned_inputs(S, Hkv, dtype)
    f = lambda q, k, v: jnp.sum(jnp.sin(ops.flash_attention(
        q, k, v, causal=causal, window=window, softcap=cap
    ).astype(jnp.float32)))
    fr = lambda q, k, v: jnp.sum(jnp.sin(flash_attention_ref(
        q, k, v, causal=causal, window=window, softcap=cap
    ).astype(jnp.float32)))
    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    for name, a, b in zip("qkv", g, gr):
        assert a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol, err_msg=f"d{name}")


@pytest.mark.parametrize("S,D,rep,plan,counts", [
    (512, 64, 1, (512, 512), (1, 0)),       # X_32, s512 cell: one tile
    (4096, 64, 1, (512, 512), (8, 28)),     # X_32, s4096 cell
    (4096, 128, 1, (512, 512), (8, 28)),    # head dim 128, MHA
    (8192, 64, 1, (256, 512), (32, 240)),   # 512 x 512 is refused on v5e
    (640, 64, 1, (128, 128), (5, 10)),      # larger tiles would pad S
    (2048, 128, 4, (256, 512), (8, 12)),    # 32 q / 8 kv heads: 4 staged
    (2048, 256, 2, (256, 512), (8, 12)),    # gemma-2 widths
    (500, 64, 1, (512, 512), (1, 0)),       # padded to one 512 tile
    (100, 64, 1, (128, 128), (1, 0)),       # short: one aligned tile
])
def test_tile_plan(S, D, rep, plan, counts):
    """The planned (block_q, block_k) and the (masked, mask-free) tiles a
    head walks per causal call of the padded sequence; 128-row tiles at
    S = 4096 walked 32 masked and 496 mask-free."""
    from repro.kernels.flash_attention import tile_counts, tile_plan
    assert tile_plan(S, D, jnp.bfloat16, rep) == plan
    S_pad = -(-S // max(plan)) * max(plan)
    assert tile_counts(S_pad, *plan, kv_len=S) == counts
    assert tile_counts(4096, 128, 128) == (32, 496)


@pytest.mark.parametrize("given", [(None, None), (32, 32), (128, None),
                                   (None, 64), (1024, 1024)])
def test_block_sizes_hold_after_padding(given):
    """ops pads S to what ``block_sizes`` picks; the kernels, asked again on
    the padded length, pick the same sizes, and the padding stays under
    one tile."""
    import math
    from repro.kernels.flash_attention import block_sizes
    for S in range(1, 2100, 7):
        for D, rep in ((64, 1), (128, 4), (256, 2)):
            bq, bk = block_sizes(S, D, jnp.bfloat16, rep, *given)
            mult = bq * bk // math.gcd(bq, bk)
            S_pad = -(-S // mult) * mult
            assert S_pad - S < max(bq, bk), (S, D, rep, bq, bk)
            assert block_sizes(S_pad, D, jnp.bfloat16, rep, *given) \
                == (bq, bk), (S, D, rep)


def _pallas_calls(fn, *args):
    """(grid, first operand's shape) of every pallas_call in fn's jaxpr."""
    calls = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.append((tuple(eqn.params["grid_mapping"].grid),
                              eqn.invars[0].aval.shape))
            for val in eqn.params.values():
                for u in (val if isinstance(val, (tuple, list)) else (val,)):
                    if hasattr(u, "jaxpr") and hasattr(u.jaxpr, "eqns"):
                        walk(u.jaxpr)
                    elif hasattr(u, "eqns"):
                        walk(u)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return calls


@pytest.mark.parametrize("S,tile", [(200, 256), (500, 512)])
def test_flash_attention_pads_to_the_planned_tile(S, tile):
    """A sequence one planned tile covers is padded up to that tile, not
    run as a tile of S rows (500 is no multiple of the v5e's 8 sublanes):
    forward, dq and dkv each walk one tile a head."""
    q, k, v = _planned_inputs(S, 1, jnp.float32)
    loss = lambda f: lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v, softcap=30.0)))
    grad = jax.grad(loss(ops.flash_attention), argnums=(0, 1, 2))
    calls = _pallas_calls(grad, q, k, v)
    assert sorted(calls) == [((1, 1, 1), (1, 1, 2, tile, 64))] \
        + [((1, 2, 1), (1, 2, tile, 64))] * 2, calls
    g = grad(q, k, v)
    gr = jax.grad(loss(flash_attention_ref), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5, err_msg=f"d{name}")


def test_flash_bwd_padded_keys_get_no_gradient():
    """The backward kernels called on a padded sequence: keys past
    ``kv_len`` are masked in dk/dv as in the forward (non-causal, so only
    the kv_len tail masks the last k-tile)."""
    from repro.kernels import flash_attention as fa
    S, kv_len = 640, 600
    q, k, v = _planned_inputs(S, 2, jnp.float32)
    do = jax.random.normal(jax.random.PRNGKey(5), q.shape)
    out, lse = fa.flash_attention_fwd(q, k, v, causal=False, kv_len=kv_len,
                                      interpret=True)
    dq, dk, dv = fa.flash_attention_bwd(q, k, v, out, lse, do, causal=False,
                                        kv_len=kv_len, interpret=True)
    assert not np.any(np.asarray(dk[:, kv_len:]))
    assert not np.any(np.asarray(dv[:, kv_len:]))
    assert np.all(np.isfinite(np.asarray(dq)))


@pytest.mark.parametrize("rows,d", [(16, 128), (37, 256), (4, 512), (256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(rows, d, dtype):
    key = jax.random.PRNGKey(rows + d)
    x = jax.random.normal(key, (rows, d), dtype)
    s = jax.random.normal(jax.random.fold_in(key, 1), (d,))
    out = ops.rmsnorm(x, s)
    ref = rmsnorm_ref(x, s)
    assert out.dtype == dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("rows,d,plus_one", [(37, 256, False), (16, 128, True),
                                             (300, 64, True)])
def test_rmsnorm_grads(rows, d, plus_one):
    key = jax.random.PRNGKey(rows + d)
    x = jax.random.normal(key, (rows, d))
    s = jax.random.normal(jax.random.fold_in(key, 1), (d,))

    def ref(x, s):
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        se = (1.0 + s) if plus_one else s
        return x32 * jax.lax.rsqrt(var + 1e-6) * se

    f = lambda x, s: jnp.sum(jnp.cos(ops.rmsnorm(x, s, plus_one=plus_one)))
    fr = lambda x, s: jnp.sum(jnp.cos(ref(x, s)))
    g, gr = jax.grad(f, (0, 1))(x, s), jax.grad(fr, (0, 1))(x, s)
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(gr[0]),
                               rtol=1e-5, atol=1e-5, err_msg="dx")
    np.testing.assert_allclose(np.asarray(g[1]), np.asarray(gr[1]),
                               rtol=1e-5, atol=1e-5, err_msg="dscale")


# ---------------------------------------------------------------------------
# No O(S²) intermediate in the lowered backward
# ---------------------------------------------------------------------------
def _walk_avals(jaxpr, visit):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            visit(v.aval)
        for val in eqn.params.values():
            for u in (val if isinstance(val, (tuple, list)) else (val,)):
                if hasattr(u, "jaxpr") and hasattr(u.jaxpr, "eqns"):
                    _walk_avals(u.jaxpr, visit)
                elif hasattr(u, "eqns"):
                    _walk_avals(u, visit)


def _max_quadratic_dims(fn, *args, S):
    """Largest count of >=S dims in any intermediate of fn's jaxpr."""
    jpr = jax.make_jaxpr(fn)(*args)
    worst = [0]

    def visit(aval):
        if hasattr(aval, "shape"):
            worst[0] = max(worst[0], sum(1 for d in aval.shape if d >= S))

    _walk_avals(jpr.jaxpr, visit)
    return worst[0]


def test_no_quadratic_intermediate_in_backward():
    """jax.grad through the flash custom VJP must never materialise an
    [S, S]-shaped value — the whole point of the tiled backward.  The
    reference path is the positive control (it does)."""
    S, D = 256, 64
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, S, 2, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, S, 1, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, S, 1, D))
    g = jax.grad(lambda q, k, v: jnp.sum(ops.flash_attention(
        q, k, v, window=64, softcap=30.0, block_q=64, block_k=64)),
        argnums=(0, 1, 2))
    gr = jax.grad(lambda q, k, v: jnp.sum(flash_attention_ref(
        q, k, v, window=64, softcap=30.0)), argnums=(0, 1, 2))
    assert _max_quadratic_dims(g, q, k, v, S=S) <= 1
    assert _max_quadratic_dims(gr, q, k, v, S=S) >= 2   # ref: [.., S, S] logits


# ---------------------------------------------------------------------------
# End-to-end: kernels on vs off through a transformer block
# ---------------------------------------------------------------------------
def _block_cfg(**kw):
    import dataclasses
    from repro.models.common import ModelConfig
    base = ModelConfig(name="k", arch_type="dense", num_layers=4, d_model=64,
                       num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=97,
                       sliding_window=16, local_global_period=2,
                       attn_logit_softcap=30.0,
                       dtype="float32", param_dtype="float32")
    return dataclasses.replace(base, **kw)


def test_flash_attention_in_model_layer():
    """use_pallas=True end-to-end through a dense layer forward."""
    from repro.models import transformer as T
    from repro.models.common import AxisCtx
    cfg = _block_cfg(sliding_window=0, local_global_period=0,
                     attn_logit_softcap=0.0, num_layers=2)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 97)
    batch = {"tokens": toks, "labels": toks, "mask": jnp.ones_like(toks)}
    x_ref, _ = T.forward(cfg, params, batch, AxisCtx(), remat=False,
                         use_pallas=False)
    x_pal, _ = T.forward(cfg, params, batch, AxisCtx(), remat=False,
                         use_pallas=True)
    np.testing.assert_allclose(np.asarray(x_pal), np.asarray(x_ref),
                               rtol=2e-4, atol=2e-4)


def test_block_grad_kernels_on_matches_off():
    """Acceptance: jax.grad through a transformer block (windowed layers via
    the per-layer table, softcap, GQA) with kernels on == kernels off within
    fp32 1e-5."""
    import dataclasses
    from repro.models import transformer as T
    from repro.models.common import AxisCtx
    cfg_on = _block_cfg()
    cfg_off = dataclasses.replace(cfg_on, kernels=False)
    params = T.init_params(cfg_on, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, 97)
    batch = {"tokens": toks, "labels": toks, "mask": jnp.ones_like(toks)}

    def loss(cfg):
        def f(p):
            l, _ = T.loss_fn(cfg, p, batch, AxisCtx(), remat=True)
            return l
        return f

    g_on = jax.jit(jax.grad(loss(cfg_on)))(params)
    g_off = jax.jit(jax.grad(loss(cfg_off)))(params)
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(g_on),
                                 jax.tree_util.tree_leaves_with_path(g_off)):
        a, b = np.asarray(a), np.asarray(b)
        # fp32 1e-5, scale-aware: atol relative to the leaf's grad magnitude
        scale = max(float(np.max(np.abs(b))), 1.0)
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5 * scale,
                                   err_msg=str(path))


# ---------------------------------------------------------------------------
# Fused AdamW chunk update
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("block_rows", [None, 2])   # auto + forced tiling
def test_fused_adamw_matches_treemap(block_rows):
    from repro.kernels import adamw as aw

    key = jax.random.PRNGKey(0)
    p = jax.random.normal(key, (4, 1, 1, 700))       # odd chunk: pad path
    m = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), p.shape)
    v = jnp.abs(0.1 * jax.random.normal(jax.random.fold_in(key, 2), p.shape))
    g = 0.3 * jax.random.normal(jax.random.fold_in(key, 3), p.shape)
    lr, b1, b2, eps, wd = 1e-3, 0.9, 0.95, 1e-8, 0.1
    b1c, b2c, gs = 0.19, 0.0975, 0.7
    sc = jnp.array([lr, b1c, b2c, gs], jnp.float32)
    po, mo, vo = aw.adamw_update(p, m, v, g, sc, b1=b1, b2=b2, eps=eps, wd=wd,
                                 block_rows=block_rows, interpret=True)
    gsd = g * gs
    m32 = b1 * m + (1 - b1) * gsd
    v32 = b2 * v + (1 - b2) * jnp.square(gsd)
    pref = p - lr * ((m32 / b1c) / (jnp.sqrt(v32 / b2c) + eps) + wd * p)
    # same float ops; only FMA contraction may differ between lowerings
    np.testing.assert_allclose(np.asarray(po), np.asarray(pref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(mo), np.asarray(m32),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vo), np.asarray(v32),
                               rtol=1e-6, atol=1e-6)
