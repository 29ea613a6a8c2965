"""Plain reference of the dense pre-norm language model and its AdamW steps.

Written from the configuration's description alone and in float32 at
``Precision.HIGHEST``, with no kernels, no cache, no micro-batch schedule and
no partition.  It imports nothing of the program.

    x = embed[tokens]
    for each layer:
        h = LayerNorm(x; ln1)          (eps 1e-5)
        q, k, v = h wq, h wk, h wv     (heads of head_dim; rotary positions,
                                        rotate-half, theta rope_theta)
        x = x + softmax(causal(q k^T / sqrt(head_dim))) v wo
        h = LayerNorm(x; ln2)
        x = x + gelu_tanh(h w_up) w_down
    logits = LayerNorm(x; final) head^T
    loss = mean over tokens of the cross entropy of the next token

AdamW: global-norm clipping, bias-corrected moments, decoupled weight decay
on every leaf, learning rate by linear warm-up and cosine decay.

``Numerics`` chooses the precision: ``FP32`` is the reference; ``FP8``, the
control, rounds both operands of every matrix product to float8 e4m3 with a
per-tensor scale (products accumulated in float32), the step below the
bfloat16 that the configurations state.

The weights are sharded over a one-axis mesh of the cell's chips, each leaf
on its widest dimension; each layer's weights are gathered whole inside the
layer loop, and the rows of a block are spread over the chips.  A step runs
over the batch in blocks of rows, with each layer's activations recomputed
in the backward pass, so that it fits beside nothing else on the chip.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from bench import weights

HIGHEST = lax.Precision.HIGHEST
AXIS = "r"


def _identity(x):
    return x


def _fp8(x):
    """Per-tensor scaled float8 e4m3 rounding; the gradient passes through."""
    amax = lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = 448.0 / jnp.maximum(amax, 1e-30)
    q = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + lax.stop_gradient(q - x)


@dataclasses.dataclass(frozen=True)
class Numerics:
    name: str
    quant: Callable


FP32 = Numerics("float32", _identity)
FP8 = Numerics("float8_e4m3", _fp8)


def _mm(eq, a, b, num: Numerics):
    return jnp.einsum(eq, num.quant(a), num.quant(b), precision=HIGHEST)


def _layer_norm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _rope(x, theta):
    """x: [B, S, H, hd]; rotate-half rotary positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs      # [S, hd/2]
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]      # [S, 1, hd/2]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, lp, x, num):
    B, S, _ = x.shape
    H, KV, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    h = _layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
    q = _mm("bsd,dh->bsh", h, lp["wq"], num).reshape(B, S, H, hd)
    k = _mm("bsd,dh->bsh", h, lp["wk"], num).reshape(B, S, KV, hd)
    v = _mm("bsd,dh->bsh", h, lp["wv"], num).reshape(B, S, KV, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    logits = _mm("bqhd,bkhd->bhqk", q, k, num) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    logits = jnp.where(causal, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    o = _mm("bhqk,bkhd->bqhd", p, v, num).reshape(B, S, H * hd)
    x = x + _mm("bsh,hd->bsd", o, lp["wo"], num)
    h = _layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
    u = _gelu_tanh(_mm("bsd,df->bsf", h, lp["w_up"], num))
    return x + _mm("bsf,fd->bsd", u, lp["w_down"], num)


def nll_sum(cfg, num, w, tokens, labels, mask, whole=None):
    """Summed next-token cross entropy of a block of rows; ``whole`` (a
    replicated sharding) gathers each layer's weights inside the loop."""
    replicated = lambda t: t if whole is None else jax.tree.map(  # noqa: E731
        lambda a: lax.with_sharding_constraint(a, whole), t)
    x = jnp.take(w["embed"], tokens, axis=0)

    @jax.checkpoint
    def body(x, lp):
        return _layer(cfg, replicated(lp), x, num), None

    x, _ = lax.scan(body, x, w["layers"])
    x = _layer_norm(x, w["final_scale"], w["final_bias"])
    logits = _mm("bsd,vd->bsv", x, w["head"], num)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - picked) * mask)


def learning_rate(opt: dict, t: int) -> float:
    warm = min(t / max(opt["warmup_steps"], 1), 1.0)
    frac = min(max((t - opt["warmup_steps"]) / max(opt["decay_steps"], 1),
                   0.0), 1.0)
    r = opt["min_lr_ratio"]
    return opt["lr"] * warm * (r + (1 - r) * 0.5 * (1 + math.cos(math.pi * frac)))


def leaf_sharding(mesh: Mesh, shape: tuple, layered: bool) -> NamedSharding:
    """The leaf's widest dimension (past the layer dim) over all chips."""
    n = mesh.devices.size
    dims = list(range(1 if layered else 0, len(shape)))
    best = max(dims, key=lambda d: shape[d])
    spec = [None] * len(shape)
    if n > 1 and shape[best] % n == 0:
        spec[best] = AXIS
    return NamedSharding(mesh, P(*spec))


class Reference:
    """The reference run over the first steps of one seed's batches, from
    the weights ``bench.weights`` makes of the seed's key (the program is
    given the same).  ``mesh`` is a one-axis mesh named ``"r"`` over the
    cell's chips.
    """

    def __init__(self, cfg: dict, mesh: Mesh, num: Numerics):
        self.cfg, self.mesh, self.num = cfg, mesh, num
        self.opt = cfg["optimizer"]
        self.w_shard = {
            k: (leaf_sharding(mesh, s, False) if k != "layers" else
                {n: leaf_sharding(mesh, ls, True) for n, ls in s.items()})
            for k, s in weights.shapes(cfg).items()}
        self.make = jax.jit(lambda key: weights.make(cfg, key),
                            out_shardings=self.w_shard)
        self._norms = jax.jit(weights.leaf_norms)
        self._change = jax.jit(lambda w, key: weights.leaf_norms(
            jax.tree.map(jnp.subtract, w, weights.make(cfg, key))))
        rows = NamedSharding(mesh, P(AXIS))
        whole = NamedSharding(mesh, P())
        zeros = lambda w: jax.tree.map(jnp.zeros_like, w)       # noqa: E731
        self._zeros = jax.jit(zeros, out_shardings=self.w_shard)

        def accumulate(acc, loss, w, tokens, labels, mask, inv_n):
            l, g = jax.value_and_grad(
                lambda w_: nll_sum(cfg, num, w_, tokens, labels, mask,
                                   whole))(w)
            acc = jax.tree.map(lambda a, b: a + b * inv_n, acc, g)
            return acc, loss + l * inv_n

        self._acc = jax.jit(accumulate, donate_argnums=(0,),
                            in_shardings=(self.w_shard, None, self.w_shard,
                                          rows, rows, rows, None),
                            out_shardings=(self.w_shard, None))
        o = self.opt

        def adam(w, m, v, g, lr, t):
            gsq = sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g))
            gnorm = jnp.sqrt(gsq + 1e-16)
            scale = (jnp.minimum(1.0, o["grad_clip"] / gnorm)
                     if o["grad_clip"] > 0 else 1.0)
            g = jax.tree.map(lambda x: x * scale, g)
            m = jax.tree.map(lambda a, b: o["b1"] * a + (1 - o["b1"]) * b, m, g)
            v = jax.tree.map(lambda a, b: o["b2"] * a
                             + (1 - o["b2"]) * jnp.square(b), v, g)
            c1 = 1 - o["b1"] ** t
            c2 = 1 - o["b2"] ** t
            w = jax.tree.map(
                lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2)
                                                       + o["eps"])
                                          + o["weight_decay"] * p), w, m, v)
            return w, m, v, g

        self._adam = jax.jit(adam, donate_argnums=(0, 1, 2),
                             out_shardings=(self.w_shard,) * 4)

    def run(self, key, batches: list, *, rows: int, drop_half: bool = False):
        """Follow ``len(batches)`` steps from the weights of ``key``.

        ``batches``: host batches with leaves ``[M, B/M, S]``.  With
        ``drop_half`` the second half of each batch's rows is left out and
        the mean taken over the rest (a planted fault).  Returns
        ``(losses, grad_norms, change_norms)``: the loss of each step, the
        norm of each leaf of the first step's gradient as the optimizer gets
        it (clipped), and of each leaf's change over all the steps.
        """
        w = self.make(key)
        m, v = self._zeros(w), self._zeros(w)
        losses = []
        for t, batch in enumerate(batches, start=1):
            flat = {k: np.asarray(a).reshape(-1, a.shape[-1])
                    for k, a in batch.items()}
            mask = flat["mask"].astype(np.float32)
            if drop_half:
                mask[mask.shape[0] // 2:] = 0.0
            inv_n = np.float32(1.0 / mask.sum())
            acc, loss = self._zeros(w), jnp.zeros((), jnp.float32)
            for r in range(0, mask.shape[0], rows):
                sl = slice(r, r + rows)
                acc, loss = self._acc(acc, loss, w, flat["tokens"][sl],
                                      flat["labels"][sl], mask[sl], inv_n)
            losses.append(float(loss))
            w, m, v, g = self._adam(w, m, v, acc,
                                    np.float32(learning_rate(self.opt, t)),
                                    np.float32(t))
            del acc
            if t == 1:
                grad_norms = weights.flatten_norms(self._norms(g))
            del g
        change = weights.flatten_norms(self._change(w, key))
        return losses, grad_norms, change
