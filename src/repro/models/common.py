"""Shared model components: config, norms, RoPE, embeddings, losses.

All layer code in this package is *axis-aware*: it receives an ``AxisCtx``
naming the mesh axes it runs under (inside ``shard_map``) or ``None`` axes
when running single-device.  Collectives are inserted explicitly so the
communication schedule — the object of study of the paper — is visible in
the lowered HLO.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.compat import match_vma, pvary_missing  # noqa: F401

PyTree = Any


# ---------------------------------------------------------------------------
# Axis context
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class AxisCtx:
    """Mesh axis names visible to layer code (None = axis not present)."""

    data: str | None = None    # data parallel / ZeRO partition axis
    model: str | None = None   # tensor parallel axis (Megatron style)
    pod: str | None = None     # slow inter-pod axis (extra data parallelism)
    seq: str | None = None     # sequence-parallel axis for long-context decode
    expert: str | None = None  # axis sharding the MoE expert dim when it is
                               # NOT `model` (serving: experts over `data`,
                               # tokens exchanged via all_to_all)
    tp: int = 1                # static size of the `model` axis
    dp: int = 1                # static size of the `data` (x `pod`) axis
    ndata: int = 1             # static size of the `data` axis alone (ZeRO)

    def psum_model(self, x):
        return lax.psum(x, self.model) if self.model else x

    def psum_data(self, x):
        if self.data:
            x = lax.psum(x, self.data)
        if self.pod:
            x = lax.psum(x, self.pod)
        return x

    def model_size(self) -> int:
        return lax.psum(1, self.model) if self.model else 1

    def data_size(self) -> int:
        n = lax.psum(1, self.data) if self.data else 1
        if self.pod:
            n *= lax.psum(1, self.pod)
        return n


# pvary_missing / match_vma live in repro.compat (the shard_map/vma seam);
# re-exported above for the existing call sites.


# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture description covering dense / MoE / SSM / hybrid models."""

    name: str
    arch_type: str               # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0            # 0 -> d_model // num_heads
    hidden_act: str = "silu"     # silu | gelu
    glu: bool = True             # gated (SwiGLU/GeGLU) vs plain 2-layer MLP
    norm: str = "rmsnorm"        # rmsnorm | layernorm
    rope_theta: float = 10_000.0
    tie_embeddings: bool = False
    embed_scale: bool = False    # gemma-style sqrt(d_model) embedding scaling
    # --- attention extras -------------------------------------------------
    sliding_window: int = 0              # >0: window size used by "local" layers
    local_global_period: int = 0         # 0: all global. k>0: layer is global iff (i % k == k-1)
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    # --- MoE ---------------------------------------------------------------
    num_experts: int = 0
    experts_per_token: int = 0
    moe_dense_residual: bool = False     # arctic: dense FFN in parallel with experts
    moe_dense_ff: int = 0                # width of the parallel dense FFN
    router_aux_weight: float = 0.01
    # --- SSM / hybrid ------------------------------------------------------
    block_kind: str = "attn"             # attn | mamba | rwkv  (primary block)
    hybrid_attn_period: int = 0          # k>0: shared attn block applied after every k-th layer
    ssm_state: int = 0                   # mamba2 state dim per head
    ssm_head_dim: int = 64               # head size of the linear-recurrence heads
    rwkv_heads: int = 0                  # 0 -> d_model // ssm_head_dim (padded for TP)
    # --- modality frontend stubs -------------------------------------------
    input_mode: str = "tokens"           # tokens | embeddings | vlm
    vision_prefix_len: int = 0           # vlm: number of projected patch embeddings
    # --- numerics ----------------------------------------------------------
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # --- fused Pallas kernels (kernels/) -----------------------------------
    # Routes the training hot path (norms, flash attention, and — via the
    # step builders — the fused AdamW chunk update) through the custom-VJP
    # Pallas kernels.  Default on; set False to fall back to the pure-jnp
    # reference paths for debugging (interpret mode on CPU either way).
    kernels: bool = True

    # ------------------------------------------------------------------
    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // max(self.num_heads, 1))
        if self.block_kind == "rwkv" and self.rwkv_heads == 0:
            object.__setattr__(self, "rwkv_heads",
                               self.d_model // self.ssm_head_dim)

    @property
    def rwkv_inner(self) -> int:
        return self.rwkv_heads * self.ssm_head_dim

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def attn_free(self) -> bool:
        return self.block_kind in ("mamba", "rwkv") and self.hybrid_attn_period == 0

    @property
    def sub_quadratic(self) -> bool:
        """Whether the architecture supports long-context (500k) decode."""
        return self.block_kind in ("mamba", "rwkv") or (
            self.sliding_window > 0 and self.local_global_period > 0
        )

    # -- per-layer static tables (used inside lax.scan bodies) ----------
    def layer_windows(self) -> jnp.ndarray:
        """Per-layer attention window (0 = full/global attention)."""
        if self.local_global_period <= 0 or self.sliding_window <= 0:
            return jnp.zeros((self.num_layers,), jnp.int32)
        idx = jnp.arange(self.num_layers)
        is_global = (idx % self.local_global_period) == (self.local_global_period - 1)
        return jnp.where(is_global, 0, self.sliding_window).astype(jnp.int32)

    def attn_layer_flags(self) -> jnp.ndarray:
        """Hybrid models: 1 where the shared attention block runs after the layer."""
        if self.hybrid_attn_period <= 0:
            return jnp.zeros((self.num_layers,), jnp.int32)
        idx = jnp.arange(self.num_layers)
        return ((idx % self.hybrid_attn_period) == (self.hybrid_attn_period - 1)).astype(jnp.int32)

    def attn_slot_index(self) -> jnp.ndarray:
        """KV-cache slot for each layer (0 where the layer has no KV cache)."""
        if self.block_kind == "attn":
            return jnp.arange(self.num_layers, dtype=jnp.int32)
        flags = self.attn_layer_flags()
        return jnp.maximum(jnp.cumsum(flags) - 1, 0).astype(jnp.int32) * flags

    def num_attn_slots(self) -> int:
        if self.block_kind == "attn":
            return self.num_layers
        if self.hybrid_attn_period > 0:
            return self.num_layers // self.hybrid_attn_period
        return 0

    # -- windowed (ring) KV cache for local-attention layers -------------
    @property
    def has_window_cache(self) -> bool:
        return (self.block_kind == "attn" and self.sliding_window > 0
                and self.local_global_period > 0)

    def window_cache_tables(self):
        """(is_win [L], slot [L]): ring-buffer vs full-cache slot per layer."""
        win = self.layer_windows()
        is_win = (win > 0).astype(jnp.int32)
        slot_w = jnp.maximum(jnp.cumsum(is_win) - 1, 0)
        slot_g = jnp.maximum(jnp.cumsum(1 - is_win) - 1, 0)
        slot = jnp.where(is_win > 0, slot_w, slot_g)
        return is_win, slot

    def num_window_slots(self) -> tuple[int, int]:
        """(windowed slots, global slots) — pure python (trace-safe)."""
        if not self.has_window_cache:
            return 0, self.num_attn_slots()
        k = self.local_global_period
        n_w = sum(1 for i in range(self.num_layers) if i % k != k - 1)
        return n_w, self.num_layers - n_w

    # -- parameter counting (used by roofline + calculator) --------------
    def params_per_layer(self, *, active_only: bool = False) -> int:
        d, h = self.d_model, self.head_dim
        n = 0
        if self.block_kind == "attn":
            n += d * h * (self.num_heads + 2 * self.num_kv_heads) + self.num_heads * h * d
        elif self.block_kind == "mamba":
            heads = self.d_ff // self.ssm_head_dim
            # in-proj (x, z), B/C (shared across heads), dt proj, out-proj
            n += d * self.d_ff * 2 + d * (2 * self.ssm_state + heads) + self.d_ff * d
        elif self.block_kind == "rwkv":
            inner = self.rwkv_inner
            n += 6 * d * inner            # r,k,v,g,w,time_out projections
            n += 2 * d * self.d_ff + d * d  # channel mix (cm_k, cm_v, cm_r)
        if self.is_moe:
            e = self.experts_per_token if active_only else self.num_experts
            mult = 3 if self.glu else 2
            n += e * mult * d * self.d_ff + d * self.num_experts
            if self.moe_dense_residual:
                n += mult * d * (self.moe_dense_ff or self.d_ff)
        elif self.block_kind == "attn":
            mult = 3 if self.glu else 2
            n += mult * d * self.d_ff
        return n

    def param_count(self, *, active_only: bool = False) -> int:
        n = self.num_layers * self.params_per_layer(active_only=active_only)
        if self.hybrid_attn_period > 0:
            d, h = self.d_model, self.head_dim
            n += d * h * (self.num_heads + 2 * self.num_kv_heads) + self.num_heads * h * d
            n += (3 if self.glu else 2) * d * self.d_ff
        n += self.vocab_size * self.d_model * (1 if self.tie_embeddings else 2)
        return n

    # -- tensor-parallel head padding ------------------------------------
    def padded_for_tp(self, tp: int) -> "ModelConfig":
        """Pad head counts so they divide the tensor-parallel axis.

        Extra heads are zero-initialised so outputs are unchanged; the waste is
        reported through the useful-FLOPs ratio in the roofline analysis.
        """
        nh = self.num_heads
        if nh % tp != 0:
            nh = ((nh + tp - 1) // tp) * tp
        dff = ((self.d_ff + tp - 1) // tp) * tp
        changes = {}
        if nh != self.num_heads:
            changes["num_heads"] = nh
        if dff != self.d_ff:
            changes["d_ff"] = dff
        if self.block_kind == "mamba":
            heads = self.d_ff // self.ssm_head_dim
            if heads % tp != 0:
                heads = ((heads + tp - 1) // tp) * tp
                changes["d_ff"] = heads * self.ssm_head_dim
        if self.block_kind == "rwkv":
            heads = self.rwkv_heads
            if heads % tp != 0:
                changes["rwkv_heads"] = ((heads + tp - 1) // tp) * tp
        if not changes:
            return self
        return dataclasses.replace(self, **changes)


# ---------------------------------------------------------------------------
# Normalisation
# ---------------------------------------------------------------------------
def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, *, eps: float = 1e-6,
             plus_one: bool = False) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(var + eps)
    s = (1.0 + scale.astype(jnp.float32)) if plus_one else scale.astype(jnp.float32)
    return (y * s).astype(dt)


def layer_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               *, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def apply_norm(cfg: ModelConfig, p: PyTree, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.norm == "layernorm":
        return layer_norm(x, p["scale"], p["bias"])
    if cfg.kernels:
        from repro.kernels import ops as kops
        return kops.rmsnorm(x, p["scale"], plus_one=cfg.norm == "rmsnorm_p1")
    return rms_norm(x, p["scale"], plus_one=cfg.norm == "rmsnorm_p1")


def init_norm(cfg: ModelConfig, d: int) -> PyTree:
    dt = jnp.dtype(cfg.param_dtype)
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)}
    if cfg.norm == "rmsnorm_p1":
        return {"scale": jnp.zeros((d,), dt)}
    return {"scale": jnp.ones((d,), dt)}


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., S, H, D]; positions: [..., S] (broadcastable)."""
    freqs = rope_freqs(x.shape[-1], theta)
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # [..., S, 1, D/2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------
def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    if name == "relu":
        return jax.nn.relu
    raise ValueError(f"unknown activation {name!r}")


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    return cap * jnp.tanh(x / cap) if cap > 0 else x


# ---------------------------------------------------------------------------
# Embedding / LM head (vocab sharded over the model axis)
# ---------------------------------------------------------------------------
def embed_tokens(cfg: ModelConfig, embed: jnp.ndarray, tokens: jnp.ndarray,
                 axis: AxisCtx) -> jnp.ndarray:
    """embed: [V_local, D] (vocab-sharded over `model`).  tokens: [..., S]."""
    if axis.model:
        vocab_local = embed.shape[0]
        shard = lax.axis_index(axis.model)
        lo = shard * vocab_local
        local_ids = jnp.clip(tokens - lo, 0, vocab_local - 1)
        mask = (tokens >= lo) & (tokens < lo + vocab_local)
        x = jnp.take(embed, local_ids, axis=0) * mask[..., None].astype(embed.dtype)
        x = lax.psum(x, axis.model)
    else:
        x = jnp.take(embed, tokens, axis=0)
    x = x.astype(jnp.dtype(cfg.dtype))
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def lm_head_loss(cfg: ModelConfig, head: jnp.ndarray, x: jnp.ndarray,
                 labels: jnp.ndarray, mask: jnp.ndarray, axis: AxisCtx) -> jnp.ndarray:
    """Distributed softmax cross-entropy over a vocab-sharded head.

    head: [V_local, D]; x: [B, S, D]; labels/mask: [B, S].
    Returns the summed (not averaged) loss; the caller normalises so that
    micro-batch accumulation stays linear.
    """
    logits = jnp.einsum("bsd,vd->bsv", x, head.astype(x.dtype)).astype(jnp.float32)
    logits = softcap(logits, cfg.final_logit_softcap)
    if axis.model:
        vocab_local = head.shape[0]
        shard = lax.axis_index(axis.model)
        lo = shard * vocab_local
        # stabilizer only — constant w.r.t. AD (pmax lacks an AD rule, so the
        # cross-shard max is taken over an all_gather of the local maxima)
        local_m = lax.stop_gradient(jnp.max(logits, axis=-1))
        m = jnp.max(lax.all_gather(local_m, axis.model, axis=0), axis=0)
        se = lax.psum(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), axis.model)
        local_ids = jnp.clip(labels - lo, 0, vocab_local - 1)
        in_range = (labels >= lo) & (labels < lo + vocab_local)
        picked = jnp.take_along_axis(logits, local_ids[..., None], axis=-1)[..., 0]
        picked = lax.psum(picked * in_range.astype(jnp.float32), axis.model)
    else:
        m = jnp.max(logits, axis=-1)
        se = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
        picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (m + jnp.log(se) - picked) * mask.astype(jnp.float32)
    total = jnp.sum(nll)
    if axis.model:
        # value is already replicated across `model` (the stabilizer came from
        # an all_gather); this scalar psum/size only restores the invariant
        # typing for the vma machinery.
        total = lax.psum(total, axis.model) / lax.psum(1.0, axis.model)
    return total


def lm_logits(cfg: ModelConfig, head: jnp.ndarray, x: jnp.ndarray,
              axis: AxisCtx) -> jnp.ndarray:
    """Full logits for decoding: [B, S, V_local] (still vocab-sharded)."""
    logits = jnp.einsum("bsd,vd->bsv", x, head.astype(x.dtype)).astype(jnp.float32)
    return softcap(logits, cfg.final_logit_softcap)


# ---------------------------------------------------------------------------
# Initialisation helpers
# ---------------------------------------------------------------------------
def dense_init(key, shape, dtype, *, scale: float | None = None) -> jnp.ndarray:
    fan_in = shape[0]
    s = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)
