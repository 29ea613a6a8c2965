"""Operations and bytes the algorithm needs, computed from shapes.

Nothing that is recomputed counts: a step's FLOPs are those of one forward
and one backward pass (the backward twice the forward), however the program
schedules them.  The input embedding is a gather and counts no FLOPs.
"""
from __future__ import annotations


def _attn_width(cfg: dict) -> int:
    return cfg["num_heads"] * cfg["head_dim"]


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product per token: every layer's
    projections and feed-forward, and the output head."""
    D, F, hd = cfg["d_model"], cfg["d_ff"], cfg["head_dim"]
    qo = 2 * D * cfg["num_heads"] * hd
    kv = 2 * D * cfg["num_kv_heads"] * hd
    ffn = (3 if cfg["glu"] else 2) * D * F
    return cfg["num_layers"] * (qo + kv + ffn) + cfg["vocab_size"] * D


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Causal attention, forward and backward: per layer and token,
    2 S d_attn forward (q k^T and p v over S/2 keys on average) and twice
    that backward, 6 S d_attn in all."""
    return 6.0 * seq_len * _attn_width(cfg) * cfg["num_layers"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 6.0 * matmul_params(cfg) + attention_flops_per_token(cfg, seq_len)


def param_count(cfg: dict) -> int:
    """Every stored parameter, norms and embedding included."""
    D = cfg["d_model"]
    norms = 4 * D * cfg["num_layers"] + 2 * D
    return matmul_params(cfg) + cfg["vocab_size"] * D + norms


def flash_attention(cfg: dict, seq_len: int, rows: int,
                    compute_bytes: int = 2) -> dict:
    """{kernel: (FLOPs, bytes)} of one call of each flash attention kernel
    (``fwd``, ``dq``, ``dkv``): one layer, ``rows`` causal sequences.

    FLOPs: each kernel is given two causal products of 2 S^2 hd / 2 per head
    and row: the forward q k^T and p v; dq the dO v^T it needs for dS and dS
    k; dkv p^T dO and dS^T q (the scores and dO v^T that it recomputes do
    not count).  Together ``attention_flops_per_token`` per layer.  Bytes:
    the forward reads q, k, v and writes o and the log-sum-exp; dq reads q,
    k, v, dO, lse and delta and writes dq; dkv reads the same and writes dk
    and dv (q, k, v, o, dO, dq, dk, dv in the compute dtype, lse and delta
    in float32)."""
    H = cfg["num_heads"]
    hq = _attn_width(cfg) * seq_len * compute_bytes * rows
    hkv = cfg["num_kv_heads"] * cfg["head_dim"] * seq_len * compute_bytes * rows
    col = H * seq_len * 4 * rows              # one float32 per head and row
    flops = 2.0 * rows * seq_len * seq_len * _attn_width(cfg)
    return {"fwd": (flops, float(hq + 2 * hkv + hq + col)),
            "dq": (flops, float(2 * hq + 2 * hkv + 2 * col + hq)),
            "dkv": (flops, float(2 * hq + 2 * hkv + 2 * col + 2 * hkv))}


def least_s(flops_bytes: tuple, peak: dict) -> float:
    """The least time of (FLOPs, bytes) on a chip: the larger of FLOPs over
    peak FLOP/s and bytes over peak bandwidth."""
    flops, nbytes = flops_bytes
    return max(flops / peak["flops"], nbytes / peak["hbm_bytes_per_s"])


def adamw_bytes(n_params: int, state_bytes: int = 4) -> float:
    """One fused AdamW pass: reads p, m, v, g and writes p, m, v."""
    return 7.0 * state_bytes * n_params
