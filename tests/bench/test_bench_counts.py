"""The benchmark's FLOP and byte counts against counts made by hand."""
import json

import pytest

from bench import counts
from bench.spec import ROOT


# the paper's X_64 (appendix B, eq. 1) cut to 8 layers, as a configuration
# file of a later cell would give it
X64_8L = {"num_layers": 8, "d_model": 4096, "num_heads": 32, "num_kv_heads": 32,
          "head_dim": 128, "d_ff": 16384, "vocab_size": 32000, "glu": False}


def config(name):
    if name == "x64-8l":
        return dict(X64_8L)
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


# per token: 6 x (L x (4 D d_attn + 2 D F) + V D) + 6 S d_attn L
@pytest.mark.parametrize("name,seq,total,attn", [
    ("paper-x32", 512, 6 * (32 * (4 * 1024 * 1024 + 2 * 1024 * 4096)
                            + 32000 * 1024) + 6 * 512 * 1024 * 32,
     6 * 512 * 1024 * 32),
    ("paper-x32", 4096, 6 * (32 * (4 * 1024 * 1024 + 2 * 1024 * 4096)
                             + 32000 * 1024) + 6 * 4096 * 1024 * 32,
     6 * 4096 * 1024 * 32),
    ("x64-8l", 1024, 6 * (8 * (4 * 4096 * 4096 + 2 * 4096 * 16384)
                          + 32000 * 4096) + 6 * 1024 * 4096 * 8,
     6 * 1024 * 4096 * 8),
])
def test_flops_per_token(name, seq, total, attn):
    cfg = config(name)
    assert counts.train_flops_per_token(cfg, seq) == total
    assert counts.attention_flops_per_token(cfg, seq) == attn


def test_x32_at_512_matches_the_issue_figures():
    cfg = config("paper-x32")
    assert counts.train_flops_per_token(cfg, 512) == pytest.approx(2.713e9, rel=1e-3)
    assert counts.attention_flops_per_token(cfg, 512) == pytest.approx(1.007e8, rel=1e-3)


def test_x64_8l_matches_the_issue_figures():
    cfg = config("x64-8l")
    assert counts.train_flops_per_token(cfg, 1024) == pytest.approx(10.65e9, rel=1e-3)


@pytest.mark.parametrize("name,n", [("paper-x32", 468_322_304),
                                    ("x64-8l", 1_872_896_000)])
def test_param_count(name, n):
    cfg = config(name)
    assert counts.param_count(cfg) == n
    norms = 4 * cfg["d_model"] * cfg["num_layers"] + 2 * cfg["d_model"]
    if name == "paper-x32":
        from bench.drivers.common import model_config
        # the program counts the same weights, less the norms
        assert counts.param_count(cfg) - norms == model_config(cfg).param_count()


def test_flash_attention_counts_by_hand():
    cfg = config("paper-x32")
    S, B, L, H, hd = 512, 8, 32, 16, 64
    t = H * hd * S * 2 * B                # one bf16 [B, S, H*hd] tensor
    col = H * S * 4 * B                   # one float32 per head and row
    c = counts.flash_attention(cfg, S, B)
    assert c["fwd"] == (2 * B * H * S * S * hd, 3 * t + t + col)
    assert c["dq"] == (2 * B * H * S * S * hd, 4 * t + 2 * col + t)
    assert c["dkv"] == (2 * B * H * S * S * hd, 4 * t + 2 * col + 2 * t)
    # the three kernels of every layer make the step's attention FLOPs
    assert L * sum(f for f, _ in c.values()) == \
        counts.attention_flops_per_token(cfg, S) * S * B


def test_least_time_is_the_larger_bound():
    peak = {"flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_s((300.0, 20.0), peak) == 3.0
    assert counts.least_s((100.0, 50.0), peak) == 5.0


def test_adamw_bytes():
    assert counts.adamw_bytes(10) == 280.0
