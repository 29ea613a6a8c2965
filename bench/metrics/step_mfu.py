"""The whole step's share of the chips' peak, in %: the benchmark's FLOPs
per token (``bench/counts.py``: matmul weights without the embedding
gather, plus causal attention, nothing recomputed) x the traced window's
tokens/s, over chips x peak FLOP/s."""
from bench import counts


def read(ctx):
    f = counts.train_flops_per_token(ctx.cell.config,
                                     ctx.cell.traffic["seq_len"])
    return 100.0 * f * ctx.tokens_per_s / (ctx.chips * ctx.peak["flops"])
