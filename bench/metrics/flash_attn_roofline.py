"""Flash attention's share of its roofline, in %: over every call of its
kernels in the traced window, the least time the chip needs for that call's
work (``bench/counts.flash_attention``: the larger of FLOPs over peak FLOP/s
and bytes over peak bandwidth) over the calls' device time, summed over the
chips.  A call is one layer and one micro-batch.  The trace names the
forward kernel ``flash_attention_fwd`` (run again where the layer is
recomputed) and both backward kernels, dq and dkv, ``flash_attention_bwd``;
the two backward kernels run in pairs.  Nothing when a kernel is absent."""
import sys

from bench import counts

FWD, BWD = "flash_attention_fwd", "flash_attention_bwd"


def read(ctx):
    k = ctx.trace.kernel_calls((FWD, BWD))
    if k is None or k[BWD][0] % 2:
        print(f"flash_attn_roofline: {FWD} and pairs of {BWD} did not run "
              f"in the trace ({k})", file=sys.stderr)
        return None
    t = ctx.cell.traffic
    rows = t["global_batch"] // t["n_microbatches"] // t["mesh"].get("data", 1)
    c = counts.flash_attention(ctx.cell.config, t["seq_len"], rows)
    least = (k[FWD][0] * counts.least_s(c["fwd"], ctx.peak)
             + k[BWD][0] // 2 * (counts.least_s(c["dq"], ctx.peak)
                                 + counts.least_s(c["dkv"], ctx.peak)))
    return 100.0 * least / (k[FWD][1] + k[BWD][1])
