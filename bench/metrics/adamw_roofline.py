"""The fused AdamW kernel's share of its roofline, in %: the state it reads
and writes each step (p, m, v, g read and p, m, v written, float32, for
every stored parameter that takes the kernel) over peak bandwidth, times the
traced steps, over the kernel's device time summed over the chips.  The
trace names the kernel ``adamw_update``.  Nothing when it is absent."""
import sys

from bench import counts

KERNEL = "adamw_update"


def read(ctx):
    k = ctx.trace.kernel_calls((KERNEL,))
    if k is None or not ctx.fused_adamw_params:
        print(f"adamw_roofline: {KERNEL} did not run in the trace",
              file=sys.stderr)
        return None
    least = counts.adamw_bytes(ctx.fused_adamw_params) / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least * ctx.steps / k[KERNEL][1]
