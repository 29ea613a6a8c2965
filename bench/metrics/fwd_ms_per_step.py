"""The forward's device time per step, in ms: the traced window's ops that
``bench/phases.py`` puts in ``forward`` (under the program's ``fwd`` scope,
neither transposed nor recomputed), summed over the traced steps' ops,
averaged over the chips, over the steps.  Nothing where the program names
no phase."""
from bench import phases


def read(ctx):
    return phases.ms_per_step(ctx, "forward", "fwd_ms_per_step")
