"""The backward's device time per step, in ms: the traced window's ops that
``bench/phases.py`` puts in ``backward`` (the transposes under the
program's ``fwd`` and ``bwd`` scopes, and the rest of ``bwd`` but its
recomputed forward), averaged over the chips, over the traced steps.
Nothing where the program names no phase."""
from bench import phases


def read(ctx):
    return phases.ms_per_step(ctx, "backward", "bwd_ms_per_step")
