"""The optimizer's device time per step, in ms: the traced window's ops
under the program's ``optimizer`` scope (the gradient norm, clipping and
the AdamW update), averaged over the chips, over the traced steps.
Nothing where the program names no phase."""
from bench import phases


def read(ctx):
    return phases.ms_per_step(ctx, "optimizer", "optimizer_ms_per_step")
