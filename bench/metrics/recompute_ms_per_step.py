"""The recomputed forward's device time per step, in ms: the traced
window's ops that ``bench/phases.py`` puts in ``recompute`` (``jax.vjp``'s
forward under the program's ``bwd`` scope, or ``jax.checkpoint``'s
recomputation), averaged over the chips, over the traced steps.  Nothing
where the program names no phase."""
from bench import phases


def read(ctx):
    return phases.ms_per_step(ctx, "recompute", "recompute_ms_per_step")
