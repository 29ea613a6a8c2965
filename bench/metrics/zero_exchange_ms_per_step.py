"""The ZeRO exchange's device time per step, in ms: the traced window's ops
under the program's ``zero_gather`` and ``zero_reduce`` scopes (the
per-layer weight gather and gradient reduce-scatter; local casts, copies
and slices on one chip), averaged over the chips, over the traced steps.
Nothing where the program names no phase."""
from bench import phases


def read(ctx):
    return phases.ms_per_step(ctx, "zero_exchange",
                              "zero_exchange_ms_per_step")
