"""Tiny cells of the paper's X family, for runs on the CPU."""
from bench import spec

TINY = {"num_layers": 2, "d_model": 256, "num_heads": 4, "num_kv_heads": 4,
        "head_dim": 64, "d_ff": 1024, "vocab_size": 1024}

# the one-chip cell of BENCHMARK.json
LAYERED = ("x32-s512-layered-1chip", "paper-x32", "s512-b64-layered")

# Limits at this size, set from bench/control.py's readings on the CPU over
# the traffic's two checked steps (seeds 11, 21-24; control and half the rows
# on the first three): the program at most 4.5e-5 / 1.9e-3 / 3.8e-4, the
# float8 control at least 2.5e-5 / 5.5e-3 / 1.5e-3, half the rows at least
# 1.2e-3 / 4.3e-2 / 0.18.  The control fails grad_gap and change_gap here.
# The cells' own limits (bench/limits/) are set from readings on the chip.
LIMITS = {"loss_gap": 7e-5, "grad_gap": 4e-3, "change_gap": 6e-4}


def tiny_cell(name: str, config: str, traffic: str) -> spec.Cell:
    """A cell from its own configuration and traffic files, at a
    size the CPU runs in seconds: the X family cut to TINY (head size 64, as
    X_32's), 64 tokens a row, 8 rows a step."""
    cfg = spec.load_config(spec.ROOT, {"name": config,
                                       "file": f"bench/configs/{config}.json"})
    t = spec.load_traffic(spec.ROOT, traffic)
    bench = spec.load_benchmark()
    n_dev = 1
    for v in t["mesh"].values():
        n_dev *= v
    return spec.Cell(
        name=name, chips=n_dev, config=dict(cfg, **TINY),
        traffic=dict(t, seq_len=64, global_batch=8, n_microbatches=4,
                     reference_rows=4, trace_steps=2),
        limits=dict(LIMITS),
        end_to_end=tuple(bench["end_to_end"]), per_layer=())
