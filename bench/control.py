#!/usr/bin/env python3
"""The readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/control.py --workload <cell> --seeds 1-12 --control-seeds 3

One process on the cell's chips.  For every seed it runs the program's
first steps exactly as a benchmark run does (the same driver, compiled step
and feed) and the float32 reference, and prints the three numbers of
``bench/check.py``: the lower readings.  For the first ``--control-seeds``
seeds it also reads, against the same reference:

- ``control``: the reference in the program's place, every matrix product
  in float8 e4m3 (the precision below the bfloat16 that the configuration
  states);
- ``half_rows``: the reference in the program's place with half of each
  batch's rows left out and the mean taken over the rest;
- ``state_unchanged`` needs no run: the program's change would be 0, which
  reads 1 on ``change_gap``.

The benchmark's own runs never run this.  The last line of stdout is one
JSON object with every reading, each judged by ``bench/check.py``'s verdict
against the cell's limits, and, per number, the largest program reading and
the smallest reading of each planted fault.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, spec                    # noqa: E402
from bench import run as bench_run               # noqa: E402

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
FAULTS = ("control", "half_rows")


def program_readings(cell, seeds, devices) -> dict:
    drv = bench_run.make_driver(cell, devices)
    compiled, progs = None, {}
    for seed in seeds:
        compiled, state, batch, feed, prog = bench_run.first_steps(
            drv, seed, compiled)
        progs[seed] = prog
        del state, batch, feed
        gc.collect()
    del compiled, drv
    gc.collect()
    return progs


def seeds_of(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi) + 1)) if hi else [int(lo)]
    return out


def readings(cell, seeds, control_seeds, devices, *,
             log=bench_run.log) -> dict:
    """{seed: {"program"|fault: {number: value, "correct": verdict}}}, the
    verdict that of ``check.verdict`` under the cell's limits."""
    progs = program_readings(cell, seeds, devices)
    out = {}
    ref32 = bench_run.reference(cell, devices, "FP32")
    ref8 = bench_run.reference(cell, devices, "FP8")
    for k, seed in enumerate(seeds):
        ref = bench_run.reference_readings(ref32, cell, seed)
        got = {"program": progs[seed]}
        if k < control_seeds:
            got["control"] = bench_run.reference_readings(ref8, cell, seed)
            got["half_rows"] = bench_run.reference_readings(
                ref32, cell, seed, drop_half=True)
        out[seed] = {}
        for what, r in got.items():
            g = check.gaps(r, ref)
            ok, _ = check.verdict(g, cell.limits)
            out[seed][what] = dict({n: g[n][0] for n in NUMBERS}, correct=ok)
            log(f"seed {seed} {what}: " + ", ".join(
                f"{n} {g[n][0]:.4g} ({g[n][1]})" for n in NUMBERS)
                + f", correct {ok}")
    return out


def summary(out: dict) -> dict:
    res = {}
    for n in NUMBERS:
        res[n] = {"program_max": max(o["program"][n] for o in out.values())}
        for what in FAULTS:
            vals = [o[what][n] for o in out.values() if what in o]
            if vals:
                res[n][f"{what}_min"] = min(vals)
    res["change_gap"]["state_unchanged"] = 1.0
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 1-12 or 5,9,20")
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import jax
    if jax.default_backend() != "tpu" or len(jax.devices()) < cell.chips:
        bench_run.log(f"control: cell {cell.name} needs {cell.chips} TPU "
                      f"chip(s)")
        return bench_run.NO_CHIP
    bench_run.enable_cache()
    out = readings(cell, seeds_of(args.seeds), args.control_seeds,
                   jax.devices()[:cell.chips])
    print(json.dumps({"workload": cell.name, "seeds": out,
                      "summary": summary(out)}), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
