#!/usr/bin/env python3
"""Benchmark of the program's train step on the chip, one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell is an entry of ``BENCHMARK.json``;
its configuration, traffic, limits and per-layer metric readers are files
under ``bench/`` found by name (``bench/spec.py``).

A run:

1. set-up: makes the weights from the seed on the device, in the program's
   storage layout (one jitted call), compiles the train step (JAX's
   persistent compilation cache in the checkout, as ``repro.launch.cache``
   places it), and drives the step through its first ``check_steps`` steps
   on the seed's batches, reading the loss of each, the first gradient as
   the optimizer got it, and the change of every leaf;
2. with ``--trace 0``, the window: steps back to back, at most ``in_flight``
   dispatched ahead, each batch made and placed while the device runs the
   steps before it, until the clock passes ``--seconds``; the rate is taken
   over whole steps and the real time from the first dispatch to the last
   completion.  With ``--trace 1``, ``trace_steps`` steps under the profiler
   instead, reduced to the per-layer metrics by ``bench/metrics/<name>.py``;
3. frees the program's state and runs the plain reference
   (``bench/reference/<reference>.py``) over the same first steps from the
   same weights, and compares (``bench/check.py``).

The last line of stdout is one JSON object: ``correct``, ``attempted`` and
``failed`` (window steps, and those whose loss was not finite), ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with its limit, also printed as the last lines of stderr.
Without a TPU, or with fewer chips than the cell asks for, the run exits 3
and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse                    # noqa: E402
import collections                 # noqa: E402
import contextlib                  # noqa: E402
import gc                          # noqa: E402
import importlib                   # noqa: E402
import json                        # noqa: E402
import math                        # noqa: E402
import os                          # noqa: E402
import pathlib                     # noqa: E402
import shutil                      # noqa: E402
import sys                         # noqa: E402
import tempfile                    # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spec             # noqa: E402

NO_CHIP = 3


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts JAX's compile events while armed."""

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if self.armed and event.startswith("/jax/core/compile/"):
            self.count += 1

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def enable_cache() -> str:
    import jax
    from bench.drivers import common  # noqa: F401  (the program's path)
    from repro.launch.cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def steps(compiled, drv, state, batch, next_batch, n_steps=None,
          seconds=None, in_flight=2, traced=False):
    """Drive the step back to back; returns (state, batch, steps run, window
    seconds, losses).  Stops after ``n_steps`` or once the clock passes
    ``seconds`` at a completion; never more than ``in_flight`` pending."""
    import jax
    from bench import tracing
    span = tracing.span if traced else (lambda _: contextlib.nullcontext())
    storage, opt = state
    pending, losses, n = collections.deque(), [], 0
    t_first = time.perf_counter()
    while True:
        with span("dispatch"):
            storage, opt, m = compiled(storage, opt, batch)
        pending.append(m["loss"])
        n += 1
        with span("make_batch"):
            batch = next_batch()
        if len(pending) >= in_flight:
            with span("await_step"):
                losses.append(float(pending.popleft()))
        if n_steps is not None and n >= n_steps:
            break
        if seconds is not None and time.perf_counter() - t_first >= seconds:
            break
    with span("await_step"):
        jax.block_until_ready((storage, opt))
        losses += [float(x) for x in pending]
    return (storage, opt), batch, n, time.perf_counter() - t_first, losses


class Feed:
    """The seed's batches, made on the host and placed on the mesh, one
    step ahead of the step that needs them.  Step ``i`` (from 1) takes
    ``traffic.make_batch(..., seed, i)``."""

    def __init__(self, drv, seed: int):
        from bench import traffic
        self.drv, self.i = drv, 0
        self.make = lambda i: traffic.make_batch(
            drv.cell.traffic, drv.cell.config["vocab_size"], seed, i)

    def __call__(self):
        self.i += 1
        return self.drv.place(self.make(self.i))


def first_steps(drv, seed: int, compiled=None):
    """Set-up of one seed: the program's state from the seed's weights, the
    compiled step (unless given), and the first ``check_steps`` steps
    through the window's own call and feed, with the program's readings.
    Returns (compiled, state, batch, feed, readings)."""
    from bench import weights
    key = weights.key_of(seed, 0)
    feed = Feed(drv, seed)
    state = drv.init_state(key)
    batch = feed()
    if compiled is None:
        compiled = drv.compile(*state, batch)
    prog = {"losses": []}
    for i in range(drv.cell.traffic["check_steps"]):
        state, batch, _, _, ls = steps(compiled, drv, state, batch, feed,
                                       n_steps=1, in_flight=1)
        prog["losses"] += ls
        if i == 0:
            prog["grad"] = drv.grad_norms(state[1])
    prog["change"] = drv.change_norms(state[0], key)
    log(f"set-up steps: losses {prog['losses']}")
    return compiled, state, batch, feed, prog


def reference(cell: spec.Cell, devices, numerics: str = "FP32"):
    import jax
    import numpy as np
    mod = importlib.import_module(f"bench.reference.{cell.config['reference']}")
    mesh = jax.sharding.Mesh(np.array(devices), ("r",))
    return mod.Reference(cell.config, mesh, getattr(mod, numerics))


def reference_readings(ref, cell: spec.Cell, seed: int, *,
                       drop_half: bool = False) -> dict:
    from bench import traffic, weights
    t = cell.traffic
    batches = [traffic.make_batch(t, cell.config["vocab_size"], seed, i)
               for i in range(1, t["check_steps"] + 1)]
    t0 = time.perf_counter()
    losses, grad, change = ref.run(weights.key_of(seed, 0), batches,
                                   rows=t["reference_rows"],
                                   drop_half=drop_half)
    log(f"reference ({ref.num.name}{', half the rows' if drop_half else ''})"
        f": losses {losses}, {time.perf_counter() - t0:.1f} s")
    return {"losses": losses, "grad": grad, "change": change}


def make_driver(cell: spec.Cell, devices):
    drivers = importlib.import_module(f"bench.drivers.{cell.traffic['driver']}")
    return drivers.Driver(cell, devices)


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        devices=None) -> dict:
    import jax
    from bench import check, peaks, tracing

    t = cell.traffic
    devices = list(devices or jax.devices()[:cell.chips])
    cache = enable_cache() if devices[0].platform != "cpu" else None
    counter = CompileCounter()
    drv = make_driver(cell, devices)
    log(f"cell {cell.name}: seed {seed}, {len(devices)} x "
        f"{devices[0].device_kind}, compile cache {cache}, {drv.info()}")
    compiled, state, batch, feed, prog = first_steps(drv, seed)
    ma = compiled.memory_analysis()
    step_bytes = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                  - ma.alias_size_in_bytes + ma.temp_size_in_bytes)

    counter.armed = True
    t_setup = time.perf_counter() - T0
    result = {"correct": False, "attempted": 0, "failed": 0}
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            with tracing.capture(tdir):
                with tracing.span("window"):
                    state, batch, n, window, losses = steps(
                        compiled, drv, state, batch, feed,
                        n_steps=t["trace_steps"], in_flight=t["in_flight"],
                        traced=True)
            tr = tracing.Trace(tracing.load(tdir))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    else:
        state, batch, n, window, losses = steps(
            compiled, drv, state, batch, feed, seconds=seconds,
            in_flight=t["in_flight"])
    counter.armed = False
    counter.close()
    result["attempted"] = n
    result["failed"] = sum(1 for x in losses if not math.isfinite(x))
    tok_s = n * cell.tokens_per_step / window
    log(f"window: {n} steps in {window:.3f} s, {tok_s:.1f} tokens/s, "
        f"{counter.count} compile events")
    device = device_info(devices)

    if trace:
        ctx = MetricContext(cell=cell, trace=tr, tokens_per_s=tok_s,
                            steps=n, chips=len(devices),
                            peak=peaks.peak(devices[0].device_kind),
                            fused_adamw_params=drv.fused_adamw_params(state[0]))
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_module(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(),
                               "idle_gaps": tr.idle_gaps()}
    else:
        values = {"tokens_per_s_per_chip": tok_s / len(devices),
                  "step_hbm_gib": step_bytes / 2**30,
                  "setup_s": t_setup}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    # the reference, once the program's state is gone
    del state, batch, compiled, drv, feed
    gc.collect()
    ref = reference_readings(reference(cell, devices), cell, seed)
    numbers = check.gaps(prog, ref)
    numbers["window_compiles"] = (float(counter.count), "window")
    ok, checks = check.verdict(numbers, dict(cell.limits, window_compiles=0))
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r} "
            f"(worst at {numbers[name][1]})")
    result.update(correct=ok and result["failed"] == 0, metrics=metrics,
                  device=device, checks=checks)
    return result


class MetricContext:
    """What a per-layer metric reader may read: the cell, the reduced trace
    (``bench.tracing.Trace``), the traced window's tokens/s, the number of
    traced steps and chips, the chip's peak, and how many stored parameters
    take the fused AdamW kernel per step (the whole cell)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        log(f"bench: {e}")
        return 2
    import jax
    devs = jax.devices()
    if jax.default_backend() != "tpu" or len(devs) < cell.chips:
        log(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
            f"finds {len(devs)} {jax.default_backend()} device(s)")
        return NO_CHIP
    result = run(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the result is out; skip the runtime's slow teardown of the chips
    os._exit(code)
