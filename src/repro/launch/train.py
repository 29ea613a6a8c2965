"""Training driver: real steps on the available devices.

CPU-runnable at reduced scale (--smoke uses the per-arch reduced configs);
on a TPU slice the same driver runs the full configs with the production
mesh.  Supports both accumulation schedules, the ZeRO partition, streaming
checkpoints (§8.2) and deterministic synthetic data.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke --steps 20
  PYTHONPATH=src python -m repro.launch.train --arch dbrx-132b --smoke \\
      --method standard --no-partition --steps 10

Planner integration (the analysis -> execution loop):
  PYTHONPATH=src python -m repro.launch.plan --arch yi-6b --smoke \\
      --devices 4 --out plan.json
  PYTHONPATH=src python -m repro.launch.train --plan plan.json
The plan's execution section supplies arch/mesh/method/partition/
microbatches/global-batch/seq-len (and steps, unless --steps is passed
explicitly); explicit CLI flags still win over the plan.

On an accelerator the run keeps its compiled programs in JAX's persistent
compilation cache (``repro.launch.cache``: ``$JAX_COMPILATION_CACHE_DIR``,
else ``<checkout>/.jax_cache``).  CPU runs leave the cache as JAX's own
configuration has it.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.checkpointing import store
from repro.core import stepfn
from repro.core.accumulation import AccumConfig
from repro.core.schedules import PipeSpec
from repro.data.synthetic import DataConfig, batch_for
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_train_mesh
from repro.obs import drift as obs_drift
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.optim.adam import AdamConfig, adam_init


def apply_plan(args, argv) -> None:
    """Fill args from a plan's execution section (launch.plan output).

    Plan values override argparse *defaults*; flags the user passed
    explicitly (present in argv) keep their CLI value.
    """
    from repro.planner.plan import execution_of, load_plan

    ex = execution_of(load_plan(args.plan))
    passed = {a.split("=")[0] for a in (argv or []) if a.startswith("--")}

    def take(flag: str, attr: str, key: str):
        if key in ex and flag not in passed:
            setattr(args, attr, ex[key])

    take("--arch", "arch", "arch")
    take("--smoke", "smoke", "smoke")
    take("--mesh", "mesh", "mesh")
    take("--method", "method", "method")
    take("--microbatches", "microbatches", "microbatches")
    take("--global-batch", "global_batch", "global_batch")
    take("--seq-len", "seq_len", "seq_len")
    take("--steps", "steps", "steps")
    take("--stages", "stages", "stages")
    take("--schedule", "schedule", "schedule")
    take("--split-backward", "split_backward", "split_backward")
    if "partitioned" in ex and "--no-partition" not in passed:
        args.no_partition = not ex["partitioned"]
    # schedule-as-data: a pipelined plan embeds the tick table it scored;
    # carry it along so the executor interprets exactly that table
    args.plan_tick_table = ex.get("tick_table")


def _run_supervised(args, cfg, opt_cfg, d, m, partitioned) -> dict:
    """Run under the resilience supervisor (--faults / --resume auto).

    ``--steps`` is the *total* completed-step target here, not
    steps-after-resume: a killed-and-resumed run finishes at the same step
    as an unkilled one, which is what the trajectory-parity check needs.
    """
    from repro.resilience import faults as flt
    from repro.resilience.reshard import MeshLayout
    from repro.resilience.supervisor import Supervisor, SupervisorConfig

    layout = MeshLayout(stages=args.stages, data=d, model=m,
                        partitioned=partitioned, schedule=args.schedule,
                        n_microbatches=args.microbatches)
    plan_ex = None
    if args.plan:
        from repro.planner.plan import execution_of, load_plan
        plan_ex = execution_of(load_plan(args.plan))
    fault_plan = flt.FaultPlan.load(args.faults) if args.faults else None
    sup = SupervisorConfig(
        checkpoint_every=args.checkpoint_every or 1,
        keep_checkpoints=args.keep_checkpoints, seed=args.seed)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                      global_batch=args.global_batch,
                      n_microbatches=args.microbatches, seed=args.seed)
    sink = obs_metrics.MetricsSink(
        args.metrics,
        meta={"arch": args.arch, "smoke": args.smoke, "mesh": args.mesh,
              "stages": args.stages, "supervised": True,
              "global_batch": args.global_batch, "seq_len": args.seq_len,
              "partitioned": partitioned,
              "faults": fault_plan.to_json()["faults"] if fault_plan else []})
    tracer = obs_trace.Tracer() if args.trace else None
    sv = Supervisor(cfg, opt_cfg, data, layout, ckpt_root=args.checkpoint_dir,
                    method=args.method, sup=sup, fault_plan=fault_plan,
                    sink=sink, tracer=tracer, plan_execution=plan_ex)
    result: dict = {}
    try:
        result = sv.run(args.steps)
        result["arch"] = args.arch
        print(json.dumps(result))
        return result
    finally:
        if tracer is not None and args.trace:
            tracer.save(args.trace)
        sink.close(extra={k: v for k, v in result.items()
                          if not isinstance(v, (list, dict))} or None)


def main(argv=None) -> dict:
    # allow_abbrev=False: apply_plan detects explicitly-passed flags by their
    # full spelling, so abbreviations must not be silently accepted
    ap = argparse.ArgumentParser(allow_abbrev=False)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--plan", default=None,
                    help="JSON plan from `python -m repro.launch.plan`; "
                         "its execution section fills unset flags")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--method", default="layered",
                    choices=["layered", "standard"])
    ap.add_argument("--no-partition", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="1x1",
                    help="data x model, e.g. 2x2 (needs that many devices)")
    ap.add_argument("--stages", type=int, default=1,
                    help="pipeline stages; > 1 trains on a stage x data x "
                         "model mesh through the generic tick-table executor")
    ap.add_argument("--schedule", default="modular",
                    help="pipeline schedule (used when --stages > 1): "
                         "modular | naive/gpipe | 1f1b | interleaved "
                         "(validated against the executable set, so a plan "
                         "naming an unsupported schedule fails fast)")
    ap.add_argument("--split-backward", action="store_true",
                    help="zero-bubble schedule variant (used when --stages "
                         "> 1): each backward tick splits into a dgrad tick "
                         "(releases the upstream cotangent) and a deferred "
                         "wgrad tick scheduled into bubble slots; grads and "
                         "losses match the unsplit schedule exactly")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--resume", nargs="?", const="latest", default=None,
                    choices=["latest", "auto"],
                    help="latest: restore the newest valid checkpoint once "
                         "and continue; auto: run under the resilience "
                         "supervisor, which auto-resumes after crashes "
                         "(bounded retries, checksum fallback)")
    ap.add_argument("--faults", default=None,
                    help="JSON fault plan (repro.resilience.faults) to "
                         "inject deterministically; implies the supervised "
                         "loop and requires --checkpoint-dir")
    ap.add_argument("--keep-checkpoints", type=int, default=3,
                    help="garbage-collect all but the newest N valid "
                         "checkpoints after each save")
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--metrics", default=None,
                    help="stream per-step metrics (loss, step time, tokens/s,"
                         " MFU) to this JSONL file; flushed per record so a "
                         "crashed run keeps everything up to the failed step")
    ap.add_argument("--trace", default=None,
                    help="write a Chrome-trace (Perfetto-loadable) JSON of "
                         "the run phases here; with --stages > 1 it also "
                         "holds the measured per-tick stage timeline from a "
                         "segmented profiling pass, next to the plan's "
                         "predicted timeline")
    ap.add_argument("--drift-report", default=None,
                    help="with --stages > 1: run a segmented profiling pass "
                         "and write the measured-vs-predicted tick timeline "
                         "drift report (obs/drift.py) to this JSON file")
    args = ap.parse_args(argv)
    args.plan_tick_table = None
    if args.plan:
        apply_plan(args, argv if argv is not None else sys.argv[1:])
    if not args.arch:
        ap.error("--arch required (directly or via --plan)")
    if jax.default_backend() != "cpu":
        enable_compile_cache()

    cfg = configs.get_config(args.arch, smoke=args.smoke)
    d, m = (int(v) for v in args.mesh.split("x"))
    if m > 1:
        cfg = cfg.padded_for_tp(m)
    partitioned = not args.no_partition
    opt_cfg = AdamConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                         decay_steps=args.steps)

    if args.faults or args.resume == "auto":
        # fault injection / auto-resume: hand the run to the supervisor,
        # which owns the (mesh, step, state) triple and survives crashes
        if not args.checkpoint_dir:
            ap.error("--faults / --resume auto require --checkpoint-dir")
        return _run_supervised(args, cfg, opt_cfg, d, m, partitioned)

    mesh = make_train_mesh(stages=args.stages, data=d, model=m)

    n_devices = args.stages * d * m
    device_kind = jax.devices()[0].device_kind
    tokens_per_step = args.global_batch * args.seq_len
    sink = obs_metrics.MetricsSink(
        args.metrics,
        meta={"arch": args.arch, "smoke": args.smoke, "mesh": args.mesh,
              "stages": args.stages,
              "schedule": args.schedule if args.stages > 1 else None,
              "global_batch": args.global_batch, "seq_len": args.seq_len,
              "n_devices": n_devices, "device_kind": device_kind,
              "partitioned": partitioned})
    tracer = obs_trace.Tracer() if args.trace else None

    def span(name, **kw):
        return (tracer.span(name, **kw) if tracer
                else contextlib.nullcontext())

    exec_table = None
    if args.stages > 1:
        from repro.planner import simulator as simlib

        # pipelined path: schedule-as-data.  Fail fast, legibly, on any
        # schedule the generic executor cannot interpret — whether it came
        # from --schedule or from a plan's execution section.
        from repro.core.schedules import KNOWN_SCHEDULES
        if args.schedule not in KNOWN_SCHEDULES:
            ap.error(
                f"--schedule {args.schedule!r} is not executable; the tick-"
                f"table executor runs: "
                f"{', '.join(simlib.EXECUTABLE_SCHEDULES)} "
                f"(aliases: naive = gpipe)")
        if cfg.num_layers % args.stages:
            ap.error(f"--stages {args.stages} does not divide "
                     f"num_layers={cfg.num_layers}")
        try:
            spec = PipeSpec(n_stages=args.stages,
                            layers_per_stage=cfg.num_layers // args.stages,
                            n_microbatches=args.microbatches,
                            schedule=args.schedule,
                            split_backward=args.split_backward)
        except AssertionError as e:
            ap.error(f"infeasible pipeline shape for schedule "
                     f"{args.schedule!r}: {e}")
        table = None
        if args.plan_tick_table is not None:
            table = simlib.TickTable.from_json(args.plan_tick_table)
            if (table.schedule, table.n_stages, table.n_microbatches) != \
                    (spec.schedule, spec.n_stages, spec.n_microbatches):
                ap.error(
                    f"plan tick table ({table.schedule}, S={table.n_stages}, "
                    f"M={table.n_microbatches}) does not match the resolved "
                    f"execution (schedule={spec.schedule}, S={spec.n_stages}, "
                    f"M={spec.n_microbatches})")
            if table.is_split != spec.split_backward:
                # older plans embed a split table without the execution-level
                # flag (or vice versa): the table is the contract, follow it
                import dataclasses
                spec = dataclasses.replace(spec,
                                           split_backward=table.is_split)
        exec_table = table if table is not None else spec.tick_table()
        try:
            # fail fast, legibly, before tracing: a stale plan JSON with
            # tick kinds this executor cannot interpret (or a malformed
            # dgrad/wgrad pairing) names the offending kinds and the
            # planner flag that produces them
            exec_table.validate_executable()
        except (NotImplementedError, ValueError) as e:
            ap.error(f"plan tick table is not executable: {e}")
        with span("build_step"):
            # params and optimizer state are rebound every step, so the
            # step donates them: one copy of the state lives on the device
            step = stepfn.build_pipeline_train_step(
                cfg, mesh, spec, opt_cfg, partitioned=partitioned,
                donate=True, table=exec_table)
        with span("init_storage"):
            storage = stepfn.init_pipeline_storage(
                cfg, mesh, jax.random.PRNGKey(args.seed), spec,
                partitioned=partitioned)
    else:
        acc = AccumConfig(method=args.method, partitioned=partitioned,
                          n_microbatches=args.microbatches)
        with span("build_step"):
            step = stepfn.build_train_step(cfg, mesh, acc, opt_cfg,
                                           donate=True)
        with span("init_storage"):
            storage = stepfn.init_storage(cfg, mesh,
                                          jax.random.PRNGKey(args.seed),
                                          partitioned=partitioned)
    opt = adam_init(storage, moment_dtype=opt_cfg.moment_dtype)

    layout_meta = {"stages": args.stages, "data": d, "model": m,
                   "partitioned": partitioned, "schedule": args.schedule,
                   "n_microbatches": args.microbatches}
    start = 0
    if args.resume and args.checkpoint_dir:
        like = {"params": storage, "mu": opt["mu"], "nu": opt["nu"],
                "opt_step": opt["step"]}
        try:
            bundle, start, _ = store.load_latest(args.checkpoint_dir, like)
            storage = jax.tree.map(jnp.asarray, bundle["params"])
            opt = {"mu": jax.tree.map(jnp.asarray, bundle["mu"]),
                   "nu": jax.tree.map(jnp.asarray, bundle["nu"]),
                   "step": jnp.asarray(bundle["opt_step"], jnp.int32)}
        except store.CheckpointError:
            # legacy flat checkpoint: params only, at the dir root (resumed
            # moments are unavailable — Adam restarts its estimates)
            storage, start = store.load_state(args.checkpoint_dir, storage)
        print(f"resumed from step {start}")

    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                      global_batch=args.global_batch,
                      n_microbatches=args.microbatches, seed=args.seed)
    history = []
    result: dict = {}
    t_start = time.time()
    # Everything below streams through the sink and is flushed per record;
    # the finally block writes the summary line and saves the trace even
    # when a step raises or the run is interrupted — a crashed run keeps
    # its telemetry up to the failed step (it used to lose all output).
    try:
        for i in range(start, start + args.steps):
            batch = batch_for(cfg, data, i)
            t0 = time.perf_counter()
            with span("train step", cat="step", step=i):
                storage, opt, metrics = step(storage, opt, batch)
                loss = float(metrics["loss"])     # device sync: ends the step
            dt = time.perf_counter() - t0
            tok_s = tokens_per_step / dt
            rec = {"step": i, "loss": loss, "lr": float(metrics["lr"]),
                   "grad_norm": float(metrics["grad_norm"]),
                   "step_time_s": dt, "tokens_per_s": tok_s,
                   "mfu": obs_metrics.mfu_estimate(
                       cfg, global_batch=args.global_batch,
                       seq_len=args.seq_len, step_time_s=dt,
                       n_devices=n_devices, device_kind=device_kind)}
            sink.log(rec)
            history.append(loss)
            if i % args.log_every == 0:
                print(f"step {i:5d}  loss {loss:8.4f}"
                      f"  lr {float(metrics['lr']):.2e}"
                      f"  gnorm {float(metrics['grad_norm']):7.3f}"
                      f"  {tok_s:9.0f} tok/s"
                      f"  {time.time()-t_start:6.1f}s", flush=True)
            if (args.checkpoint_every and args.checkpoint_dir
                    and (i + 1) % args.checkpoint_every == 0):
                bundle = {"params": storage, "mu": opt["mu"],
                          "nu": opt["nu"], "opt_step": opt["step"]}
                store.save_checkpoint(
                    args.checkpoint_dir, bundle, step=i + 1,
                    meta={"arch": args.arch, "loss": loss,
                          "layout": layout_meta,
                          "moment_dtype": opt_cfg.moment_dtype},
                    keep=args.keep_checkpoints)

        # ---- segmented profiling pass: measured tick timeline + drift ----
        if exec_table is not None and (args.trace or args.drift_report):
            with span("tick profiling"):
                prof = stepfn.build_pipeline_tick_profiler(
                    cfg, mesh, spec, partitioned=partitioned,
                    table=exec_table)
                events = obs_trace.measure_tick_timeline(
                    prof, storage, batch_for(cfg, data, 0), warmup=1,
                    tracer=tracer, pid=1)
            predicted = exec_table.timeline()
            if tracer is not None and events:
                # render the plan's unit-tick timeline at the measured
                # mean tick length, so the lanes align side by side
                mk = max(e[5] for e in events)
                tracer.name_process(2, "planned ticks")
                obs_trace.add_timeline(
                    tracer, predicted, pid=2, name="planned ticks",
                    scale_us=mk * 1e6 / max(exec_table.n_ticks, 1))
            if args.drift_report:
                rep = obs_drift.drift_report(events, predicted)
                obs_drift.save_report(rep, args.drift_report)
                print(obs_drift.format_report(rep))
                sink.log(event="drift",
                         record={"max_abs_drift": rep["max_abs_drift"],
                                 "matched": rep["overall"]["matched"],
                                 "missing": rep["overall"]["missing"],
                                 "extra": rep["overall"]["extra"]})
                result["max_abs_drift"] = rep["max_abs_drift"]

        result.update({"arch": args.arch, "first_loss": history[0],
                       "last_loss": history[-1], "losses": history,
                       "steps": len(history),
                       "seconds": round(time.time() - t_start, 1)})
        print(json.dumps(result))
        return result
    finally:
        if tracer is not None and args.trace:
            tracer.save(args.trace)
        sink.close(extra=result or None)


if __name__ == "__main__":
    main()
