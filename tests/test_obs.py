"""Telemetry subsystem (obs/): jit-safe registry, crash-safe sink,
Chrome-trace schema round-trip, timeline drift, serving latency, and the
end-to-end acceptance run — a pipelined plan through ``launch.train``
producing metrics JSONL + per-tick trace + drift report."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.obs import drift as obs_drift
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


# ---------------------------------------------------------------------------
# Registry: device-side, jit-safe
# ---------------------------------------------------------------------------
def test_registry_jit_safe_no_recompile():
    """The metric tree update is fixed-shape: carrying it through a jitted
    step must compile exactly once across steps (the compile-count probe)."""
    reg = obs_metrics.Registry()
    reg.counter("tokens")
    reg.gauge("loss")
    reg.histogram("step_ms", [1.0, 4.0, 16.0])
    n_traces = [0]

    @jax.jit
    def step(tree, x):
        n_traces[0] += 1
        return reg.update(tree, tokens=8, loss=x, step_ms=x)

    tree = reg.init()
    for i in range(5):
        tree = step(tree, jnp.float32(i))
    assert n_traces[0] == 1, "metric update retraced across steps"
    host = reg.to_host(tree)
    assert host["tokens"] == 40.0            # counter accumulates
    assert host["loss"] == 4.0               # gauge keeps the last value
    assert sum(host["step_ms"]) == 5         # every value lands in a bucket
    # histogram bucketization: values 0..4 against inclusive upper edges
    # [1, 4, 16]: {0, 1} <= 1, {2, 3, 4} <= 4
    assert host["step_ms"] == [2, 3, 0, 0]


def test_registry_merge_and_scan():
    reg = obs_metrics.Registry()
    reg.counter("n")
    reg.gauge("g")
    a = reg.update(reg.init(), n=2, g=1.0)
    b = reg.update(reg.init(), n=3, g=7.0)
    m = reg.to_host(reg.merge(a, b))
    assert m["n"] == 5.0 and m["g"] == 7.0

    def body(tree, x):
        return reg.update(tree, n=1, g=x), None

    tree, _ = lax.scan(body, reg.init(), jnp.arange(4.0))
    host = reg.to_host(tree)
    assert host["n"] == 4.0 and host["g"] == 3.0


# ---------------------------------------------------------------------------
# Sink: crash-flush, summary, torn-line tolerance
# ---------------------------------------------------------------------------
def test_sink_survives_midrun_exception(tmp_path):
    """The ISSUE 7 bugfix: a crash mid-run must leave every already-logged
    step line AND the summary on disk (per-line flush + finally-close)."""
    path = tmp_path / "metrics.jsonl"
    sink = obs_metrics.MetricsSink(str(path), meta={"arch": "t"})
    with pytest.raises(RuntimeError):
        try:
            for i in range(3):
                sink.log(step=i, loss=1.0 / (i + 1))
            raise RuntimeError("boom at step 3")
        finally:
            sink.close(extra={"aborted": True})
    recs = obs_metrics.read_jsonl(str(path))
    events = [r["event"] for r in recs]
    assert events == ["meta", "step", "step", "step", "summary"]
    summ = recs[-1]
    assert summ["aborted"] is True
    assert summ["records"] == 3
    assert summ["loss"]["last"] == pytest.approx(1.0 / 3)
    assert summ["loss"]["max"] == pytest.approx(1.0)


def test_sink_close_idempotent_and_read_skips_torn_line(tmp_path):
    path = tmp_path / "m.jsonl"
    sink = obs_metrics.MetricsSink(str(path))
    sink.log(step=0, loss=2.0)
    sink.close()
    sink.close(extra={"late": 1})            # second close: no extra line
    with open(path, "a") as f:
        f.write('{"event": "step", "trunc')  # hard-crash torn final line
    recs = obs_metrics.read_jsonl(str(path))
    assert [r["event"] for r in recs] == ["step", "summary"]


def test_percentiles_nearest_rank():
    vals = list(range(1, 101))
    p = obs_metrics.percentiles(vals)
    assert p == {"p50": 50.0, "p95": 95.0, "p99": 99.0}
    assert obs_metrics.percentiles([]) == {}
    assert obs_metrics.percentiles([7.0])["p99"] == 7.0


def test_mfu_cross_checks_roofline():
    """mfu_estimate must be exactly roofline 6ND flops over time*devices*peak
    of the device kind's row (one source of truth for the flops model and
    device peak); a device with no published peak has no MFU."""
    from repro.core import roofline
    from repro.configs.gemma_2b import SMOKE as cfg
    gb, seq, dt, nd = 8, 32, 0.25, 4
    kind = "TPU v5 lite"
    got = obs_metrics.mfu_estimate(cfg, global_batch=gb, seq_len=seq,
                                   step_time_s=dt, n_devices=nd,
                                   device_kind=kind)
    flops = roofline.model_flops_train(cfg, gb, seq)
    want = flops / (dt * nd * 197e12)
    assert got == pytest.approx(want)
    assert obs_metrics.mfu_estimate(cfg, global_batch=gb, seq_len=seq,
                                    step_time_s=0.0, device_kind=kind) == 0.0
    # the CPU this runs on (and any other unlisted kind) gives None, and the
    # roofline lookup itself raises rather than defaulting to the v5e peak
    assert obs_metrics.mfu_estimate(cfg, global_batch=gb, seq_len=seq,
                                    step_time_s=dt) is None
    assert obs_metrics.mfu_estimate(cfg, global_batch=gb, seq_len=seq,
                                    step_time_s=dt,
                                    device_kind="TPU v4") is None
    with pytest.raises(ValueError, match="no published peak"):
        roofline.mfu(flops, dt, device_kind="cpu")


# ---------------------------------------------------------------------------
# Chrome trace: schema + timeline round-trip
# ---------------------------------------------------------------------------
def _sim_timeline():
    from repro.core.schedules import PipeSpec
    from repro.planner.simulator import CostModel, simulate
    spec = PipeSpec(n_stages=2, layers_per_stage=2, n_microbatches=4,
                    schedule="1f1b")
    cost = CostModel(flops_fwd_layer=1.0, flops_bwd_layer=2.0, act_bytes=0.0,
                     layer_param_bytes=0.0, layer_grad_bytes=0.0,
                     flops_rate=1.0, p2p_bw=1.0, coll_bw=1.0)
    res = simulate(spec.sim_config(), cost, record_timeline=True)
    assert res.timeline, "simulator produced no timeline events"
    return spec, res.timeline


def test_chrome_trace_schema_roundtrip(tmp_path):
    """add_timeline -> save -> load -> validate == [] -> timeline_from_chrome
    recovers every unit with identity and times intact."""
    spec, timeline = _sim_timeline()
    tracer = obs_trace.Tracer()
    with tracer.span("outer", cat="phase"):
        tracer.instant("marker")
    obs_trace.add_timeline(tracer, timeline, pid=3, name="planned",
                           scale_us=1e6)
    path = tmp_path / "trace.json"
    tracer.save(str(path))

    doc = obs_trace.load_chrome(str(path))
    assert obs_trace.validate_chrome(doc) == []
    assert doc["traceEvents"], "empty trace"
    # metadata lanes: one process, one thread per stage
    procs = [e for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"
             and e["pid"] == 3]
    threads = [e for e in doc["traceEvents"]
               if e["ph"] == "M" and e["name"] == "thread_name"
               and e["pid"] == 3]
    assert len(procs) == 1 and procs[0]["args"]["name"] == "planned"
    assert {t["tid"] for t in threads} == set(range(spec.n_stages))

    back = obs_trace.timeline_from_chrome(doc, pid=3)
    want = {(int(s), str(k), int(v), int(mb)): (float(a), float(b))
            for (s, k, v, mb, a, b) in timeline}
    got = {(s, k, v, mb): (a / 1e6, b / 1e6)
           for (s, k, v, mb, a, b) in back}
    assert set(got) == set(want)
    for key in want:
        assert got[key] == pytest.approx(want[key], abs=1e-9)


def test_tracer_span_sits_on_the_profiler_clock(tmp_path):
    """A ``Tracer.span`` is also a profiler annotation: under a capture it
    lands on a host plane of the device trace, around the work it spans."""
    import glob
    from jax.profiler import ProfileData
    tracer = obs_trace.Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with tracer.span("build_step"):
            jax.block_until_ready(jnp.ones(8) * 2)
    assert [e["name"] for e in tracer.events] == ["build_step"]
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = [e.duration_ns for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events if e.name == "build_step"]
    assert len(found) == 1 and found[0] > 0


def test_phase_scopes_only_known_names():
    assert obs_trace.PHASES == ("fwd", "bwd", "zero_gather", "zero_reduce",
                                "optimizer")
    with pytest.raises(AssertionError):
        obs_trace.phase("backward")

    @jax.jit
    @obs_trace.phase("optimizer")
    def f(x):
        with obs_trace.phase("fwd"):
            return jnp.sin(x) * 2

    text = f.lower(jnp.ones(4)).as_text(debug_info=True)
    assert "optimizer/fwd/sin" in text


def test_validate_chrome_rejects_malformed():
    assert obs_trace.validate_chrome([]) != []
    assert obs_trace.validate_chrome({"traceEvents": "nope"}) != []
    bad = {"traceEvents": [{"name": "x", "ph": "X", "ts": 0.0, "dur": -1.0,
                            "pid": 0, "tid": 0},
                           {"name": "y", "ph": "?", "ts": 0.0}]}
    assert len(obs_trace.validate_chrome(bad)) == 2


# ---------------------------------------------------------------------------
# Drift: a timeline against itself is exactly zero
# ---------------------------------------------------------------------------
def test_drift_of_timeline_against_itself_is_zero():
    _, timeline = _sim_timeline()
    rep = obs_drift.drift_report(timeline, timeline)
    assert rep["max_abs_drift"] == 0.0
    assert rep["overall"]["missing"] == 0 and rep["overall"]["extra"] == 0
    assert rep["overall"]["matched"] == len(timeline)
    assert "tick drift" in obs_drift.format_report(rep)


def test_drift_table_unit_rendering_self_zero():
    """TickTable.timeline() (the lockstep unit-tick rendering the segmented
    measurement aligns against) is also self-zero, and scale/shift invariant
    (the normalization removes absolute rate)."""
    from repro.core.schedules import PipeSpec
    table = PipeSpec(n_stages=2, layers_per_stage=2, n_microbatches=4,
                     schedule="1f1b").tick_table()
    tl = obs_drift.table_timeline(table)
    assert tl, "empty table timeline"
    rep = obs_drift.drift_report(tl, tl)
    assert rep["max_abs_drift"] == 0.0
    scaled = [(s, k, v, mb, 5.0 + 3.0 * a, 5.0 + 3.0 * b)
              for (s, k, v, mb, a, b) in tl]
    assert obs_drift.drift_report(scaled, tl)["max_abs_drift"] == \
        pytest.approx(0.0, abs=1e-12)


def test_drift_detects_shifted_unit():
    _, timeline = _sim_timeline()
    moved = [list(ev) for ev in timeline]
    moved[0][4] += 0.5 * (max(e[5] for e in timeline)
                          - min(e[4] for e in timeline))
    rep = obs_drift.drift_report([tuple(e) for e in moved], timeline)
    assert rep["max_abs_drift"] > 0.01


# ---------------------------------------------------------------------------
# Serving latency: TTFT / ITL percentiles
# ---------------------------------------------------------------------------
def test_engine_latency_summary():
    from repro.models import transformer as T
    from repro.models.common import AxisCtx, ModelConfig
    from repro.serving.cache import PagedCacheConfig
    from repro.serving.engine import ServingEngine
    from repro.serving.scheduler import SchedulerConfig, poisson_trace

    cfg = ModelConfig(name="obs-serve", arch_type="dense", num_layers=2,
                      d_model=32, num_heads=4, num_kv_heads=2, d_ff=64,
                      vocab_size=64, dtype="float32", param_dtype="float32")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    pcfg = PagedCacheConfig(num_blocks=24, block_size=8,
                            max_blocks_per_seq=3)
    tracer = obs_trace.Tracer()
    eng = ServingEngine(cfg, params,
                        SchedulerConfig(cache=pcfg, max_batch=2,
                                        mode="continuous"),
                        axis=AxisCtx(), use_pallas=False, tracer=tracer)
    rng = np.random.default_rng(3)
    eng.submit_all(poisson_trace(rng, n_requests=4, rate=1.0, vocab=64,
                                 prompt_lens=[8], max_new=[4, 8]))
    eng.run()

    lsum = eng.latency_summary()
    assert lsum["n_requests"] == 4
    for key in ("ttft_ms", "itl_ms"):
        pct = lsum[key]
        assert set(pct) == {"p50", "p95", "p99"}
        assert 0.0 < pct["p50"] <= pct["p95"] <= pct["p99"]
    # the tracer saw prefill/decode spans from the same run
    cats = {e.get("cat") for e in tracer.events if e.get("ph") == "X"}
    assert "serve" in cats
    assert obs_trace.validate_chrome(tracer.to_chrome()) == []


# ---------------------------------------------------------------------------
# Segmented executor: measured ticks + parity with the scan executor
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def mesh_stage_data():
    return compat.make_mesh((2, 2), ("stage", "data"))


def test_segmented_ticks_match_scan_executor(mesh_stage_data):
    """The opt-in one-dispatch-per-tick mode must (a) cover the table's
    non-idle units exactly (measured timeline aligns with zero misses) and
    (b) reproduce the scan executor's gradients and loss — telemetry cannot
    change numerics."""
    from repro.configs.gemma_2b import SMOKE as cfg
    from repro.core import stepfn
    from repro.core.pipeline import make_partitioned_pipeline_grad_fn
    from repro.core.schedules import PipeSpec

    mesh = mesh_stage_data
    spec = PipeSpec(n_stages=2, layers_per_stage=cfg.num_layers // 2,
                    n_microbatches=4, schedule="1f1b")
    table = spec.tick_table()
    prof = stepfn.build_pipeline_tick_profiler(cfg, mesh, spec,
                                               partitioned=True, table=table)
    storage = stepfn.init_pipeline_storage(cfg, mesh, jax.random.PRNGKey(0),
                                           spec, partitioned=True)
    M, gb, seq = spec.n_microbatches, 8, 32
    kb = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(kb, (M, gb // M, seq), 0,
                                          cfg.vocab_size),
             "labels": jax.random.randint(kb, (M, gb // M, seq), 0,
                                          cfg.vocab_size),
             "mask": np.ones((M, gb // M, seq), np.float32)}

    tracer = obs_trace.Tracer()
    events = obs_trace.measure_tick_timeline(prof, storage, batch, warmup=0,
                                             tracer=tracer, pid=1)
    rep = obs_drift.drift_report(events, table.timeline())
    assert rep["overall"]["missing"] == 0 and rep["overall"]["extra"] == 0
    assert rep["overall"]["matched"] > 0
    assert obs_trace.validate_chrome(tracer.to_chrome()) == []

    grads, metrics = prof.finish(prof.last_state, storage, batch)

    axis = stepfn.axis_ctx(mesh)
    tmpl = stepfn.full_template(cfg)
    lt = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
                      tmpl["layers"])
    gfn = make_partitioned_pipeline_grad_fn(cfg, axis, spec, lt, table=table)
    sspecs = stepfn.pipeline_storage_specs(cfg, axis, True)
    bspecs = stepfn.batch_specs(cfg, axis, microbatched=True)
    fn = jax.jit(compat.shard_map(gfn, mesh=mesh,
                                  in_specs=(sspecs, bspecs),
                                  out_specs=(sspecs,
                                             {"loss": P(), "ntok": P()})))
    g2, m2 = fn(storage, batch)
    np.testing.assert_allclose(float(metrics["loss"]), float(m2["loss"]),
                               rtol=1e-6)
    for (pa, ga), (_, gb_) in zip(jax.tree_util.tree_leaves_with_path(grads),
                                  jax.tree_util.tree_leaves_with_path(g2)):
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb_),
                                   rtol=1e-5, atol=1e-6, err_msg=str(pa))


# ---------------------------------------------------------------------------
# Acceptance: pipelined plan -> launch.train with all three artifacts
# ---------------------------------------------------------------------------
def test_train_cli_emits_metrics_trace_and_drift(tmp_path):
    """The ISSUE 7 acceptance run: one smoke ``launch.train --plan`` on a
    pipelined plan produces (a) metrics JSONL with loss / step-time /
    tokens-per-s / MFU, (b) a valid Chrome trace with per-tick stage spans,
    and (c) a drift report aligning the measured timeline against the plan's
    embedded TickTable."""
    from repro.launch import plan as plan_cli
    from repro.launch import train as train_cli

    plan_path = tmp_path / "plan.json"
    doc = plan_cli.main(["--arch", "gemma-2b", "--smoke", "--devices", "4",
                         "--stages", "2", "--microbatches", "2,4",
                         "--global-batch", "4", "--seq-len", "32",
                         "--steps", "2", "--out", str(plan_path)])
    assert doc["execution"]["tick_table"] is not None

    mpath = tmp_path / "metrics.jsonl"
    tpath = tmp_path / "trace.json"
    dpath = tmp_path / "drift.json"
    result = train_cli.main(["--plan", str(plan_path), "--steps", "2",
                             "--metrics", str(mpath),
                             "--trace", str(tpath),
                             "--drift-report", str(dpath)])

    # (a) metrics JSONL: meta + per-step records + summary
    recs = obs_metrics.read_jsonl(str(mpath))
    steps = [r for r in recs if r["event"] == "step"]
    assert len(steps) == 2
    for r in steps:
        for key in ("loss", "step_time_s", "tokens_per_s"):
            assert key in r and np.isfinite(r[key]), (key, r)
        assert r["tokens_per_s"] > 0
        assert r["mfu"] is None      # the CPU has no published peak
    meta = [r for r in recs if r["event"] == "meta"]
    assert meta and meta[0]["stages"] == 2
    assert recs[-1]["event"] == "summary"
    assert "loss" in recs[-1]

    # (b) Chrome trace: valid, with per-tick stage spans (pid 1 = measured)
    tdoc = obs_trace.load_chrome(str(tpath))
    assert obs_trace.validate_chrome(tdoc) == []
    measured = obs_trace.timeline_from_chrome(tdoc, pid=1)
    assert measured, "no measured per-tick stage spans in the trace"
    planned = obs_trace.timeline_from_chrome(tdoc, pid=2)
    assert planned, "no planned timeline lane in the trace"

    # (c) drift report: measured aligns against the plan's embedded table
    with open(dpath) as f:
        rep = json.load(f)
    assert rep["overall"]["missing"] == 0 and rep["overall"]["extra"] == 0
    assert rep["overall"]["matched"] == len(measured)
    assert 0.0 <= rep["max_abs_drift"] <= 1.0
    assert result["max_abs_drift"] == pytest.approx(rep["max_abs_drift"])


def test_plan_cli_dump_table_chrome(tmp_path, capsys):
    """--dump-table --format chrome exports the simulator's predicted
    timeline for the winning plan's table through the shared writer."""
    from repro.launch import plan as plan_cli

    out = tmp_path / "table_trace.json"
    plan_cli.main(["--arch", "gemma-2b", "--smoke", "--devices", "4",
                   "--stages", "2", "--microbatches", "2,4",
                   "--global-batch", "4", "--seq-len", "32",
                   "--dump-table", "--format", "chrome",
                   "--table-out", str(out)])
    assert os.path.exists(out)
    doc = obs_trace.load_chrome(str(out))
    assert obs_trace.validate_chrome(doc) == []
    tl = obs_trace.timeline_from_chrome(doc, pid=0)
    assert tl, "no planned units in the dumped table trace"
    assert {e[1] for e in tl} <= {"F", "B", "Bd", "Bw"}
