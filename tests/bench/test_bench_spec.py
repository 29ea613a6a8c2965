"""The harness finds configurations, cells and metrics by name, refuses an
unknown name or a malformed file, and takes a new cell as new files plus a
new entry, with no existing file edited."""
import hashlib
import json
import math
import re

import pytest

from bench import spec
from bench.spec import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_loads_with_its_files(name):
    cell = spec.load_cell(name)
    assert cell.config["name"] == next(
        w["config"] for w in BENCH["workloads"] if w["name"] == name)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.metric_module(m["name"]).read)


def test_a_driver_is_found_by_its_file(bench_root):
    traffic = BENCH["workloads"][0]["traffic"]
    drivers = bench_root / "bench" / "drivers"
    (drivers / "throwaway.py").write_text("class Driver:\n    pass\n")
    _edit(bench_root, f"bench/workloads/{traffic}.json",
          lambda t: t.update(driver="throwaway"))
    assert spec.load_cell(CELLS[0], bench_root).traffic["driver"] == "throwaway"
    (drivers / "throwaway.py").unlink()
    with pytest.raises(spec.SpecError, match="no bench/drivers/throwaway.py"):
        spec.load_cell(CELLS[0], bench_root)


def test_a_driver_refuses_traffic_without_its_keys():
    import dataclasses
    from bench.drivers import layered
    cell = spec.load_cell(CELLS[0])
    t = {k: v for k, v in cell.traffic.items() if k != "method"}
    with pytest.raises(spec.SpecError, match="missing keys"):
        layered.Driver(dataclasses.replace(cell, traffic=t))


def test_unknown_workload_is_refused():
    with pytest.raises(spec.SpecError, match="unknown workload"):
        spec.load_cell("no-such-cell")


def test_unknown_metric_reader_is_refused():
    with pytest.raises(spec.SpecError, match="no reader"):
        spec.metric_module("no_such_metric")


def _edit(root, name, fn):
    p = root / name
    obj = json.loads(p.read_text())
    fn(obj)
    p.write_text(json.dumps(obj))


@pytest.mark.parametrize("breakage,match", [
    (lambda b: b["workloads"][0].update(config="nope"), "unknown config"),
    (lambda b: b["workloads"][0].update(traffic="nope"), "no file"),
    (lambda b: b["workloads"][0].update(traffic="bad name!"), "not a valid name"),
    (lambda b: b["workloads"][0].update(chips=4), "needs 1 chips"),
    (lambda b: b["workloads"].append(dict(b["workloads"][0])), "twice"),
])
def test_malformed_benchmark_is_refused(bench_root, breakage, match):
    _edit(bench_root, "BENCHMARK.json", breakage)
    with pytest.raises(spec.SpecError, match=match):
        spec.load_cell(CELLS[0], bench_root)


@pytest.mark.parametrize("breakage,match", [
    (lambda t: t.pop("seq_len"), "missing keys"),
    (lambda t: t.update(driver="warp"), "unknown driver"),
    (lambda t: t.update(n_microbatches=7), "do not divide"),
    (lambda t: t.update(check_steps=5), "check_steps"),
])
def test_malformed_traffic_is_refused(bench_root, breakage, match):
    traffic = BENCH["workloads"][0]["traffic"]
    _edit(bench_root, f"bench/workloads/{traffic}.json", breakage)
    with pytest.raises(spec.SpecError, match=match):
        spec.load_cell(CELLS[0], bench_root)


def test_malformed_files_are_refused(bench_root):
    (bench_root / "bench" / "limits" / f"{CELLS[0]}.json").write_text("{")
    with pytest.raises(spec.SpecError, match="not JSON"):
        spec.load_cell(CELLS[0], bench_root)
    _edit(bench_root, BENCH["configs"][0]["file"], lambda c: c.pop("d_ff"))
    with pytest.raises(spec.SpecError, match="missing keys"):
        spec.load_config(bench_root, BENCH["configs"][0])
    (bench_root / "bench" / "metrics" / "empty.py").write_text("x = 1\n")
    with pytest.raises(spec.SpecError, match="no read"):
        spec.metric_module("empty", bench_root)


def _digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_new_cell_and_metric_are_new_files_and_entries(bench_root):
    before = _digest(bench_root / "bench")
    w = dict(BENCH["workloads"][0], name="x32-s256-throwaway",
             traffic="s256-throwaway")
    t = json.loads((bench_root / "bench" / "workloads"
                    / f"{BENCH['workloads'][0]['traffic']}.json").read_text())
    (bench_root / "bench" / "workloads" / "s256-throwaway.json").write_text(
        json.dumps(dict(t, seq_len=256, global_batch=128)))
    (bench_root / "bench" / "limits" / "x32-s256-throwaway.json").write_text(
        json.dumps({"loss_gap": 1e-3, "grad_gap": 0.1, "change_gap": 0.1}))
    (bench_root / "bench" / "metrics" / "throwaway_share.py").write_text(
        "def read(ctx):\n    return None\n")

    def add(b):
        b["workloads"].append(w)
        b["per_layer"].append({"name": "throwaway_share", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device (TPU v5e)",
                               "moves": "tokens_per_s_per_chip",
                               "workloads": ["x32-s256-throwaway"]})
    _edit(bench_root, "BENCHMARK.json", add)
    cell = spec.load_cell("x32-s256-throwaway", bench_root)
    assert cell.traffic["seq_len"] == 256 and cell.tokens_per_step == 32768
    assert "throwaway_share" in {m["name"] for m in cell.per_layer}
    assert "throwaway_share" not in {
        m["name"] for m in spec.load_cell(CELLS[0], bench_root).per_layer}
    after = _digest(bench_root / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


# -- BENCHMARK.json against the benchmark contract ---------------------------
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and ".." not in p and not p.startswith("/")
    assert 1 <= BENCH["run_seconds"] <= 51
    n = 24        # the most cells any later change may bring
    assert (2 + 14 * n) * (BENCH["run_seconds"] + 60) + n * 180 + 1200 <= 43200
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and NAME.match(c["name"])
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert len(c["why"]) <= 200
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in BENCH["workloads"]}
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert math.isfinite(BENCH["run_seconds"])
