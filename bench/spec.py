"""The benchmark's description, found by name.

``BENCHMARK.json`` at the checkout's root lists the configurations, the
cells (``workloads``) and the metrics.  Everything that belongs to one of
them lives in a file of its own, which this module finds by the name the
list gives:

    configs   bench/configs/<config>.json     (the entry's ``file``)
    traffic   bench/workloads/<traffic>.json  (the job's shape and layout)
    drivers   bench/drivers/<driver>.py       (named by the traffic file)
    limits    bench/limits/<cell>.json        (what ``correct`` compares)
    metrics   bench/metrics/<metric>.py       (one reader per per-layer metric)

A later change adds a configuration, a cell or a metric by adding a file and
an entry; nothing here names one.  An unknown name or a malformed file raises
``SpecError``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
TRAFFIC_KEYS = {"driver", "seq_len", "global_batch", "n_microbatches", "noise",
                "partitioned", "mesh", "check_steps", "trace_steps",
                "in_flight", "reference_rows"}
CONFIG_KEYS = {"name", "source", "reference", "arch_type", "num_layers",
               "d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
               "vocab_size", "hidden_act", "glu", "norm", "rope_theta",
               "tie_embeddings", "precision", "optimizer"}
OPT_KEYS = {"lr", "b1", "b2", "eps", "weight_decay", "grad_clip",
            "warmup_steps", "decay_steps", "min_lr_ratio"}
CHECKS = ("loss_gap", "grad_gap", "change_gap")


class SpecError(ValueError):
    """A name the benchmark does not know, or a file it cannot use."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple       # metric entries this cell reports with --trace 0
    per_layer: tuple        # metric entries this cell reports with --trace 1

    @property
    def tokens_per_step(self) -> int:
        return self.traffic["global_batch"] * self.traffic["seq_len"]


def _read_json(path: pathlib.Path, what: str) -> dict:
    try:
        with open(path) as f:
            obj = json.load(f)
    except FileNotFoundError:
        raise SpecError(f"{what}: no file {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{what}: {path} is not JSON: {e}") from None
    if not isinstance(obj, dict):
        raise SpecError(f"{what}: {path} holds no JSON object")
    return obj


def _name(value, what: str) -> str:
    if not isinstance(value, str) or not NAME.match(value):
        raise SpecError(f"{what}: {value!r} is not a valid name")
    return value


def check_keys(obj: dict, need: set, what: str) -> None:
    missing = sorted(need - set(obj))
    if missing:
        raise SpecError(f"{what}: missing keys {missing}")


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    bench = _read_json(pathlib.Path(root) / "BENCHMARK.json", "BENCHMARK.json")
    check_keys(bench, {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"},
                "BENCHMARK.json")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for entry in bench[group]:
            n = _name(entry.get("name"), f"BENCHMARK.json {group}")
            if n in seen:
                raise SpecError(f"BENCHMARK.json {group}: {n!r} twice")
            seen.add(n)
    return bench


def load_config(root: pathlib.Path, entry: dict) -> dict:
    cfg = _read_json(pathlib.Path(root) / entry["file"],
                     f"config {entry['name']!r}")
    check_keys(cfg, CONFIG_KEYS, f"config {entry['name']!r}")
    check_keys(cfg["optimizer"], OPT_KEYS, f"config {entry['name']!r} optimizer")
    if cfg["name"] != entry["name"]:
        raise SpecError(f"config file {entry['file']} is named "
                        f"{cfg['name']!r}, not {entry['name']!r}")
    return cfg


def load_traffic(root: pathlib.Path, name: str) -> dict:
    _name(name, "traffic")
    t = _read_json(pathlib.Path(root) / "bench" / "workloads" / f"{name}.json",
                   f"traffic {name!r}")
    check_keys(t, TRAFFIC_KEYS, f"traffic {name!r}")
    driver = _name(t["driver"], f"traffic {name!r} driver")
    if not (pathlib.Path(root) / "bench" / "drivers" / f"{driver}.py").is_file():
        raise SpecError(f"traffic {name!r}: unknown driver {driver!r} "
                        f"(no bench/drivers/{driver}.py)")
    if t["global_batch"] % t["n_microbatches"]:
        raise SpecError(f"traffic {name!r}: {t['n_microbatches']} micro-"
                        f"batches do not divide {t['global_batch']} rows")
    if t["global_batch"] % t["reference_rows"]:
        raise SpecError(f"traffic {name!r}: reference blocks of "
                        f"{t['reference_rows']} rows do not divide "
                        f"{t['global_batch']}")
    if not 1 <= t["check_steps"] <= 3:
        raise SpecError(f"traffic {name!r}: check_steps must be 1 to 3")
    return t


def load_limits(root: pathlib.Path, cell: str) -> dict:
    lim = _read_json(pathlib.Path(root) / "bench" / "limits" / f"{cell}.json",
                     f"limits of {cell!r}")
    check_keys(lim, set(CHECKS), f"limits of {cell!r}")
    for k in CHECKS:
        if not isinstance(lim[k], (int, float)) or lim[k] <= 0:
            raise SpecError(f"limits of {cell!r}: {k} must be a number > 0")
    return lim


def metric_applies(entry: dict, cell_name: str, e2e_names: set) -> bool:
    if entry.get("moves") is not None and entry["moves"] not in e2e_names:
        return False
    ws = entry.get("workloads")
    return ws is None or cell_name in ws


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SpecError(f"unknown workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w.get("config") not in configs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w.get('config')!r}")
    if w.get("chips") not in (1, 4):
        raise SpecError(f"workload {name!r}: chips must be 1 or 4")
    cfg = load_config(root, configs[w["config"]])
    traffic = load_traffic(root, _name(w.get("traffic"), "traffic"))
    mesh = traffic["mesh"]
    n_dev = mesh.get("stages", 1) * mesh.get("data", 1) * mesh.get("model", 1)
    if n_dev != w["chips"]:
        raise SpecError(f"workload {name!r}: mesh {mesh} needs {n_dev} "
                        f"chips, the entry asks for {w['chips']}")
    e2e = tuple(m for m in bench["end_to_end"]
                if "workloads" not in m or name in m["workloads"])
    e2e_names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if metric_applies(m, name, e2e_names))
    for m in per_layer:
        metric_module(m["name"], root)     # every reader exists and loads
    return Cell(name=name, chips=w["chips"], config=cfg, traffic=traffic,
                limits=load_limits(root, name), end_to_end=e2e,
                per_layer=per_layer)


def metric_module(name: str, root: pathlib.Path = ROOT):
    """The reader ``bench/metrics/<name>.py``: a module with
    ``read(ctx) -> float | None``."""
    _name(name, "metric")
    path = pathlib.Path(root) / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"metric {name!r}: no reader {path}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"metric {name!r}: {path} defines no read(ctx)")
    return mod
