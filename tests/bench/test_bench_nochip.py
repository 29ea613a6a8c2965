"""Without a TPU the benchmark exits non-zero and prints no result; in a
directory that holds only BENCHMARK.json and the benchmark's paths (no
program) it cannot start."""
import json
import os
import subprocess
import sys

from bench.spec import ROOT


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=240)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            pass
    return True


def test_no_tpu_no_result():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = bench["workloads"][0]["name"]
    p = _run(ROOT, "bench/run.py", "--workload", cell, "--seed", "3",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "TPU" in p.stderr


def test_unknown_workload_no_result():
    p = _run(ROOT, "bench/run.py", "--workload", "nope", "--seed", "3",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and _no_result(p.stdout)


def test_without_the_program_nothing_runs(bench_root):
    # the driver of every cell imports the program from src/, which is not
    # there: the run fails before it could measure anything
    p = _run(bench_root, "-c", "import sys; sys.path.insert(0, '.'); "
             "import bench.drivers.layered")
    assert p.returncode != 0 and "repro" in p.stderr
