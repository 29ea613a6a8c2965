"""The control and the planted faults come out not correct through
``bench/check.py``'s verdict, at a size a test run holds: ``bench/control.py``'s
readings on the CPU, held to the limits of that size.  The control is the
reference in the program's place with every matrix product in float8 e4m3;
``half_rows`` leaves half of each batch's rows out; a state left unchanged
reads 1 on ``change_gap``."""
import jax

from bench import check, control
from bench_tiny import LAYERED, tiny_cell


def test_control_fails_and_program_passes():
    cell = tiny_cell(*LAYERED)
    out = control.readings(cell, [21], 1, jax.devices()[:cell.chips])
    got = out[21]
    assert got["program"]["correct"], got["program"]
    assert not got["control"]["correct"], got["control"]
    assert not got["half_rows"]["correct"], got["half_rows"]
    # the program, in bfloat16, reads closer to the reference than the
    # control in float8 does
    assert all(got["program"][n] < got["control"][n]
               for n in ("loss_gap", "grad_gap")), got
    unchanged = {n: (0.0, "") for n in control.NUMBERS}
    unchanged["change_gap"] = (control.summary(out)["change_gap"]
                               ["state_unchanged"], "every leaf")
    ok, _ = check.verdict(unchanged, cell.limits)
    assert not ok


def test_verdict_holds_each_number_to_its_limit():
    limits = {"loss_gap": 1e-4, "grad_gap": 1e-2}
    ok, out = check.verdict({"loss_gap": (5e-5, "step 1"),
                             "grad_gap": (2e-2, "wq[0]")}, limits)
    assert not ok and out["grad_gap"] == {"value": 2e-2, "limit": 1e-2}
    ok, _ = check.verdict({"loss_gap": (5e-5, ""), "grad_gap": (1e-2, "")},
                          limits)
    assert ok
    ok, out = check.verdict({"loss_gap": (float("inf"), ""),
                             "grad_gap": (0.0, "")}, limits)
    assert not ok and out["loss_gap"]["value"] is None
