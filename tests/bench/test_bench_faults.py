"""A run with the timed path broken underneath comes out not correct.

Each case drives the rest of a run (``bench.run.run``, past the look for a
chip) on the CPU at a tiny size, against the limits of that size
(``bench_tiny.LIMITS``), with one fault a train step can have:

- the step returns its state unchanged;
- half of each batch's rows are left out, the mean taken over the rest.

A one-chip cell has no exchange between chips to leave out.

The one-chip cell's run unbroken comes out correct.
"""
import jax
import jax.numpy as jnp
import pytest

from bench import run as bench_run
from bench.drivers import common
from bench_tiny import LAYERED, tiny_cell


def _run(cell, seed=11):
    return bench_run.run(cell, seed, 0.2, False,
                         devices=jax.devices()[:cell.chips])


def _breaking(monkeypatch, wrap):
    """Replace the compiled step the window drives by ``wrap(compiled)``."""
    real = common.Driver.compile

    def compile_(self, *args):
        compiled = real(self, *args)
        step = wrap(compiled)
        step.memory_analysis = compiled.memory_analysis
        return step
    monkeypatch.setattr(common.Driver, "compile", compile_)


def state_unchanged(compiled):
    def step(storage, opt, batch):
        copy = lambda t: jax.tree.map(jnp.copy, t)          # noqa: E731
        _, _, metrics = compiled(copy(storage), copy(opt), batch)
        return storage, opt, metrics
    return step


def half_rows(compiled):
    def step(storage, opt, batch):
        mask = batch["mask"]
        half = mask.shape[1] // 2
        return compiled(storage, opt, dict(batch,
                                           mask=mask.at[:, half:].set(0)))
    return step


def test_sound_run_is_correct():
    res = _run(tiny_cell(*LAYERED))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("fault", [state_unchanged, half_rows])
def test_broken_step_is_not_correct(monkeypatch, fault):
    _breaking(monkeypatch, fault)
    res = _run(tiny_cell(*LAYERED))
    assert not res["correct"], res["checks"]
