#!/usr/bin/env python3
"""Proof that the training path runs on a TPU: the paper's X_32 through
``repro.launch.train``, at its published shape (32 layers, d_model 1024,
16 heads of 64, FFN 4096, vocab 32000, sequence 16x = 512), random weights
from a seed, layered accumulation with ZeRO-partitioned storage.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --four-chip  # four chips: pipeline vs layered

One chip:
  1. step 0 with the Pallas kernels on and off, through the same
     ``stepfn.build_train_step`` the trainer uses: loss and grad norm agree
     within stated tolerances, and the compiled kernels-on step holds
     ``tpu_custom_call`` (flash attention and fused AdamW were compiled by
     Mosaic, not interpreted or bypassed);
  2. ``launch.train.main`` trains a few steps: every loss finite, step 0
     near ln(vocab), the trajectory falling;
  3. memory: each compiled step's ``memory_analysis()`` within the chip's
     ``bytes_limit``, and peak device memory from ``memory_stats()`` under
     16 GiB.
Four chips (``--four-chip``) runs only the paper's pipeline and what it is
compared with: the modular schedule on 2 stages x 2-way data, partitioned,
against the non-pipelined layered/partitioned trainer on 4-way data, same
seed, batch and microbatches; loss trajectories agree, and each device's
peak memory shows the state spread over the chips.

Step times printed on the way are set-up checks, not speed measurements.
The last line of stdout is ``{"ok": true, "device": {...}}``; it is printed
only when every check passed.  Without a TPU the script exits 2 and prints
no result.  One process holds the chip(s); nothing else is started.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out")

ARCH = "paper-x32"
SEQ = 512                     # 16 x for X_32 (configs/paper_x.py)
GLOBAL_BATCH = 64
MICROBATCHES = 8
SEED = 0
LR = 3e-4
STEPS = 40                    # one chip
PIPE_STEPS = 6                # four chips, each of the two runs

# Kernels on vs off at step 0: same params and batch, and loss and grad
# norm are taken before the update.  The two paths differ in
# rounding alone: the flash kernel keeps logits and probabilities in fp32,
# the reference rounds them to bf16.  On the CPU at X_32 width that moved
# the loss by <2e-6 and the grad norm by <1e-4 (relative); dropping the
# causal mask moved them by 3e-4 / 8e-2 and dropping the softmax scale by
# 6e-4 / 40x at 8 layers.  The tolerances sit between the two.
LOSS_RTOL = 1e-4
GNORM_RTOL = 5e-3
# step 0 of an untrained model predicts close to uniform: ln(vocab)
LOSS0_ATOL = 0.5
# pipelined vs layered: the same math in another order of reductions; a
# lost or doubled microbatch or stage gradient moves the loss by far more
PIPE_LOSS_RTOL = 1e-3


def _check(failures: list, name: str, ok: bool, detail: str) -> None:
    print(f"{'PASS' if ok else 'FAIL'} {name}: {detail}", flush=True)
    if not ok:
        failures.append(name)


def _train_args(steps: int, metrics: str) -> list[str]:
    """launch.train arguments shared by every run: X_32 at full size."""
    return ["--arch", ARCH, "--seq-len", str(SEQ),
            "--global-batch", str(GLOBAL_BATCH),
            "--microbatches", str(MICROBATCHES), "--steps", str(steps),
            "--lr", str(LR), "--seed", str(SEED),
            "--metrics", os.path.join(OUT, metrics)]


def _peaks(jax) -> list[int]:
    return [d.memory_stats().get("peak_bytes_in_use", 0)
            for d in jax.devices()]


def _check_fit(jax, failures: list, compiled, name: str) -> None:
    """The compiler's own account of what the program holds at once:
    arguments, outputs not aliased to a donated argument, temporaries."""
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    limit = jax.devices()[0].memory_stats()["bytes_limit"]
    _check(failures, f"{name} fits", need < limit,
           f"memory_analysis: arguments {ma.argument_size_in_bytes}, "
           f"outputs {ma.output_size_in_bytes} (aliased "
           f"{ma.alias_size_in_bytes}), temporaries {ma.temp_size_in_bytes}: "
           f"{need} ({need / 2**30:.2f} GiB) of bytes_limit {limit}")


def kernels_on_off(jax, failures: list) -> None:
    """Step 0 of the trainer's own step with the kernels on and off."""
    from repro import configs
    from repro.core import stepfn
    from repro.core.accumulation import AccumConfig
    from repro.data.synthetic import DataConfig, batch_for
    from repro.launch.mesh import make_train_mesh
    from repro.optim.adam import AdamConfig, adam_init

    cfg = configs.get_config(ARCH)
    mesh = make_train_mesh(data=1, model=1)
    acc = AccumConfig(method="layered", partitioned=True,
                      n_microbatches=MICROBATCHES)
    # as launch.train builds it, so that this is the trainer's own program
    # (and the trainer finds it in the compile cache)
    opt_cfg = AdamConfig(lr=LR, warmup_steps=max(STEPS // 10, 1),
                         decay_steps=STEPS)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=SEQ,
                      global_batch=GLOBAL_BATCH,
                      n_microbatches=MICROBATCHES, seed=SEED)
    batch = batch_for(cfg, data, 0)
    got = {}
    for kernels in (True, False):
        c = dataclasses.replace(cfg, kernels=kernels)
        step = stepfn.build_train_step(c, mesh, acc, opt_cfg, donate=True)
        storage = stepfn.init_storage(c, mesh, jax.random.PRNGKey(SEED),
                                      partitioned=True)
        opt = adam_init(storage)
        t0 = time.perf_counter()
        compiled = step.lower(storage, opt, batch).compile()
        t1 = time.perf_counter()
        _check_fit(jax, failures, compiled,
                   f"kernels-{'on' if kernels else 'off'} step")
        if kernels:
            n = compiled.as_text().count("tpu_custom_call")
            _check(failures, "compiled step holds Mosaic kernels", n > 0,
                   f"{n} tpu_custom_call in the kernels-on train step HLO")
        m = compiled(storage, opt, batch)[2]   # new state freed at once
        got[kernels] = (float(m["loss"]), float(m["grad_norm"]))
        print(f"kernels={'on' if kernels else 'off'}: step 0 loss "
              f"{got[kernels][0]!r} grad_norm {got[kernels][1]!r} "
              f"(set-up: compile {t1 - t0:.1f}s, step "
              f"{time.perf_counter() - t1:.1f}s)", flush=True)
        del storage, opt, m, compiled
    (l_on, g_on), (l_off, g_off) = got[True], got[False]
    dl, dg = abs(l_on - l_off) / abs(l_off), abs(g_on - g_off) / abs(g_off)
    _check(failures, "kernels on/off loss", dl <= LOSS_RTOL,
           f"relative difference {dl:.3e} (tolerance {LOSS_RTOL:g})")
    _check(failures, "kernels on/off grad norm", dg <= GNORM_RTOL,
           f"relative difference {dg:.3e} (tolerance {GNORM_RTOL:g})")


def train_one_chip(jax, failures: list) -> None:
    from repro import configs
    from repro.launch import train

    res = train.main(_train_args(STEPS, "chip_smoke_x32.jsonl")
                     + ["--mesh", "1x1", "--method", "layered",
                        "--log-every", "5"])
    losses = res["losses"]
    print("losses " + json.dumps(losses), flush=True)
    _check(failures, "finite losses", all(math.isfinite(v) for v in losses),
           f"{len(losses)} steps")
    vocab = configs.get_config(ARCH).vocab_size
    ln_v = math.log(vocab)
    _check(failures, "step 0 loss near ln(vocab)",
           abs(losses[0] - ln_v) <= LOSS0_ATOL,
           f"{losses[0]!r} vs ln({vocab}) = {ln_v:.4f} (+-{LOSS0_ATOL})")
    n = len(losses)
    tbar = (n - 1) / 2
    slope = (sum((t - tbar) * v for t, v in enumerate(losses))
             / sum((t - tbar) ** 2 for t in range(n)))
    q = max(n // 4, 1)
    first, last = sum(losses[:q]) / q, sum(losses[-q:]) / q
    _check(failures, "falling loss", slope < 0 and last < first,
           f"least-squares slope {slope:.3e}/step, mean of first {q} "
           f"{first:.4f} -> last {q} {last:.4f}")


def pipeline_vs_layered(jax, failures: list) -> None:
    from repro.launch import train

    runs = {}
    for name, extra in (
            ("pipelined", ["--stages", "2", "--mesh", "2x1",
                           "--schedule", "modular"]),
            ("layered", ["--mesh", "4x1", "--method", "layered"])):
        res = train.main(_train_args(PIPE_STEPS,
                                     f"chip_smoke_x32_{name}.jsonl")
                         + extra)
        runs[name] = res["losses"]
        print(f"{name} losses {json.dumps(res['losses'])}", flush=True)
        print(f"peak_bytes_in_use per device after {name}: "
              f"{json.dumps(_peaks(jax))}", flush=True)
    a, b = runs["pipelined"], runs["layered"]
    worst = max(abs(x - y) / abs(y) for x, y in zip(a, b))
    _check(failures, "pipelined vs layered losses",
           len(a) == len(b) == PIPE_STEPS and worst <= PIPE_LOSS_RTOL,
           f"worst relative difference {worst:.3e} over {PIPE_STEPS} steps "
           f"(tolerance {PIPE_LOSS_RTOL:g})")
    peaks = _peaks(jax)
    spread = min(peaks) > 0 and max(peaks) <= 2 * min(peaks)
    _check(failures, "state spread over the chips", spread,
           f"per-device peak bytes {peaks}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run the 2-stage modular pipeline against the "
                         "4-way layered trainer on four chips, and nothing "
                         "else")
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend {jax.default_backend()!r})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    os.makedirs(OUT, exist_ok=True)
    devs = jax.devices()
    want = 4 if args.four_chip else 1
    failures: list = []
    _check(failures, "device count", len(devs) >= want,
           f"{len(devs)} x {devs[0].device_kind}")
    if failures:
        return 1
    if args.four_chip:
        pipeline_vs_layered(jax, failures)
    else:
        kernels_on_off(jax, failures)
        train_one_chip(jax, failures)
        stats = devs[0].memory_stats()
        peak, limit = stats["peak_bytes_in_use"], stats.get("bytes_limit")
        _check(failures, "peak device memory", peak < 16 * 2**30,
               f"peak_bytes_in_use {peak} ({peak / 2**30:.2f} GiB) of "
               f"bytes_limit {limit}, under 16 GiB")
    if failures:
        print(f"chip_smoke: {len(failures)} check(s) failed: {failures}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
