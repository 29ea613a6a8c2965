"""Roofline accounting for lowered steps on the production mesh.

``compiled.cost_analysis()`` on the CPU backend does NOT multiply while-loop
bodies by their trip count (verified: a 5-layer scan reports ~1 layer of
FLOPs), so deriving roofline terms from it would undercount any scanned model
by ``num_layers``x.  Instead we walk the jaxpr: ``lax.scan`` lengths are known
statically, collectives carry their mesh axis names, and ``dot_general``
shapes give exact MXU FLOPs.  All shapes inside ``shard_map`` are per-device,
so every figure below is already per-chip.

Terms (the planner's modelled target, the TPU v5e row of ``PEAKS``):
  compute    = dot_flops / 197e12            (bf16 peak per chip)
  memory     = hbm_bytes / 819e9             (HBM bandwidth)
  collective = sum_axis wire_bytes / 50e9    (ICI per link; pod axis reported
                                              separately — DCN is slower)

``hbm_bytes`` is a traffic *model*, not a measurement: inputs+outputs of every
dot_general (weights, activations, KV cache reads) plus collective payloads
plus scan xs streaming.  XLA fusion can only reduce it; treat as upper bound.

Wire-byte conventions (bandwidth-optimal ring algorithms, as in the paper's
appendix C.4):
  all_gather      (n-1)/n * gathered bytes
  psum            2 (n-1)/n * bytes          (reduce-scatter + all-gather)
  psum_scatter    (n-1)/n * bytes
  all_to_all      (n-1)/n * bytes
  ppermute        bytes
"""
from __future__ import annotations

import dataclasses
import math
from collections import defaultdict
from typing import Any, Callable

import jax
import jax.numpy as jnp

# --- hardware constants ------------------------------------------------------
# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM).
# A device kind that is not here has no peak: ``peak_of`` raises.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9},
}

# the chip the planner models: its row of PEAKS, named explicitly
TARGET_KIND = "TPU v5 lite"
PEAK_FLOPS = PEAKS[TARGET_KIND]["flops"]     # bf16 per chip
HBM_BW = PEAKS[TARGET_KIND]["hbm_bw"]        # bytes/s per chip
ICI_BW = 50e9              # bytes/s per link
DCN_BW = 6.25e9            # bytes/s per chip across pods (50 Gb/s assumption)

COLLECTIVES = {
    "all_gather": lambda n: (n - 1) / n,
    "all_gather_invariant": lambda n: (n - 1) / n,
    "psum": lambda n: 2 * (n - 1) / n,
    "psum_invariant": lambda n: 2 * (n - 1) / n,
    "psum2": lambda n: 2 * (n - 1) / n,
    "reduce_scatter": lambda n: (n - 1) / n,
    "psum_scatter": lambda n: (n - 1) / n,
    "all_to_all": lambda n: (n - 1) / n,
    "ppermute": lambda n: 1.0,
    "pmax": lambda n: 2 * (n - 1) / n,
    "pmin": lambda n: 2 * (n - 1) / n,
}

_INNER_JAXPR_PRIMS = ("jit", "pjit", "closed_call", "custom_vjp_call_jaxpr",
                      "custom_jvp_call", "custom_vjp_call", "remat2", "checkpoint")


@dataclasses.dataclass
class Costs:
    """Per-device cost accounting."""
    dot_flops: float = 0.0
    hbm_bytes: float = 0.0
    coll_bytes: dict = dataclasses.field(default_factory=lambda: defaultdict(float))
    coll_counts: dict = dataclasses.field(default_factory=lambda: defaultdict(float))
    notes: list = dataclasses.field(default_factory=list)

    # -- roofline terms ----------------------------------------------------
    def compute_s(self) -> float:
        return self.dot_flops / PEAK_FLOPS

    def memory_s(self) -> float:
        return self.hbm_bytes / HBM_BW

    def collective_s(self) -> float:
        t = 0.0
        for ax, b in self.coll_bytes.items():
            t += b / (DCN_BW if ax == "pod" else ICI_BW)
        return t

    def dominant(self) -> str:
        terms = {"compute": self.compute_s(), "memory": self.memory_s(),
                 "collective": self.collective_s()}
        return max(terms, key=terms.get)

    def summary(self) -> dict:
        return {
            "dot_flops": self.dot_flops,
            "hbm_bytes": self.hbm_bytes,
            "coll_bytes": dict(self.coll_bytes),
            "compute_s": self.compute_s(),
            "memory_s": self.memory_s(),
            "collective_s": self.collective_s(),
            "dominant": self.dominant(),
        }


def _aval_bytes(aval) -> float:
    try:
        return math.prod(aval.shape) * aval.dtype.itemsize
    except Exception:
        return 0.0


def _dot_flops(eqn) -> float:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = (v.aval for v in eqn.invars[:2])
    batch = math.prod(lhs.shape[i] for i in lb) if lb else 1
    contract = math.prod(lhs.shape[i] for i in lc) if lc else 1
    lfree = math.prod(lhs.shape[i] for i in range(len(lhs.shape))
                      if i not in lc and i not in lb)
    rfree = math.prod(rhs.shape[i] for i in range(len(rhs.shape))
                      if i not in rc and i not in rb)
    return 2.0 * batch * contract * lfree * rfree


def _axis_names(eqn) -> tuple:
    for key in ("axes", "axis_name", "axis_names"):
        if key in eqn.params:
            v = eqn.params[key]
            if v is None:
                continue
            return v if isinstance(v, tuple) else (v,)
    return ()


def walk_jaxpr(jaxpr, mult: float, costs: Costs, axis_sizes: dict,
               cond_weight: float = 0.5) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "scan":
            walk_jaxpr(eqn.params["jaxpr"].jaxpr, mult * eqn.params["length"],
                       costs, axis_sizes, cond_weight)
            # scan xs/ys streaming traffic (per iteration slices)
            n = eqn.params["length"]
            for v in eqn.invars:
                if v.aval.shape and v.aval.shape[0] == n:
                    costs.hbm_bytes += mult * _aval_bytes(v.aval)
        elif name == "while":
            body = eqn.params["body_jaxpr"].jaxpr
            costs.notes.append("while loop: trip count unknown, counted once")
            walk_jaxpr(body, mult, costs, axis_sizes, cond_weight)
        elif name == "cond":
            for br in eqn.params["branches"]:
                walk_jaxpr(br.jaxpr, mult * cond_weight, costs, axis_sizes,
                           cond_weight)
        elif name == "shard_map":
            inner = eqn.params["jaxpr"]
            walk_jaxpr(inner.jaxpr if hasattr(inner, "jaxpr") else inner,
                       mult, costs, axis_sizes, cond_weight)
        elif name in _INNER_JAXPR_PRIMS:
            inner = (eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                     or eqn.params.get("fun_jaxpr"))
            if inner is not None:
                walk_jaxpr(inner.jaxpr if hasattr(inner, "jaxpr") else inner,
                           mult, costs, axis_sizes, cond_weight)
        elif name == "dot_general":
            f = _dot_flops(eqn)
            costs.dot_flops += mult * f
            costs.hbm_bytes += mult * (sum(_aval_bytes(v.aval) for v in eqn.invars)
                                       + sum(_aval_bytes(v.aval) for v in eqn.outvars))
        elif name in COLLECTIVES:
            axes = _axis_names(eqn)
            nbytes = sum(_aval_bytes(v.aval) for v in eqn.invars)
            out_bytes = sum(_aval_bytes(v.aval) for v in eqn.outvars)
            wire_base = max(nbytes, out_bytes)
            for ax in axes:
                n = axis_sizes.get(ax, 1)
                if n <= 1:
                    continue
                wire = COLLECTIVES[name](n) * wire_base
                costs.coll_bytes[ax] += mult * wire
                costs.coll_counts[(ax, name)] += mult
            costs.hbm_bytes += mult * (nbytes + out_bytes)
        elif name in ("gather", "dynamic_slice"):
            # reads: the extracted slice
            costs.hbm_bytes += mult * sum(_aval_bytes(v.aval) for v in eqn.outvars)
        elif name in ("dynamic_update_slice", "scatter", "scatter-add"):
            # writes are in-place on TPU: count the update operand, not the
            # whole buffer aval
            upd = eqn.invars[1] if len(eqn.invars) > 1 else eqn.invars[0]
            costs.hbm_bytes += mult * _aval_bytes(upd.aval)


def analyze(fn: Callable, *args, mesh=None, cond_weight: float = 0.5) -> Costs:
    """Trace ``fn`` (typically a jitted shard_map step) with abstract args and
    account its per-device costs."""
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh is not None else {}
    jpr = jax.make_jaxpr(fn)(*args)
    costs = Costs()
    walk_jaxpr(jpr.jaxpr, 1.0, costs, axis_sizes, cond_weight)
    return costs


def model_flops_train(cfg, global_batch: int, seq: int) -> float:
    """6*N*D rule (paper appendix C.1: fwd 2ND + bwd 4ND; +2ND with full
    activation recompute, reported separately)."""
    n_active = cfg.param_count(active_only=True)
    return 6.0 * n_active * global_batch * seq


def model_flops_decode(cfg, global_batch: int) -> float:
    return 2.0 * cfg.param_count(active_only=True) * global_batch


def peak_of(device_kind: str) -> dict:
    """The ``PEAKS`` row of ``device_kind``; an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peak for device kind {device_kind!r} "
                         f"(known: {sorted(PEAKS)})") from None


def mfu(flops_per_step: float, step_time_s: float, *, device_kind: str,
        n_devices: int = 1) -> float:
    """Model-flops utilization: model flops of one step over the hardware
    flops ``n_devices`` chips of ``device_kind`` could have delivered in its
    wall time — the telemetry layer (obs/metrics.py) reports this against
    the 6ND numerator above.  An unknown device kind raises."""
    peak = peak_of(device_kind)["flops"]
    if step_time_s <= 0 or n_devices <= 0:
        return 0.0
    return flops_per_step / (step_time_s * n_devices * peak)
