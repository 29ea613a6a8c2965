"""Jit-safe metric registry + host-side JSONL sink.

Device side: a ``Registry`` names counters / gauges / histograms once, then
``init()`` builds a zero metric pytree and ``update()`` folds new values in —
all fixed-shape ``jnp`` ops, so a step function can carry the tree through
``jax.jit``/``lax.scan`` without retracing (asserted by the compile-count
probe in tests/test_obs.py).

Host side: ``MetricsSink`` streams one JSON object per line and flushes every
line, so the metrics file survives a crashed step; ``close()`` (or the
context manager, or a ``finally:``) appends a summary record aggregated from
everything logged so far.  ``mfu_estimate`` cross-checks throughput against
``core/roofline.py``'s 6ND flops model and device peak.
"""
from __future__ import annotations

import dataclasses
import json
import math
import time
from typing import Any

PyTree = Any

_KINDS = ("counter", "gauge", "histogram")


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    name: str
    kind: str                        # counter | gauge | histogram
    buckets: tuple = ()              # histogram bucket upper edges


class Registry:
    """Declares metrics once; builds/updates fixed-shape device pytrees."""

    def __init__(self):
        self._specs: dict[str, MetricSpec] = {}

    def _add(self, name: str, kind: str, buckets=()):
        assert kind in _KINDS, kind
        if name in self._specs:
            assert self._specs[name].kind == kind, (name, kind)
            return name
        self._specs[name] = MetricSpec(name, kind, tuple(buckets))
        return name

    def counter(self, name: str) -> str:
        """Monotone sum: ``update`` adds, ``merge`` adds."""
        return self._add(name, "counter")

    def gauge(self, name: str) -> str:
        """Last-value wins: ``update`` overwrites, ``merge`` takes the right."""
        return self._add(name, "gauge")

    def histogram(self, name: str, buckets) -> str:
        """Bucketized counts: ``update`` increments the bucket of each value
        (edges are upper bounds; one overflow bucket)."""
        assert len(buckets) > 0
        return self._add(name, "histogram", buckets)

    @property
    def specs(self) -> dict[str, MetricSpec]:
        return dict(self._specs)

    # -- device-side ------------------------------------------------------
    def init(self) -> PyTree:
        import jax.numpy as jnp
        tree = {}
        for name, sp in self._specs.items():
            if sp.kind == "histogram":
                tree[name] = jnp.zeros((len(sp.buckets) + 1,), jnp.int32)
            else:
                tree[name] = jnp.zeros((), jnp.float32)
        return tree

    def update(self, tree: PyTree, **values) -> PyTree:
        """Fold new values in (traceable; shapes never change)."""
        import jax.numpy as jnp
        out = dict(tree)
        for name, val in values.items():
            sp = self._specs[name]
            if sp.kind == "counter":
                out[name] = out[name] + jnp.asarray(val, jnp.float32)
            elif sp.kind == "gauge":
                out[name] = jnp.asarray(val, jnp.float32)
            else:
                edges = jnp.asarray(sp.buckets, jnp.float32)
                vals = jnp.atleast_1d(jnp.asarray(val, jnp.float32))
                idx = jnp.searchsorted(edges, vals)   # == len(edges): overflow
                out[name] = out[name].at[idx].add(1)
        return out

    def merge(self, a: PyTree, b: PyTree) -> PyTree:
        out = {}
        for name, sp in self._specs.items():
            out[name] = b[name] if sp.kind == "gauge" else a[name] + b[name]
        return out

    # -- host-side --------------------------------------------------------
    def to_host(self, tree: PyTree) -> dict:
        """Device tree -> plain python (floats / int lists), for the sink."""
        import jax
        host = jax.device_get(tree)
        out = {}
        for name, sp in self._specs.items():
            v = host[name]
            out[name] = ([int(x) for x in v] if sp.kind == "histogram"
                         else float(v))
        return out


def resilience_registry() -> Registry:
    """The resilience layer's metric names (repro/resilience/supervisor.py):
    restart/shrink/anomaly counters, a lost-steps gauge-per-event folded as
    a counter total, and the recovery-time span in seconds (gauge: last
    recovery; the JSONL events carry every span).  Declared here so the
    telemetry surface is one registry away from dashboards, like the
    training metrics."""
    reg = Registry()
    reg.counter("restarts")
    reg.counter("lost_steps")
    reg.counter("skipped_steps")
    reg.counter("shrinks")
    reg.gauge("recovery_time_s")
    return reg


# ---------------------------------------------------------------------------
# Derived estimates
# ---------------------------------------------------------------------------
def mfu_estimate(cfg, *, global_batch: int, seq_len: int, step_time_s: float,
                 n_devices: int = 1,
                 device_kind: str | None = None) -> float | None:
    """Model-flops utilization of one optimizer step: the roofline 6ND
    training flops over ``step_time * devices * peak`` (core/roofline.py is
    the single source for both the numerator model and the device peak).

    ``device_kind`` defaults to the first JAX device's.  A device with no
    published peak (the CPU among them) has no MFU: the result is None."""
    import jax

    from repro.core import roofline
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    if device_kind not in roofline.PEAKS:
        return None
    if step_time_s <= 0:
        return 0.0
    flops = roofline.model_flops_train(cfg, global_batch, seq_len)
    return roofline.mfu(flops, step_time_s, n_devices=n_devices,
                        device_kind=device_kind)


def percentiles(values, qs=(50, 95, 99)) -> dict:
    """``{"p50": ..., }`` over a value list (empty -> {})."""
    vals = sorted(float(v) for v in values)
    if not vals:
        return {}
    out = {}
    for q in qs:
        # nearest-rank on the sorted list (no numpy needed host-side)
        k = max(0, min(len(vals) - 1, math.ceil(q / 100 * len(vals)) - 1))
        out[f"p{q}"] = vals[k]
    return out


# ---------------------------------------------------------------------------
# Host-side sink
# ---------------------------------------------------------------------------
class MetricsSink:
    """Streams metric records to JSONL and aggregates a summary.

    Every ``log()`` writes one line and flushes it — a crashed step loses at
    most the record being formatted, never the file.  ``close()`` appends an
    ``{"event": "summary", ...}`` line; use as a context manager (or call
    ``close`` from ``finally:``) so the summary survives exceptions too.
    ``path=None`` keeps the aggregation (summary still available) without a
    file.
    """

    def __init__(self, path: str | None = None, *, meta: dict | None = None,
                 clock=time.time):
        self.path = path
        self._clock = clock
        self._fh = open(path, "w") if path else None
        self._agg: dict[str, dict] = {}
        self._n = 0
        self._closed = False
        if meta:
            self._write(dict({"event": "meta"}, **meta))

    def _write(self, record: dict) -> None:
        if self._fh is not None:
            self._fh.write(json.dumps(record) + "\n")
            self._fh.flush()

    def log(self, record: dict | None = None, *, event: str = "step",
            **kw) -> dict:
        """Write one record (dict and/or keywords) and fold numerics into
        the running summary aggregates."""
        rec = dict(record or {}, **kw)
        self._n += 1
        for k, v in rec.items():
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            a = self._agg.setdefault(
                k, {"count": 0, "sum": 0.0, "min": v, "max": v, "last": v})
            a["count"] += 1
            a["sum"] += v
            a["min"] = min(a["min"], v)
            a["max"] = max(a["max"], v)
            a["last"] = v
        self._write(dict({"event": event, "time": self._clock()}, **rec))
        return rec

    def summary(self) -> dict:
        out: dict[str, Any] = {"records": self._n}
        for k, a in self._agg.items():
            out[k] = {"last": a["last"], "mean": a["sum"] / a["count"],
                      "min": a["min"], "max": a["max"]}
        return out

    def close(self, extra: dict | None = None) -> dict:
        """Write the summary line (idempotent) and close the file."""
        s = self.summary()
        if not self._closed:
            self._closed = True
            self._write({"event": "summary", **s, **(extra or {})})
            if self._fh is not None:
                self._fh.close()
                self._fh = None
        return s

    def __enter__(self) -> "MetricsSink":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def read_jsonl(path: str) -> list[dict]:
    """Load a sink's output (skips a torn final line from a hard crash)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return out
