"""Span tracing with a Chrome-trace (Perfetto-loadable) exporter, and the
train step's phase scopes.

``phase(name)`` names one of ``PHASES`` inside a jitted program: every HLO
instruction traced under it carries the name in its ``op_name`` metadata
(fusions and Pallas custom-calls included), so a device trace's ops can be
told apart by phase.  It changes metadata only, never the computation.

``Tracer`` records complete events (``ph: "X"``) under (pid, tid) lanes and
serializes the standard ``{"traceEvents": [...]}`` JSON object form, which
chrome://tracing and ui.perfetto.dev load directly.

``add_timeline`` is the shared writer for *tick timelines* — the
``(stage, kind, chunk, microbatch, start, end)`` tuples produced by BOTH the
planner simulator (``simulate(..., record_timeline=True)``,
``TickTable.timeline()``) and the segmented executor measurement below — so
predicted and measured schedules open side by side in one Perfetto view
(one process per timeline, one thread per stage).

``measure_tick_timeline`` drives a ``stepfn.build_pipeline_tick_profiler``
pass: every tick of the table runs as its own dispatch, host-timed with
``block_until_ready`` barriers, yielding a measured timeline in the shared
schema (``obs/drift.py`` aligns it against the plan's).
"""
from __future__ import annotations

import contextlib
import json
import time

_KIND_NAMES = {0: None, 1: "F", 2: "B", 3: "Bd", 4: "Bw"}

# The train step's phases, innermost scope wins: the forward ("fwd"), the
# backward with its recomputed forward ("bwd"), the ZeRO weight gather and
# gradient reduction, and the optimizer update.  Under ``jax.vjp`` the
# recomputed forward reads ``jvp(...)`` in ``op_name`` and the backward
# ``transpose(jvp(...))``.
PHASES = ("fwd", "bwd", "zero_gather", "zero_reduce", "optimizer")


def phase(name: str):
    """``jax.named_scope(name)`` for one of ``PHASES``: a context manager, or
    a decorator that opens a scope of its own on every call."""
    assert name in PHASES, (name, PHASES)
    return _named_scope(name)


@contextlib.contextmanager
def _named_scope(name: str):
    import jax
    with jax.named_scope(name):
        yield


class Tracer:
    """Collects Chrome-trace events; wall clock in µs from construction.

    ``span`` also opens a ``jax.profiler.TraceAnnotation`` of the same name,
    so under a profiler capture the span sits on the device trace's clock."""

    def __init__(self, *, clock=time.perf_counter):
        self._clock = clock
        self._t0 = clock()
        self.events: list[dict] = []
        self._named: set = set()

    def now_us(self) -> float:
        return (self._clock() - self._t0) * 1e6

    # -- events -----------------------------------------------------------
    def complete(self, name: str, *, ts_us: float, dur_us: float,
                 cat: str = "phase", pid: int = 0, tid: int = 0,
                 args: dict | None = None) -> None:
        ev = {"name": name, "cat": cat, "ph": "X", "ts": ts_us,
              "dur": max(dur_us, 0.0), "pid": pid, "tid": tid}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def instant(self, name: str, *, cat: str = "phase", pid: int = 0,
                tid: int = 0, args: dict | None = None) -> None:
        ev = {"name": name, "cat": cat, "ph": "i", "ts": self.now_us(),
              "s": "t", "pid": pid, "tid": tid}
        if args:
            ev["args"] = args
        self.events.append(ev)

    @contextlib.contextmanager
    def span(self, name: str, *, cat: str = "phase", pid: int = 0,
             tid: int = 0, **args):
        import jax
        t0 = self.now_us()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield self
        finally:
            self.complete(name, ts_us=t0, dur_us=self.now_us() - t0, cat=cat,
                          pid=pid, tid=tid, args=args or None)

    # -- metadata ---------------------------------------------------------
    def name_process(self, pid: int, name: str) -> None:
        if ("p", pid) in self._named:
            return
        self._named.add(("p", pid))
        self.events.append({"name": "process_name", "ph": "M", "pid": pid,
                            "tid": 0, "args": {"name": name}})

    def name_thread(self, pid: int, tid: int, name: str) -> None:
        if ("t", pid, tid) in self._named:
            return
        self._named.add(("t", pid, tid))
        self.events.append({"name": "thread_name", "ph": "M", "pid": pid,
                            "tid": tid, "args": {"name": name}})

    # -- export -----------------------------------------------------------
    def to_chrome(self) -> dict:
        return {"traceEvents": list(self.events), "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path


# ---------------------------------------------------------------------------
# Chrome-trace JSON: load / validate / timeline round-trip
# ---------------------------------------------------------------------------
def load_chrome(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def validate_chrome(doc) -> list[str]:
    """Schema problems of a Chrome-trace JSON-object-format document
    (empty list == loadable by chrome://tracing / Perfetto)."""
    problems = []
    if not isinstance(doc, dict) or not isinstance(doc.get("traceEvents"),
                                                   list):
        return ["document must be an object with a 'traceEvents' list"]
    for i, ev in enumerate(doc["traceEvents"]):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not an object")
            continue
        ph = ev.get("ph")
        if not isinstance(ev.get("name"), str):
            problems.append(f"event {i}: missing name")
        if ph not in ("X", "B", "E", "i", "I", "M", "C"):
            problems.append(f"event {i}: unknown ph {ph!r}")
            continue
        if ph == "M":
            continue
        if not isinstance(ev.get("ts"), (int, float)):
            problems.append(f"event {i}: missing ts")
        if ph == "X" and (not isinstance(ev.get("dur"), (int, float))
                          or ev["dur"] < 0):
            problems.append(f"event {i}: complete event needs dur >= 0")
    return problems


def add_timeline(tracer: Tracer, events, *, pid: int, name: str,
                 scale_us: float = 1.0, cat: str = "tick") -> None:
    """Shared timeline writer: render ``(stage, kind, chunk, microbatch,
    start, end)`` events (simulator or measured; ``kind`` a TICK_* code or
    "F"/"B" string, times in arbitrary units scaled by ``scale_us``) as
    complete events — one process per timeline, one thread per stage."""
    tracer.name_process(pid, name)
    for (s, kind, v, mb, start, end) in events:
        k = _KIND_NAMES.get(kind, kind) if isinstance(kind, int) else kind
        if k is None:
            continue
        tracer.name_thread(pid, int(s), f"stage {int(s)}")
        tracer.complete(f"{k} v{int(v)} mb{int(mb)}",
                        ts_us=float(start) * scale_us,
                        dur_us=(float(end) - float(start)) * scale_us,
                        cat=cat, pid=pid, tid=int(s),
                        args={"stage": int(s), "kind": k, "chunk": int(v),
                              "microbatch": int(mb)})


def timeline_from_chrome(doc: dict, *, pid: int) -> list:
    """Inverse of ``add_timeline`` for the given pid (times back in µs):
    the round-trip the schema tests pin."""
    out = []
    for ev in doc["traceEvents"]:
        if ev.get("ph") != "X" or ev.get("pid") != pid:
            continue
        a = ev.get("args", {})
        if not {"stage", "kind", "chunk", "microbatch"} <= set(a):
            continue
        out.append((a["stage"], a["kind"], a["chunk"], a["microbatch"],
                    ev["ts"], ev["ts"] + ev["dur"]))
    return out


# ---------------------------------------------------------------------------
# Measured per-tick timeline (segmented executor)
# ---------------------------------------------------------------------------
def measure_tick_timeline(prof, storage, batch, *, warmup: int = 1,
                          tracer: Tracer | None = None, pid: int = 1,
                          name: str = "measured ticks",
                          clock=time.perf_counter) -> list:
    """Run one profiled pass of ``prof`` (a
    ``stepfn.build_pipeline_tick_profiler`` object) and return the measured
    tick timeline: ``(stage, kind, chunk, microbatch, start_s, end_s)`` for
    every non-idle table unit, host-timed around each per-tick dispatch.

    Segmented execution is lockstep (one dispatch per tick, barriered), so
    every stage active in tick t shares that tick's measured interval — the
    same rendering the table's own unit-tick timeline uses, which is what
    makes the two directly alignable in ``obs/drift.py``.  ``warmup`` full
    passes absorb compilation before the timed pass.
    """
    import jax
    import numpy as np

    table = prof.table
    rows_np = prof.rows_np
    gather_spans = []

    def one_pass(timed: bool):
        events = []
        state = prof.init(storage, batch)
        jax.block_until_ready(state)
        t_origin = clock()
        for (t0, t1, chunks) in prof.segments:
            for v2 in chunks:
                g0 = clock()
                state = prof.gather(state, storage, np.int32(v2))
                jax.block_until_ready(state)
                if timed:
                    gather_spans.append((v2, g0 - t_origin,
                                         clock() - t_origin))
            for t in range(t0, t1):
                rows = {k: r[t] for k, r in rows_np.items()}
                s0 = clock()
                state = prof.tick(state, storage, batch, rows)
                jax.block_until_ready(state)
                s1 = clock()
                if not timed:
                    continue
                for s in range(table.n_stages):
                    k = _KIND_NAMES.get(table.kind[t][s])
                    if k is None:
                        continue
                    events.append((s, k, table.unit_v[t][s],
                                   table.unit_mb[t][s],
                                   s0 - t_origin, s1 - t_origin))
        return events, state

    for _ in range(max(warmup, 0)):
        one_pass(False)
    events, state = one_pass(True)
    prof.last_state = state            # for finish()/parity checks
    if tracer is not None:
        add_timeline(tracer, events, pid=pid, name=name, scale_us=1e6)
        for (v2, g0, g1) in gather_spans:
            tracer.name_thread(pid, -1, "zero gather")
            tracer.complete(f"gather v{v2}", ts_us=g0 * 1e6,
                            dur_us=(g1 - g0) * 1e6, cat="gather", pid=pid,
                            tid=-1, args={"chunk": int(v2)})
    return events
