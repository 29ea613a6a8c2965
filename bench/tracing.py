"""Profiler traces of the traced window, and their reduction to numbers.

``capture`` records a JAX profiler trace; ``load`` reads its ``.xplane.pb``
into a compact form that keeps what the reduction needs:

    {"devices": [{"name": "/device:TPU:0",
                  "ops": [[name, start_ns, end_ns], ...]},   # "XLA Ops"
                 ...],
     "host": [[name, start_ns, end_ns], ...]}  # the benchmark's own spans

Device and host events share the profiler's clock.  ``Trace`` reduces the
compact form: the union of busy intervals, idle share, kernel calls and
time by instruction name, the ops that took most time, and idle time by what
the host was doing.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re

HOST_SPANS = ("window", "make_batch", "dispatch", "await_step")
# control flow whose event spans the ops of its body: not an op of its own
CONTAINERS = ("while", "conditional", "call")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@contextlib.contextmanager
def capture(directory: str):
    import jax
    with jax.profiler.trace(directory):
        yield


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def load(directory: str) -> dict:
    """The newest ``.xplane.pb`` under ``directory``, in compact form."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return compact(ProfileData.from_file(max(files, key=os.path.getmtime)))


def compact(profile) -> dict:
    out = {"devices": [], "host": []}
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"name": plane.name, "ops": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] += [[e.name, e.start_ns, e.start_ns + e.duration_ns]
                                   for e in line.events]
            out["devices"].append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.start_ns + e.duration_ns]
                                for e in line.events if e.name in HOST_SPANS]
    out["devices"].sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    return out


def union(intervals) -> list:
    """Sorted, merged [start, end] intervals."""
    merged = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b) -> list:
    """a minus b, both merged."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


class Trace:
    """Reductions of one compact trace over the traced window.  A ``while``
    (or other ``CONTAINERS``) op is left out: its event spans its body's ops
    and any idle time between them."""

    def __init__(self, data: dict):
        self.data = data
        self.devices = [dict(d, ops=[o for o in d["ops"]
                                     if op_name(o[0]) not in CONTAINERS])
                        for d in data["devices"]]
        if not self.devices:
            raise ValueError("the trace holds no TPU device plane")
        win = [h for h in data["host"] if h[0] == "window"]
        if win:
            self.lo, self.hi = win[0][1], win[0][2]
        else:
            evs = [o for d in self.devices for o in d["ops"]]
            self.lo = min(o[1] for o in evs)
            self.hi = max(o[2] for o in evs)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy(self, dev) -> list:
        return union(clip([o[1:] for o in dev["ops"]], self.lo, self.hi))

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over devices."""
        return sum(length(self.busy(d)) for d in self.devices) \
            / len(self.devices) * 1e-9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def kernel_calls(self, names) -> dict | None:
        """{name: [calls, device seconds]} of the ops whose instruction name
        (``op_name``) is each of ``names``, summed over devices; None where
        any of them never ran."""
        out = {n: [0, 0.0] for n in names}
        for d in self.devices:
            for name, s, e in clip_events(d["ops"], self.lo, self.hi):
                k = out.get(op_name(name))
                if k is not None:
                    k[0] += 1
                    k[1] += (e - s) * 1e-9
        return out if all(out[n][0] > 0 for n in names) else None

    def top_ops(self, n: int = 10) -> list:
        """The device ops that took most time: [instruction name (``op_name``),
        seconds averaged over devices]."""
        tot: dict = {}
        for d in self.devices:
            for name, s, e in clip_events(d["ops"], self.lo, self.hi):
                k = op_name(name)
                tot[k] = tot.get(k, 0.0) + (e - s) * 1e-9 / len(self.devices)
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10) -> list:
        """Idle device time by what the host was doing: [host span, seconds
        averaged over devices]; a gap goes to the host span that overlaps
        it most, or to "other"."""
        host = [h for h in self.data["host"] if h[0] != "window"]
        tot: dict = {}
        for d in self.devices:
            gaps = subtract([[self.lo, self.hi]], self.busy(d))
            for s, e in gaps:
                best, over = "other", 0
                for name, hs, he in host:
                    o = min(e, he) - max(s, hs)
                    if o > over:
                        best, over = name, o
                tot[best] = tot.get(best, 0.0) + (e - s) * 1e-9 / len(self.devices)
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]


def op_name(event: str) -> str:
    """The HLO instruction name of a device op, without its ``%`` and
    trailing ``.<number>``.  The trace names an op by its HLO text, as in
    ``%flash_attention_fwd.18 = (bf16[...]) custom-call(...), ...``; a Pallas
    kernel's instruction takes the name of the jitted function that calls
    ``pallas_call``."""
    head = event.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def clip_events(events, lo, hi):
    return [[n, max(s, lo), min(e, hi)] for n, s, e in events
            if e > lo and s < hi]
