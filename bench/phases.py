"""Device time of the train step by phase, read from the program's scopes.

The program names the phases of its train step with ``jax.named_scope``:
``fwd``, ``bwd``, ``zero_gather``, ``zero_reduce`` and ``optimizer``.  Every
HLO instruction traced under one carries it in its ``op_name`` metadata,
fusions and Pallas custom-calls included.  ``classes`` reads the compiled
step's optimized HLO text (``compiled.as_text()``) into {instruction name:
class}; ``split`` sums the traced window's device time by class.

The class of an instruction, from the innermost phase in its ``op_name``
(a transformation wraps the scope it applies to, as in
``transpose(jvp(zero_gather))``):

- ``zero_gather`` or ``zero_reduce``: zero_exchange (a ``transpose(`` under
  ``zero_gather``, standard accumulation's reduce, too);
- ``optimizer``: optimizer;
- ``fwd`` or ``bwd`` inside ``jax.checkpoint``'s recomputation
  (``rematted_computation``): recompute;
- ``fwd`` or ``bwd`` with a ``transpose(``: backward;
- ``bwd`` with a ``jvp(`` below it (``jax.vjp``'s forward, run again inside
  the backward): recompute;
- ``bwd`` otherwise: backward (the gradient sums, which XLA fuses into the
  weight-gradient matmuls as their root, and the backward scan's own work);
- ``fwd`` otherwise: forward;
- no phase: unattributed.

The benchmark keeps its own copy of the phase names: it also reads a
program that lacks them, and then finds nothing (each reader returns None).
"""
from __future__ import annotations

import collections
import re
import sys

PHASES = ("fwd", "bwd", "zero_gather", "zero_reduce", "optimizer")
CLASSES = ("forward", "recompute", "backward", "zero_exchange", "optimizer")
UNATTRIBUTED = "unattributed"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=(]+)\s+=\s+(.*)$")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=(%?[\w.\-]+|\{[^}]*\})")
_REF = re.compile(r"%([\w.\-]+)")
_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def scope(component: str) -> str:
    """The scope name of one ``op_name`` component, unwrapped."""
    while (m := _WRAPPED.match(component)) is not None:
        component = m.group(1)
    return component


def classify(op_name: str) -> str:
    """The class of an ``op_name`` by the rule above."""
    parts = op_name.split("/")
    inner = max((i for i, p in enumerate(parts) if scope(p) in PHASES),
                default=None)
    if inner is None:
        return UNATTRIBUTED
    p = scope(parts[inner])
    if p in ("zero_gather", "zero_reduce"):
        return "zero_exchange"
    if p == "optimizer":
        return "optimizer"
    if "rematted_computation" in parts:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    if p == "bwd":
        return "recompute" if "jvp(" in "/".join(parts[inner:]) \
            else "backward"
    return "forward"


def classes(hlo_text: str) -> dict:
    """{instruction name: class} of every instruction in an HLO module's
    text, under the names the trace gives them (with their ``.N``).

    An instruction whose ``op_name`` names no phase, being one XLA added,
    takes the class most instructions of the computations it calls have (a
    fusion's), else that of its first operand that has one, else the one
    most of its users have.  XLA prints a computation after those it calls
    and an instruction after its operands."""
    out, members, args = {}, collections.defaultdict(list), {}
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            if c is not None:
                comp = c.group(1).lstrip("%")
            continue
        name, rest = m.group(1).lstrip("%"), m.group(2)
        op = _OP_NAME.search(rest)
        cls = classify(op.group(1)) if op else UNATTRIBUTED
        called = _CALLS.search(rest)
        if cls == UNATTRIBUTED and called:
            cls = _most(out[i] for c in _REF.findall(called.group(1))
                        for i in members.get(c, ()))
        args[name] = _REF.findall(rest.split(", metadata=", 1)[0])
        if cls == UNATTRIBUTED:
            cls = next((out[r] for r in args[name]
                        if out.get(r, UNATTRIBUTED) != UNATTRIBUTED),
                       UNATTRIBUTED)
        out[name] = cls
        members[comp].append(name)
    users = collections.defaultdict(list)
    for name, refs in args.items():
        for r in refs:
            users[r].append(name)
    for name in reversed(list(out)):
        if out[name] == UNATTRIBUTED:
            out[name] = _most(out[u] for u in users[name])
    return out


def _most(found) -> str:
    votes = collections.Counter(c for c in found if c != UNATTRIBUTED)
    return votes.most_common(1)[0][0] if votes else UNATTRIBUTED


def instruction(event: str) -> str:
    """The instruction name of a trace event, with its ``.N``: the trace
    names an op by its HLO text, ``%fusion.18 = bf16[...] fusion(...)``."""
    return event.split(" = ", 1)[0].strip().lstrip("%")


def split(trace, hlo_text: str) -> dict:
    """Device seconds of the traced window by class, averaged over the
    devices: {class: seconds} for ``CLASSES`` and ``UNATTRIBUTED``, which
    sum to the time of every op (``tracing.CONTAINERS`` left out), with
    ``unattributed_ops`` ({instruction name without ``.N``: seconds}) and
    ``calls``, the custom-calls (Pallas kernels) counted by (name, class)."""
    from bench import tracing
    cls = classes(hlo_text)
    out = dict.fromkeys((*CLASSES, UNATTRIBUTED), 0.0)
    out["unattributed_ops"] = collections.Counter()
    out["calls"] = collections.Counter()
    n = len(trace.devices)
    for d in trace.devices:
        for ev, s, e in tracing.clip_events(d["ops"], trace.lo, trace.hi):
            c = cls.get(instruction(ev), UNATTRIBUTED)
            sec = (e - s) * 1e-9 / n
            out[c] += sec
            if c == UNATTRIBUTED:
                out["unattributed_ops"][tracing.op_name(ev)] += sec
            if " custom-call(" in ev:
                out["calls"][tracing.op_name(ev), c] += 1
    return out


def step_hlo_text(ctx) -> str | None:
    """The optimized HLO text of the executable the traced window ran:
    ``ctx.hlo_text`` where the run hands it over, else the text of the live
    compiled executable (``jax.stages.Compiled``) that names the most of
    the trace's ops.  The benchmark's run holds the executable it drove
    while the readers run."""
    text = getattr(ctx, "hlo_text", None)
    if text:
        return text
    import gc

    import jax
    names = {instruction(ev) for d in ctx.trace.devices
             for ev, _, _ in d["ops"]}
    best, cover = None, 0
    for obj in gc.get_objects():
        if isinstance(obj, jax.stages.Compiled):
            try:
                t = obj.as_text()
            except jax.errors.JaxRuntimeError as e:
                log(f"phases: an executable gives no text: {e}")
                continue
            k = sum(1 for line in t.splitlines()
                    if (m := _INSTR.match(line))
                    and m.group(1).lstrip("%") in names)
            if k > cover:
                best, cover = t, k
    return best


def of(ctx) -> dict | None:
    """``split`` of the run's traced window, computed once per context; None
    where no executable's text names the trace's ops or none carries a
    phase.  Says on stderr what it found: the unattributed share with its
    five longest instructions, and the custom-calls by class."""
    if "_phases" not in vars(ctx):
        ctx._phases = _of(ctx)
    return ctx._phases


def _of(ctx) -> dict | None:
    text = step_hlo_text(ctx)
    if text is None:
        log("phases: no compiled step names the trace's ops")
        return None
    s = split(ctx.trace, text)
    sample = next((ev for d in ctx.trace.devices for ev, _, _ in d["ops"]), "")
    log(f"phases: the trace names an op as {sample[:120]!r}")
    total = sum(s[c] for c in (*CLASSES, UNATTRIBUTED))
    if s[UNATTRIBUTED] >= total:
        log("phases: no op in the trace carries a phase")
        return None
    top = ", ".join(f"{k} {v * 1e3:.3f} ms"
                    for k, v in s["unattributed_ops"].most_common(5))
    log(f"phases: unattributed {100 * s[UNATTRIBUTED] / total:.4f} % of "
        f"{total:.6f} s op time ({top or 'none'})")
    log("phases: custom-calls by class: " + ", ".join(
        f"{k}/{c} {v}" for (k, c), v in sorted(s["calls"].items())))
    return s


def ms_per_step(ctx, cls: str, metric: str) -> float | None:
    """Device ms per traced step of one class, averaged over the chips;
    None, said on stderr, where the class has no op in the trace."""
    s = of(ctx)
    if s is None or s[cls] == 0:
        log(f"{metric}: no {cls} op in the trace")
        return None
    return 1e3 * s[cls] / ctx.steps


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
