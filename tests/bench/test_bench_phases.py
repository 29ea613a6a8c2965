"""The split of the step's device time by phase (``bench/phases.py``): its
rule on hand-written ``op_name``s and HLO, its sums over a synthetic trace,
its readers, and the program's own compiled steps, which it has to
attribute whole."""
import gc
import re

import jax
import jax.numpy as jnp
import pytest

from bench import phases, spec, tracing
from bench_tiny import LAYERED, tiny_cell


# -- the rule ---------------------------------------------------------------
@pytest.mark.parametrize("op_name,cls", [
    # layered accumulation
    ("jit(step)/fwd/while/body/closed_call/bsd,df->bsf/dot_general", "forward"),
    ("jit(step)/fwd/bwd/while/body/jvp(bsd,df->bsf)/dot_general", "recompute"),
    ("jit(step)/fwd/bwd/while/body/jvp()/cond/jit(flash_attention_fwd)/"
     "pallas_call", "recompute"),
    ("jit(step)/fwd/bwd/while/body/transpose(jvp(bsd,df->bsf))/dot_general",
     "backward"),
    ("jit(step)/fwd/bwd/while/body/closed_call/add", "backward"),
    ("jit(step)/fwd/while/body/jvp(bsd,vd->bsv)/dot_general", "forward"),
    ("jit(step)/fwd/while/body/transpose(jvp(bsd,vd->bsv))/dot_general",
     "backward"),
    # nesting: the innermost phase wins
    ("jit(step)/fwd/bwd/while/body/zero_gather/convert_element_type",
     "zero_exchange"),
    ("jit(step)/fwd/bwd/while/body/zero_reduce/reduce_scatter",
     "zero_exchange"),
    ("jit(step)/fwd/bwd/while/body/optimizer/jit(adamw_update)/pallas_call",
     "optimizer"),
    ("jit(step)/optimizer/sqrt", "optimizer"),
    # standard accumulation under jax.grad: the transformation wraps a scope
    ("jit(step)/fwd/while/body/jvp(fwd)/dot_general", "forward"),
    ("jit(step)/fwd/while/body/transpose(jvp(fwd))/dot_general", "backward"),
    ("jit(step)/fwd/while/body/transpose(jvp(fwd))/rematted_computation/"
     "dot_general", "recompute"),
    ("jit(step)/fwd/while/body/transpose(jvp(zero_gather))/reduce_scatter",
     "zero_exchange"),
    ("jit(step)/fwd/while/body/transpose(jvp(fwd))/zero_gather/all_gather",
     "zero_exchange"),
    # no phase, or a name that only looks like one
    ("jit(step)/while/body/add", "unattributed"),
    ("jit(step)/jvp(fwd_layer)/forward/dot_general", "unattributed"),
])
def test_rule(op_name, cls):
    assert phases.classify(op_name) == cls


HLO = """\
HloModule jit_step

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.3 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/fwd/bwd/transpose(jvp())/mul"}
}

ENTRY %main.9 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %convolution.4 = f32[8]{0} convolution(%p.1, %p.1), metadata={op_name="jit(step)/fwd/while/body/dot_general" source_file="x.py" source_line=3}
  %fusion.2 = f32[8]{0} fusion(%convolution.4), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/fwd/bwd/closed_call"}
  %flash_attention_fwd.18 = (f32[8]{0}) custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/fwd/bwd/jvp()/jit(flash_attention_fwd)/pallas_call"}
  %all-gather-start.5 = f32[8]{0} all-gather-start(%p.1), metadata={op_name="jit(step)/fwd/bwd/zero_gather/all_gather"}
  %copy.7 = f32[8]{0} copy(%convolution.4)
  %adamw_update.3 = f32[8]{0} custom-call(%p.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/optimizer/jit(adamw_update)/pallas_call"}
  %add.8 = f32[8]{0} add(%p.1, %p.1), metadata={op_name="jit(step)/add"}
  ROOT %while.2 = f32[8]{0} while(%add.8), condition=%c, body=%b
}
"""


def test_classes_of_a_module():
    c = phases.classes(HLO)
    assert c["convolution.4"] == "forward"
    # a fusion whose own op_name names no phase takes its body's
    assert c["fusion.2"] == "backward"
    assert c["flash_attention_fwd.18"] == "recompute"
    assert c["all-gather-start.5"] == "zero_exchange"
    # a copy XLA added takes its operand's class
    assert c["copy.7"] == "forward"
    assert c["adamw_update.3"] == "optimizer"
    assert c["add.8"] == "unattributed"


def _ev(name):
    """A device op named as the trace names it: by its HLO text."""
    line = next(ln for ln in HLO.splitlines() if f"%{name} = " in ln)
    return line.strip().removeprefix("ROOT ")


def _trace():
    """Two devices, window [0, 1000] ns, every op of ``HLO`` but the loop."""
    ops0 = [[_ev("convolution.4"), 0, 100], [_ev("fusion.2"), 100, 300],
            [_ev("flash_attention_fwd.18"), 300, 350],
            [_ev("all-gather-start.5"), 350, 370], [_ev("copy.7"), 370, 380],
            [_ev("adamw_update.3"), 400, 500], [_ev("add.8"), 500, 600],
            [_ev("while.2"), 0, 1000], [_ev("adamw_update.3"), 990, 1100]]
    ops1 = [[_ev("convolution.4"), 0, 300], [_ev("adamw_update.3"), 300, 400]]
    return tracing.Trace({"devices": [{"name": "/device:TPU:0", "ops": ops0},
                                      {"name": "/device:TPU:1", "ops": ops1}],
                          "host": [["window", 0, 1000]]})


@pytest.mark.parametrize("cls,ns", [
    ("forward", (100 + 10 + 300) / 2),
    ("backward", 200 / 2),
    ("recompute", 50 / 2),
    ("zero_exchange", 20 / 2),
    ("optimizer", (100 + 10 + 100) / 2),       # the last call clipped
    ("unattributed", 100 / 2),
])
def test_split_by_class(cls, ns):
    assert phases.split(_trace(), HLO)[cls] == pytest.approx(ns * 1e-9)


def test_split_sums_to_the_op_time():
    tr = _trace()
    ops = sum(end - start for d in tr.devices
              for _, start, end in tracing.clip_events(d["ops"], tr.lo, tr.hi))
    assert ops == 590 + 400                     # the while spans its body
    s = phases.split(tr, HLO)
    assert sum(s[c] for c in (*phases.CLASSES, phases.UNATTRIBUTED)) == \
        pytest.approx(ops / 2 * 1e-9)


def test_split_counts_the_kernels_by_class():
    calls = phases.split(_trace(), HLO)["calls"]
    assert calls == {("flash_attention_fwd", "recompute"): 1,
                     ("adamw_update", "optimizer"): 3}


def _ctx(hlo_text=None, steps=2):
    from bench.run import MetricContext
    kw = {"hlo_text": hlo_text} if hlo_text is not None else {}
    return MetricContext(cell=spec.load_cell(LAYERED[0]), trace=_trace(),
                         steps=steps, chips=2, **kw)


READERS = {"fwd_ms_per_step": "forward", "recompute_ms_per_step": "recompute",
           "bwd_ms_per_step": "backward",
           "zero_exchange_ms_per_step": "zero_exchange",
           "optimizer_ms_per_step": "optimizer"}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_gives_ms_per_step(metric):
    read = spec.metric_module(metric).read
    want = phases.split(_trace(), HLO)[READERS[metric]] * 1e3 / 2
    assert read(_ctx(HLO)) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_finds_nothing_without_phases(metric, capsys):
    # a program that names no phase (the metadata taken out)
    bare = re.sub(r", metadata=\{[^}]*\}", "", HLO)
    assert spec.metric_module(metric).read(_ctx(bare)) is None
    assert metric in capsys.readouterr().err


def test_reader_finds_the_live_executable():
    # without ctx.hlo_text the readers take the text of the live executable
    # that names the trace's ops
    compiled = jax.jit(lambda x: x * 2).lower(jnp.ones(4)).compile()
    text = compiled.as_text()
    name = next(m.group(1).lstrip("%") for ln in text.splitlines()
                if (m := phases._INSTR.match(ln)) and "multiply" in ln)
    tr = tracing.Trace({"devices": [{"name": "/device:TPU:0",
                                     "ops": [[f"%{name} = f32[4]", 0, 10]]}],
                        "host": []})
    from bench.run import MetricContext
    gc.collect()
    assert phases.step_hlo_text(MetricContext(trace=tr)) == text
    assert phases.step_hlo_text(_ctx(HLO)) == HLO


def test_phase_names_are_the_programs():
    from repro.obs import trace as obs_trace
    assert phases.PHASES == obs_trace.PHASES
    with pytest.raises(AssertionError):
        obs_trace.phase("forward")


# -- the program's compiled steps -------------------------------------------
def _instructions(text):
    """[(name, opcode, op_name or None)] of an HLO module's text."""
    out = []
    for line in text.splitlines():
        m = phases._INSTR.match(line)
        if m is None:
            continue
        head = m.group(2).split(", metadata=", 1)[0]
        op = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + head)
        meta = phases._OP_NAME.search(m.group(2))
        out.append((m.group(1).lstrip("%"), op.group(1) if op else None,
                    meta.group(1) if meta else None))
    return out


def _tiny_step_text(method):
    import dataclasses

    from bench import run as bench_run
    from bench import traffic, weights
    cell = tiny_cell(*LAYERED)
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic, method=method))
    drv = bench_run.make_driver(cell, jax.devices()[:1])
    state = drv.init_state(weights.key_of(1, 0))
    batch = drv.place(traffic.make_batch(cell.traffic,
                                         cell.config["vocab_size"], 1, 1))
    return drv.compile(*state, batch).as_text()


@pytest.fixture(scope="module")
def layered_text():
    return _tiny_step_text("layered")


COMPUTE = ("dot", "convolution", "custom-call", "fusion")


@pytest.mark.parametrize("method", ["layered", "standard"])
def test_tiny_step_is_attributed_whole(method, layered_text):
    text = layered_text if method == "layered" else _tiny_step_text(method)
    cls = phases.classes(text)
    bare = [(n, op) for n, op, _ in _instructions(text)
            if op in COMPUTE and cls[n] == phases.UNATTRIBUTED]
    assert bare == []
    found = {cls[n] for n, op, _ in _instructions(text) if op in COMPUTE}
    assert found == set(phases.CLASSES)


def test_tiny_layered_step_runs_the_layer_twice(layered_text):
    # the FFN's matmuls, by their einsum: the up projection runs in the
    # forward and again in the recomputed forward; the down projection's
    # output feeds nothing the backward needs, so XLA drops its second run;
    # both have transposes in the backward
    cls = phases.classes(layered_text)
    seen = {"bsd,df->bsf": set(), "bsf,fd->bsd": set()}
    for n, op, op_name in _instructions(layered_text):
        for eq in seen:
            if op == "dot" and op_name and eq in op_name:
                seen[eq].add((cls[n], "transpose(" in op_name))
    assert seen["bsd,df->bsf"] == {("forward", False), ("recompute", False),
                                   ("backward", True)}
    assert seen["bsf,fd->bsd"] == {("forward", False), ("backward", True)}
    upd = {cls[n] for n, _, op_name in _instructions(layered_text)
           if op_name and "adamw_update" in op_name}
    assert upd == {"optimizer"}


def test_pipeline_exchange_lands_in_zero_exchange():
    from repro import compat
    from repro.core import stepfn
    from repro.core.schedules import PipeSpec
    from repro.models.common import ModelConfig
    from repro.optim.adam import AdamConfig, adam_init
    cfg = ModelConfig(name="p", arch_type="dense", num_layers=4, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                      dtype="float32", param_dtype="float32")
    M = 4
    mesh = compat.make_mesh((2, 2), ("stage", "data"))
    spec_ = PipeSpec(n_stages=2, layers_per_stage=2, n_microbatches=M,
                     schedule="modular")
    step = stepfn.build_pipeline_train_step(cfg, mesh, spec_,
                                            AdamConfig(lr=1e-3), donate=False)
    storage = stepfn.init_pipeline_storage(cfg, mesh, jax.random.PRNGKey(0),
                                           spec_, partitioned=True)
    toks = jax.random.randint(jax.random.PRNGKey(1), (M, 4, 16), 0, 64)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, -1),
             "mask": jnp.ones_like(toks)}
    text = step.lower(storage, adam_init(storage), batch).compile().as_text()
    cls = phases.classes(text)
    coll = [(n, op, cls[n]) for n, op, _ in _instructions(text)
            if op and op.split("-start")[0] in ("all-gather", "reduce-scatter")]
    assert {op.split("-start")[0] for _, op, _ in coll} == \
        {"all-gather", "reduce-scatter"}
    assert [c for c in coll if c[2] != "zero_exchange"] == []
    assert {cls[n] for n, _, op_name in _instructions(text)
            if op_name and "adamw_update" in op_name} == {"optimizer"}
