"""The reduction from a profiler trace to the per-layer numbers."""
import pytest

from bench import tracing


def test_union_subtract_length():
    u = tracing.union([[5, 9], [0, 2], [1, 3], [8, 10], [12, 12]])
    assert u == [[0, 3], [5, 10]]
    assert tracing.length(u) == 8
    assert tracing.subtract([[0, 10]], [[2, 3], [5, 7]]) == [[0, 2], [3, 5], [7, 10]]
    assert tracing.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]
    assert tracing.clip([[0, 4], [6, 9], [10, 12]], 2, 8) == [[2, 4], [6, 8]]


def _op(name, text=" = f32[8]{0} fusion(f32[8]{0} %p.1), kind=kLoop"):
    """A device op named as the trace names it: by its HLO text."""
    return f"%{name}{text}"


FWD = ' = (bf16[8,16,512,64]{3,2,1,0}, f32[8,16,512,1]{3,2,1,0}) ' \
      'custom-call(%a.1, %b.2, %c.3), custom_call_target="tpu_custom_call"'


def _synthetic():
    """Two devices, window [0, 1000] ns."""
    dev0 = {"name": "/device:TPU:0",
            "ops": [[_op("fusion.1"), 100, 300],
                    [_op("flash_attention_fwd.18", FWD), 300, 400],
                    [_op("all-gather-start.2"), 400, 450],
                    [_op("all-gather-done.2"), 450, 500],
                    [_op("fusion.3"), 480, 600],
                    [_op("adamw_update.5"), 700, 900],
                    [_op("while.2", " = (s32[]) while((s32[]) %t.1)"), 100, 900]]}
    dev1 = {"name": "/device:TPU:1",
            "ops": [[_op("fusion.1"), 100, 500],
                    [_op("collective-permute.4"), 500, 700],
                    ["adamw_update.7", 700, 800]]}
    host = [["window", 0, 1000], ["await_step", 850, 1000],
            ["make_batch", 0, 100]]
    return tracing.Trace({"devices": [dev0, dev1], "host": host})


@pytest.mark.parametrize("event,name", [
    ("%flash_attention_fwd.18" + FWD, "flash_attention_fwd"),
    ("%flash_attention_bwd = bf16[8]{0} custom-call(%a.1)", "flash_attention_bwd"),
    ("%select_add_fusion.41 = f32[4]{0} fusion(f32[4]{0} %x.2), kind=kOutput",
     "select_add_fusion"),
    ("adamw_update.3", "adamw_update"),
    ("copy-start", "copy-start"),
])
def test_op_name_is_the_instruction_name(event, name):
    assert tracing.op_name(event) == name


def test_busy_idle_and_kernels():
    tr = _synthetic()
    assert tr.window_s == pytest.approx(1e-6)
    # dev0 busy 100..600 and 700..900 = 700 (its while.2 spans 100..900
    # and is no op of its own); dev1 100..800 = 700
    assert tr.busy_s() == pytest.approx(700e-9)
    assert tr.idle_share() == pytest.approx(0.3)
    k = tr.kernel_calls(("flash_attention_fwd", "adamw_update"))
    assert k["flash_attention_fwd"] == [1, pytest.approx(100e-9)]
    assert k["adamw_update"] == [2, pytest.approx(300e-9)]
    assert tr.kernel_calls(("flash_attention_bwd",)) is None
    # an instruction name is matched whole, not as a part of another
    assert tr.kernel_calls(("flash_attention",)) is None


def test_breakdown_of_ops_and_idle_gaps():
    tr = _synthetic()
    ops = dict(tr.top_ops())
    assert ops["fusion"] == pytest.approx((200 + 120 + 400) / 2 * 1e-9)
    assert ops["adamw_update"] == pytest.approx(300 / 2 * 1e-9)
    gaps = dict(tr.idle_gaps())
    # dev0 idle: 0..100 (make_batch), 600..700 (other), 900..1000 (await);
    # dev1 idle: 0..100, 800..1000 (await_step overlaps 850..1000)
    assert gaps["make_batch"] == pytest.approx(100e-9)
    assert gaps["await_step"] == pytest.approx((100 + 200) / 2 * 1e-9)
    assert gaps["other"] == pytest.approx(50e-9)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no TPU device plane"):
        tracing.Trace({"devices": [], "host": []})


def _reader_ctx(ops):
    from bench import peaks, spec
    from bench.run import MetricContext
    cell = spec.load_cell("x32-s512-layered-1chip")
    tr = tracing.Trace({"devices": [{"name": "/device:TPU:0", "ops": ops}],
                        "host": [["window", 0, 10**9]]})
    return MetricContext(cell=cell, trace=tr, tokens_per_s=1.0, steps=1,
                         chips=1, peak=peaks.peak("TPU v5 lite"),
                         fused_adamw_params=1000)


def test_flash_attn_roofline_counts_every_call():
    from bench import counts, peaks, spec
    read = spec.metric_module("flash_attn_roofline").read
    us = 1000
    # two forward calls (the layer and its recomputation), one dq + dkv pair
    ops = [[_op("flash_attention_fwd.1", FWD), 0, 100 * us],
           [_op("flash_attention_fwd.2", FWD), 200 * us, 300 * us],
           [_op("flash_attention_bwd"), 400 * us, 600 * us],
           [_op("flash_attention_bwd.1"), 600 * us, 800 * us]]
    cfg = spec.load_cell("x32-s512-layered-1chip").config
    c = counts.flash_attention(cfg, 512, 8)
    p = peaks.peak("TPU v5 lite")
    least = 2 * counts.least_s(c["fwd"], p) + counts.least_s(c["dq"], p) \
        + counts.least_s(c["dkv"], p)
    assert read(_reader_ctx(ops)) == pytest.approx(100 * least / 600e-6)
    # a backward kernel without its pair, or no backward: nothing
    assert read(_reader_ctx(ops[:3])) is None
    assert read(_reader_ctx(ops[:2])) is None


def test_adamw_roofline_reads_nothing_without_its_kernel():
    from bench import spec
    read = spec.metric_module("adamw_roofline").read
    ops = [[_op("fusion.1"), 0, 1000]]
    assert read(_reader_ctx(ops)) is None
    v = read(_reader_ctx(ops + [[_op("adamw_update.3"), 1000, 2000]]))
    assert v == pytest.approx(100 * 28000 / 819e9 / 1e-6)
