"""The reduction from a profiler trace to device numbers.

Exact arithmetic on a hand-built trace, and the whole reduction on a
short trace recorded on a TPU v5 lite (``data/conv_window.xplane.pb``:
half a second of the k1000-conv window)."""

import pathlib

import pytest

from bench import lib, roofline, xplane

DATA = pathlib.Path(__file__).with_name("data") / "conv_window.xplane.pb"


def _trace():
    tr = xplane.Trace(window=(10.0, 20.0))
    knn = ("%knn_fused_topk.1 = (f32[8,1024]{1,0}, s32[8,1024]{1,0}) "
           "custom-call(f32[8,896]{1,0} %q, f32[8,1]{1,0} %s, "
           "s8[2048,896]{1,0} %d, s32[2,1,1024]{2,1,0} %i, "
           "f32[2,1,1024]{2,1,0} %c), custom_call_target=\"tpu_custom_call\","
           " operand_layout_constraints={f32[8,896]{1,0}}")
    other = "%fusion.3 = f32[64,896]{1,0} fusion(f32[64,896]{1,0} %a)"
    tr.devices[0] = [
        xplane.Op(other, 8.0, 3.0, other),        # 1 s inside the window
        xplane.Op(knn, 12.0, 2.0, knn),
        xplane.Op(other, 13.0, 2.0, other),       # overlaps the kernel
        xplane.Op(knn, 16.0, 1.0, knn),
        xplane.Op(other, 19.5, 5.0, other),       # 0.5 s inside
    ]
    tr.spans = [("bench.window", 10.0, 20.0),
                ("bench.router_search", 11.0, 17.0),
                ("bench.fill_wave", 17.0, 19.0)]
    return tr


def test_busy_is_the_union_inside_the_window():
    busy, merged = xplane.busy(_trace(), 0)
    assert busy == pytest.approx(1.0 + 3.0 + 1.0 + 0.5)
    assert merged == [[10.0, 11.0], [12.0, 15.0], [16.0, 17.0],
                      [19.5, 20.0]]


def test_kernel_time_and_shapes():
    ops = xplane.kernel_ops(_trace(), 0, lib.KERNELS["knn_fused_topk"])
    assert [o.dur for o in ops] == [2.0, 1.0]
    shapes = xplane.operand_shapes(ops[0].text)
    assert shapes[:2] == [("f32", (8, 1024)), ("s32", (8, 1024))]
    assert ("s8", (2048, 896)) in shapes and len(shapes) == 7
    assert xplane.short_name(ops[0].text) == "knn_fused_topk.1 f32[8,1024]"


def test_breakdown_labels_gaps_with_host_spans():
    bd = xplane.breakdown(_trace(), 0)
    assert bd["device_ops"][0] == ["fusion.3 f32[64,896]", 3.5]
    assert bd["idle_gaps"] == [["fill_wave", 2.5], ["router_search", 1.0],
                               ["router_search", 1.0]]


@pytest.fixture(scope="module")
def recorded():
    if not DATA.exists():
        pytest.fail(f"missing recorded trace {DATA}")
    return xplane.load(str(DATA))


def test_recorded_trace_reduces(recorded):
    tr = recorded
    assert 0 in tr.devices and tr.window_s > 0
    busy, _ = xplane.busy(tr, 0)
    assert 0 < busy <= tr.window_s
    knn = xplane.kernel_ops(tr, 0, lib.KERNELS["knn_fused_topk"])
    probe = xplane.kernel_ops(tr, 0, lib.KERNELS["probe_rhat_batched"])
    assert knn and probe
    peak = roofline.peaks("TPU v5 lite")
    for ops, cost in ((knn, roofline.knn_fused_topk),
                      (probe, roofline.probe_rhat_batched)):
        share, bound = roofline.share(
            [cost(xplane.operand_shapes(o.text)) for o in ops],
            sum(o.dur for o in ops), peak)
        assert 0 < share < 100 and bound == "memory"
    bd = xplane.breakdown(tr, 0)
    assert 0 < len(bd["device_ops"]) <= 10
    assert 0 < len(bd["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in bd["idle_gaps"])
