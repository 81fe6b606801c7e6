"""Trace reduction on a synthetic trace: busy union, idle share, kernel
time by name, idle gaps by host span."""
import pytest

from bench import xplane
from bench.harness import LayerContext, reader
from bench.xplane import Event


def _trace():
    device = {"/device:TPU:0": [
        Event("fusion.1", 0.0, 1.0),
        Event("run.7", 0.5, 2.0, "%run.7 = s32[16] custom-call(s32[16,8] %a)"),  # overlaps fusion.1
        Event("fusion.2", 3.0, 4.0),
        Event("run.9", 6.0, 7.0, "%run.9 = s32[16] custom-call(s32[16,8] %a)"),
        Event("fusion.3", 9.5, 11.0),  # half outside the window
    ]}
    host = [
        Event(xplane.WINDOW_SPAN, 0.0, 10.0),
        Event("bench.step", 0.0, 5.0),
        Event("bench.step", 5.0, 10.0),
        Event("PjitFunction(step_fn)", 2.0, 3.0),
        Event("np.asarray", 4.0, 6.0),
    ]
    return device, host


def test_union_and_complement():
    assert xplane.union([(0, 1), (0.5, 2), (3, 4), (4, 5)]) == [(0, 2), (3, 5)]
    assert xplane.complement([(0, 2), (3, 5)], (0, 6)) == [(2, 3), (5, 6)]
    assert xplane.complement([], (1, 2)) == [(1, 2)]


def test_busy_idle_and_gaps():
    red = xplane.reduce(*_trace())
    assert red.window == (0.0, 10.0)
    # busy: [0, 2] + [3, 4] + [6, 7] + [9.5, 10] = 4.5 s
    assert red.busy_s == pytest.approx(4.5)
    gaps = dict(red.gaps)
    # [2, 3] under PjitFunction, [4, 6] under np.asarray, [7, 9.5] under the step
    assert gaps["PjitFunction(step_fn)"] == pytest.approx(1.0)
    assert gaps["np.asarray"] == pytest.approx(2.0)
    assert gaps["bench.step"] == pytest.approx(2.5)
    assert sum(gaps.values()) == pytest.approx(10.0 - 4.5)


def test_kernel_time_by_name_and_top_ops():
    red = xplane.reduce(*_trace())
    secs, n = xplane.op_seconds(red, "custom-call")
    assert (secs, n) == (pytest.approx(2.5), 2)
    top = dict(xplane.top_ops(red))
    assert top["fusion"] == pytest.approx(1.0 + 1.0 + 0.5)
    assert top["run"] == pytest.approx(2.5)
    named = dict(xplane.top_ops(red, names={"packed": "custom-call"}))
    assert named == {"packed": pytest.approx(2.5), "fusion": pytest.approx(2.5)}


def test_short_gaps_are_the_devices_own():
    device = {"/device:TPU:0": [Event("a.1", 0.0, 1.0), Event("b.1", 1.00001, 2.0)]}
    host = [Event(xplane.WINDOW_SPAN, 0.0, 3.0), Event("bench.step", 0.0, 3.0)]
    gaps = dict(xplane.reduce(device, host).gaps)
    assert gaps[xplane.BETWEEN_OPS] == pytest.approx(1e-5)
    assert gaps["bench.step"] == pytest.approx(1.0)


def test_window_span_required():
    device, host = _trace()
    with pytest.raises(ValueError):
        xplane.reduce(device, [h for h in host if h.name != xplane.WINDOW_SPAN])


def test_idle_share_reader():
    red = xplane.reduce(*_trace())
    ctx = LayerContext(cell=None, trace=red, steps=[], peaks={})
    assert reader("metrics", "device_idle_share")(ctx) == pytest.approx(55.0)
