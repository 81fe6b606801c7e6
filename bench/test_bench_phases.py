"""Device idle split by the engine's host phases, on a synthetic trace."""
import pytest

from bench import phases, xplane
from bench.xplane import Event

# two steps in a 5 s window; the device works in [0, 1] and [3, 4] (the
# second stretch with a 10 us hole of its own), so the long idle is
# [1, 3] and [4, 5]
DEVICE = {"/device:TPU:0": [Event("a.1", 0.0, 1.0), Event("b.1", 3.0, 3.5),
                            Event("b.2", 3.50001, 4.0)]}
HOST = [
    Event(xplane.WINDOW_SPAN, 0.0, 5.0),
    Event("bench.step", 0.0, 2.5),
    Event("bench.step", 2.5, 5.0),
    Event("$engine.py:1 run", 0.0, 1.8),  # Python tracer: no prefix
    Event("np.asarray", 1.2, 1.4),
    Event("engine.step", 0.0, 1.6),
    Event("engine.dispatch", 0.0, 0.1),
    Event("engine.device_wait", 0.1, 1.2),
    Event("engine.logits_copy", 1.2, 1.4),
    Event("engine.sample", 1.4, 1.6),
    Event("engine.summary", 1.6, 1.8),
    # [1.8, 2.6]: the harness between run() calls, under no engine span
    Event("engine.admit", 2.6, 2.7),
    Event("engine.step", 2.7, 4.5),
    Event("engine.batch", 2.7, 2.8),
    Event("engine.upload", 2.8, 2.9),
    Event("engine.dispatch", 2.9, 3.1),
    Event("engine.device_wait", 3.1, 4.2),
    # [4.2, 4.25]: inside the step, between its phases
    Event("engine.logits_copy", 4.25, 4.4),
    Event("engine.sample", 4.4, 4.5),
    Event("engine.summary", 4.5, 4.6),
]


def _red():
    return xplane.reduce(DEVICE, HOST)


def test_idle_split_by_innermost_span_across_steps():
    parts = phases.idle_by_span(_red(), HOST, "engine.")
    want = {
        "engine.device_wait": 0.2 + 0.2, "engine.logits_copy": 0.2 + 0.15,
        "engine.sample": 0.2 + 0.1, "engine.summary": 0.2 + 0.1,
        "engine.admit": 0.1, "engine.batch": 0.1, "engine.upload": 0.1,
        "engine.dispatch": 0.1, "engine.step": 0.05, None: 0.8 + 0.4,
    }
    assert parts == {k: pytest.approx(v) for k, v in want.items()}


def test_spans_without_the_prefix_are_ignored():
    parts = phases.idle_by_span(_red(), HOST, "engine.")
    assert "np.asarray" not in parts and "bench.step" not in parts
    # with the harness's prefix instead, all idle lies under its steps
    bench = phases.idle_by_span(_red(), HOST, "bench.step")
    assert bench == {"bench.step": pytest.approx(3.0)}


def test_parts_sum_to_the_long_gap_idle():
    red = _red()
    parts = phases.idle_by_span(red, HOST, "engine.")
    long_idle = sum(v for k, v in red.gaps if k != xplane.BETWEEN_OPS)
    assert sum(parts.values()) == pytest.approx(long_idle) == pytest.approx(3.0)
    assert sum(e - s for s, e in phases.long_idle(red)) == pytest.approx(3.0)
    # the 10 us hole is the device's own, as reduce has it
    assert dict(red.gaps)[xplane.BETWEEN_OPS] == pytest.approx(1e-5)


def test_host_idle_per_step_in_ms():
    red = _red()
    # everything but the step, the device wait and the uncovered rest
    assert phases.engine_host_idle_ms(red, HOST, 2) == pytest.approx(
        1e3 * (0.35 + 0.3 + 0.3 + 0.4) / 2)
    assert phases.logits_host_idle_ms(red, HOST, 2) == pytest.approx(1e3 * 0.65 / 2)


def test_no_engine_span_reads_nothing():
    bare = [h for h in HOST if not h.name.startswith("engine.")]
    red = xplane.reduce(DEVICE, bare)
    assert phases.engine_host_idle_ms(red, bare, 2) is None
    assert phases.logits_host_idle_ms(red, bare, 2) is None
    assert phases.engine_host_idle_ms(_red(), HOST, 0) is None
    assert phases.idle_by_span(red, bare, "engine.") == {None: pytest.approx(3.0)}


def test_one_device_only():
    two = {**DEVICE, "/device:TPU:1": DEVICE["/device:TPU:0"]}
    with pytest.raises(ValueError):
        phases.idle_by_span(xplane.reduce(two, HOST), HOST, "engine.")


def test_summary_of_a_stretch():
    out = phases.summarize(_red(), HOST, 2, [2.5, 2.5])
    assert out["long_idle_ms"] == pytest.approx(1500.0)
    assert out["split_sum_error"] == pytest.approx(0.0, abs=1e-12)
    assert out["split_ms"]["None"] == pytest.approx(600.0)
    assert out["phase_host_ms"]["engine.device_wait"] == pytest.approx(1e3 * 2.2 / 2)
    # both waits outlast their step's ops: the idle is at their tails
    assert out["device_wait_idle_ms"] == {"head": 0.0, "within": 0.0,
                                          "tail": pytest.approx(200.0)}
