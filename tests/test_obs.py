"""Observability layer: trace recorder semantics, the metrics registry /
windowed series, live engine metrics mid-run, traced engine runs passing
the trace gate, and the plan-drift report."""
import json
import math
import pathlib
import sys

import jax
import numpy as np
import pytest

from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    WindowedSeries,
    percentile,
)
from repro.obs.trace import TraceRecorder

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks"))

import check_invariants as ci  # noqa: E402


# ---------------------------------------------------------------------------
# trace recorder
# ---------------------------------------------------------------------------


def test_trace_ring_buffer_bounds_and_counts_drops():
    tr = TraceRecorder(capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr) == 4
    assert tr.n_dropped == 6
    # oldest dropped, newest kept
    assert [e["name"] for e in tr.events] == ["e6", "e7", "e8", "e9"]
    assert tr.to_chrome()["repro"]["dropped"] == 6
    with pytest.raises(ValueError):
        TraceRecorder(capacity=0)


def test_trace_request_phases_close_automatically():
    tr = TraceRecorder()
    tr.req_begin(7, prompt_tokens=3)
    tr.req_begin(7)  # idempotent: re-attachment never double-begins
    tr.req_phase(7, "queued")
    tr.req_phase(7, "queued")  # same-phase transition is a no-op
    tr.req_phase(7, "prefill", slot=0)
    tr.req_phase(7, "decode", slot=0)
    tr.req_end(7, "ok")
    evs = tr.events
    assert sum(1 for e in evs if e["ph"] == "b" and e["name"] == "request") == 1
    begins = [e["name"] for e in evs if e["ph"] == "b"]
    ends = [e["name"] for e in evs if e["ph"] == "e"]
    assert begins == ["request", "queued", "prefill", "decode"]
    # every phase closed in order, envelope last, nothing dangles
    assert ends == ["queued", "prefill", "decode", "request"]
    assert tr.phase(7) is None


def test_trace_complete_span_and_chrome_shape(tmp_path):
    tr = TraceRecorder()
    t0 = tr.now()
    t1 = tr.now()
    tr.complete("step", t0, t1, step=1)
    d = tr.to_chrome()
    assert d["displayTimeUnit"] == "ms"
    # metadata name events prepended for Perfetto track naming
    assert [e["ph"] for e in d["traceEvents"][:2]] == ["M", "M"]
    x = d["traceEvents"][-1]
    assert x["ph"] == "X" and x["dur"] >= 0 and x["args"] == {"step": 1}
    p = tr.save(tmp_path / "sub" / "t.json")
    assert json.loads(p.read_text())["repro"]["n_events"] == 1


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_percentile_none_never_nan():
    assert percentile([], 99) is None
    assert percentile([1.0, 2.0, 3.0], 50) == 2.0
    assert not math.isnan(percentile([5.0], 99))


def test_counter_gauge_labels_and_monotonicity():
    c = Counter("c")
    c.inc(status="ok")
    c.inc(2, status="ok")
    c.inc(status="shed")
    assert c.value(status="ok") == 3 and c.value(status="shed") == 1
    with pytest.raises(ValueError):
        c.inc(-1)
    g = Gauge("g")
    g.set(5)
    g.inc(-2)  # gauges may go down
    assert g.value() == 3


def test_histogram_buckets_and_nan_guard():
    h = Histogram("h", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0, float("nan")):
        h.observe(v)
    assert h.count == 3  # NaN never enters sums/percentiles
    assert not math.isnan(h.sum)
    samples = dict((f"{n}{l}", v) for n, l, v in h.samples())
    assert samples['h_bucket{le="0.1"}'] == 1
    assert samples['h_bucket{le="1"}'] == 2  # cumulative
    assert samples['h_bucket{le="+Inf"}'] == 3
    assert h.pct(50) == 0.5


def test_registry_exposition_and_kind_clash():
    reg = MetricsRegistry()
    reg.counter("requests", "total requests").inc(3)
    reg.gauge("depth").set(2)
    assert reg.counter("requests") is reg.counter("requests")
    with pytest.raises(TypeError):
        reg.gauge("requests")
    text = reg.prometheus_text()
    assert "# HELP requests total requests" in text
    assert "# TYPE requests counter" in text
    assert "requests 3" in text and "depth 2" in text
    snap = reg.snapshot()
    assert snap["requests"] == 3


def test_windowed_series_prunes_and_rates():
    w = WindowedSeries()
    for t in range(10):
        w.add(float(t), 2.0)
    assert w.sum(now=9.0, window=3.0) == 8.0  # t in {6,7,8,9} survive
    assert w.rate(now=9.0, window=4.0) == 2.0
    assert w.rate(now=9.0, window=0.0) is None


# ---------------------------------------------------------------------------
# engine integration: live metrics mid-run, traced runs pass the gate
# ---------------------------------------------------------------------------


def _engine(arch="llama3.2-3b", **kw):
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serving import Engine, EngineConfig

    cfg = get_config(arch, smoke=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    ecfg = EngineConfig(n_slots=2, page_size=8, max_len=32, chunk_tokens=4, **kw)
    eng = Engine(cfg, params, ecfg)
    rng = jax.random.PRNGKey(1)
    for _ in range(3):
        rng, k = jax.random.split(rng)
        eng.submit(jax.random.randint(k, (6,), 1, cfg.vocab).tolist(), 5)
    return eng


def test_live_metrics_mid_run_and_metrics_without_wall():
    eng = _engine()
    eng.warmup()
    eng.run(realtime=False, max_steps=3)
    live = eng.live_metrics()
    assert live["steps"] == 3
    assert live["active_slots"] > 0  # genuinely mid-run
    assert live["steps_per_s_window"] > 0
    mid = eng.metrics()  # no wall argument: engine supplies its own clock
    assert mid["steps"] == 3 and mid["wall"] > 0
    m = eng.run(realtime=False)  # resume to completion
    assert m["statuses"] == {"ok": 3}
    assert eng.metrics()["wall"] == m["wall"]  # frozen after the run
    assert eng.live_metrics()["active_slots"] == 0
    text = eng.prometheus_text()
    assert "repro_steps_total" in text and 'status="ok"' in text


def test_traced_run_passes_trace_gate_and_is_perfetto_shaped(tmp_path):
    eng = _engine()
    tr = TraceRecorder()
    m = eng.run(realtime=False, trace=tr)
    d = tr.to_chrome()
    assert ci.check_trace(d) == []
    assert d["repro"]["steps"] == m["steps"]
    assert d["repro"]["statuses"] == m["statuses"]
    # request lifecycle actually recorded: one envelope per request, with
    # queued -> prefill -> decode phases and prefill_chunk instants
    names = {e["name"] for e in d["traceEvents"]}
    assert {"request", "queued", "prefill", "decode", "prefill_chunk",
            "step", "dispatch", "device_wait"} <= names
    # path variant: run() writes the file itself
    eng2 = _engine()
    out = tmp_path / "trace.json"
    eng2.run(realtime=False, trace=str(out))
    assert ci.run(str(out), "trace") == []


def test_traced_chaos_run_reconciles_injections():
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serving import ChaosConfig, Engine, EngineConfig

    cfg = get_config("llama3.2-3b", smoke=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(
        cfg, params,
        EngineConfig(n_slots=2, page_size=8, max_len=32, chunk_tokens=4,
                     n_pages=5, admit="on-demand", max_request_retries=64),
        chaos=ChaosConfig(seed=5, step_fault_rate=0.2, alloc_fault_rate=0.2,
                          nan_rate=0.2),
    )
    rng = jax.random.PRNGKey(1)
    for _ in range(3):
        rng, k = jax.random.split(rng)
        eng.submit(jax.random.randint(k, (6,), 1, cfg.vocab).tolist(), 5)
    tr = TraceRecorder()
    m = eng.run(realtime=False, trace=tr)
    assert sum(m["injected"].values()) > 0, "chaos never fired; raise rates"
    assert ci.check_trace(tr.to_chrome()) == []


# ---------------------------------------------------------------------------
# host phases on the profiler's clock, named scopes in the model step
# ---------------------------------------------------------------------------

# the host phases inside each engine step, in order
PHASES = ("batch", "upload", "dispatch", "device_wait", "logits_copy", "sample")
SCOPES = ("decode_paged_layer", "in_proj", "conv", "ssm", "out_proj", "lm_head")


def _profiled_run(log_dir, recorder=None):
    """Drive a smoke engine one ``run()`` call per step, as the benchmark
    does, under the JAX profiler; returns the engine and the host events
    named ``engine.*`` or ``test.run`` as ``(name, start_ns, end_ns,
    stats)``, in order (an enclosing span before what it encloses)."""
    from jax.profiler import ProfileData

    eng = _engine("mamba2-130m")
    eng.warmup()
    jax.profiler.start_trace(str(log_dir))
    try:
        while len(eng.finished) < 3:
            with jax.profiler.TraceAnnotation("test.run"):
                eng.run(realtime=False, max_steps=eng.n_steps + 1, trace=recorder)
    finally:
        jax.profiler.stop_trace()
    (path,) = pathlib.Path(log_dir).rglob("*.xplane.pb")
    evs = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
           for plane in ProfileData.from_file(str(path)).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith(("engine.", "test.run"))]
    return eng, sorted(evs, key=lambda e: (e[1], -e[2]))


def _inside(evs, outer):
    return [e for e in evs if e is not outer and outer[1] <= e[1] and e[2] <= outer[2]]


def test_engine_phases_are_profiler_spans(tmp_path):
    eng, evs = _profiled_run(tmp_path)
    steps = [e for e in evs if e[0] == "engine.step"]
    assert [e[3]["step_num"] for e in steps] == list(range(1, eng.n_steps + 1))
    # the profiler's step marker, as StepTraceAnnotation sets it
    assert all(e[3]["_r"] == 1 for e in steps)
    assert len(steps) >= 4
    for step in steps:
        # exactly one of each phase, in order, inside its step
        assert [e[0] for e in _inside(evs, step)] == [f"engine.{p}" for p in PHASES]
    runs = [e for e in evs if e[0] == "test.run"]
    assert len(runs) == eng.n_steps
    for run in runs:
        names = [e[0] for e in _inside(evs, run)]
        assert names[0] == "engine.admit" and names[-1] == "engine.summary"
        assert names.count("engine.summary") == 1 and names.count("engine.step") == 1


def test_recorder_phases_share_the_profiler_steps(tmp_path):
    from repro.obs.trace import ENGINE_PID, STEP_TID

    tr = TraceRecorder()
    eng, evs = _profiled_run(tmp_path, tr)
    spans = sorted((e for e in tr.events if e["ph"] == "X"),
                   key=lambda e: (e["ts"], -e["dur"]))
    assert all(e["pid"] == ENGINE_PID and e["tid"] == STEP_TID for e in spans)
    # the same phases, in the same order, without the prefix
    assert [e["name"] for e in spans] == [e[0].removeprefix("engine.") for e in evs
                                          if e[0] != "test.run"]
    rec = {e["args"]["step"]: e["ts"] for e in spans if e["name"] == "step"}
    prof = {e[3]["step_num"]: e[1] / 1e3 for e in evs if e[0] == "engine.step"}
    assert sorted(rec) == sorted(prof) == list(range(1, eng.n_steps + 1))
    # matched by step number, the two clocks keep one offset (microseconds)
    offsets = [prof[k] - rec[k] for k in rec]
    assert max(offsets) - min(offsets) < 500.0
    assert ci.check_trace(tr.to_chrome()) == []


def test_model_step_hlo_carries_named_scopes():
    import re

    import jax.numpy as jnp

    eng = _engine("mamba2-130m")
    S, C = eng.ecfg.n_slots, eng.ecfg.chunk_tokens
    zeros = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    text = eng._step.lower(eng.params, eng.state, jnp.asarray(eng.block_table.as_array()),
                           zeros(S, C), zeros(S), zeros(S)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope


# ---------------------------------------------------------------------------
# plan drift
# ---------------------------------------------------------------------------


def test_drift_report_structure_and_gate():
    from repro.configs import get_config
    from repro.obs.drift import build_report
    from repro.plan.search import plan_from_bits

    cfg = get_config("gemma3-1b", smoke=True)
    plan = plan_from_bits(cfg, arch="gemma3-1b",
                          bits=[(5, 4), (8, 4), (2, 2)], n_slots=4)
    report = build_report(plan, cfg, n_slots=4, reps=1)
    assert ci.check_drift(report) == []
    assert report["n_layers"] == len(plan.layers)
    assert report["n_distinct_bit_pairs"] == 3
    for row in report["layers"]:
        assert row["measured_us"] > 0
        assert row["per_proj_us"]
    shares = sum(r["measured_share"] for r in report["layers"])
    assert shares == pytest.approx(1.0)
    assert 0 <= report["rank_inversions"] <= report["n_layer_pairs"]
    # default mode="both": the in-situ block rides along, measured by
    # attribution sampling inside the fused serving step
    blk = report["in_situ"]
    assert blk["n_samples"] >= 1 and blk["attrib_every"] >= 1
    assert sum(r["measured_share"] for r in blk["layers"]) == pytest.approx(1.0)
    assert all(r["measured_us"] > 0 for r in blk["layers"])
    assert 0 <= blk["rank_inversions"] <= blk["n_layer_pairs"]
    # JSON-safe end to end (no NaN, no numpy scalars)
    json.loads(json.dumps(report, allow_nan=False))
    # gate rejects a doctored in_situ block (no samples)
    import copy

    bad = copy.deepcopy(report)
    bad["in_situ"]["n_samples"] = 0
    assert any("n_samples" in e for e in ci.check_drift(bad))


def test_kernel_timer_records_and_bests():
    from repro.kernels.common import KernelTimer, kernel_timing, timed

    timer = KernelTimer()
    with kernel_timing(timer):
        out, dt = timed(lambda x: x * 2, np.ones(4), label="mul")
        timed(lambda x: x * 2, np.ones(4), label="mul")
    assert dt > 0 and (out == 2.0).all()
    assert len(timer.records["mul"]) == 2
    assert timer.best("mul") == min(timer.records["mul"])
    assert timer.total_best() == timer.best("mul")
    # outside the context, labels go nowhere (timer detached, no crash)
    timed(lambda x: x, np.ones(2), label="mul")
    assert len(timer.records["mul"]) == 2


# ---------------------------------------------------------------------------
# trace metadata, counter tracks, incremental segments
# ---------------------------------------------------------------------------


def test_trace_metadata_names_every_used_track():
    from repro.obs.trace import ENGINE_PID, REQUEST_PID, STEP_TID

    tr = TraceRecorder()
    t0 = tr.now()
    tr.complete("step", t0, tr.now(), step=1)
    tr.instant("marker", tid=5)  # a track with no stable name
    tr.req_begin(3)
    tr.req_end(3, "ok")
    ms = tr.name_metadata()
    # golden shape: process names first, then thread names, deterministic
    rows = [(e["ph"], e["name"], e["pid"], e["tid"], e["args"]["name"])
            for e in ms]
    assert rows == [
        ("M", "process_name", ENGINE_PID, 0, "repro-engine"),
        ("M", "process_name", REQUEST_PID, 0, "repro-requests"),
        ("M", "thread_name", ENGINE_PID, STEP_TID, "fused-step"),
        ("M", "thread_name", ENGINE_PID, 5, "tid-5"),
        ("M", "thread_name", REQUEST_PID, 0, "requests"),
    ]
    # to_chrome prepends exactly these before the payload events
    evs = tr.to_chrome()["traceEvents"]
    assert [e["ph"] for e in evs[: len(rows)]] == ["M"] * len(rows)


def test_trace_counter_events_and_segment_cursor():
    tr = TraceRecorder(capacity=4)
    tr.counter("pages", free=7)
    tr.counter("slots", active=2, waiting=1)
    seg, cursor, missed = tr.segment(0)
    assert [e["ph"] for e in seg] == ["C", "C"]
    assert seg[0]["args"] == {"free": 7.0}
    assert seg[1]["args"] == {"active": 2.0, "waiting": 1.0}
    assert (cursor, missed) == (2, 0)
    # incremental: nothing new since the cursor
    assert tr.segment(cursor) == ([], 2, 0)
    # overflow: old events drop, and a stale cursor reports what it missed
    for i in range(6):
        tr.instant(f"e{i}")
    seg, cursor, missed = tr.segment(2)
    assert cursor == 8 and missed == 2  # e0/e1 region evicted
    assert [e["name"] for e in seg] == ["e2", "e3", "e4", "e5"]
    assert tr.cursor == 8
    with pytest.raises(ValueError):
        tr.segment(-1)


# ---------------------------------------------------------------------------
# prometheus exposition conformance
# ---------------------------------------------------------------------------


def test_registry_exposition_passes_conformance_with_hostile_labels():
    from repro.obs.promcheck import check_exposition

    reg = MetricsRegistry()
    reg.counter("req_total", "requests by status").inc(2, status='we"ird\\x')
    reg.counter("req_total").inc(1, status="with\nnewline")
    reg.gauge("depth", "queue depth").set(3)
    reg.histogram("lat_seconds", "latency").observe(0.3)
    text = reg.prometheus_text()
    assert check_exposition(text) == []
    # escapes actually applied, not just tolerated
    assert 'status="we\\"ird\\\\x"' in text
    assert "\\nnewline" in text


@pytest.mark.parametrize("doctored, needle", [
    ("# TYPE m counter\n# HELP m late\nm 1\n", "HELP for m after its TYPE"),
    ("# TYPE m counter\nm 1\n# TYPE m counter\n", "duplicate TYPE"),
    ("# TYPE m bogus\nm 1\n", "unknown TYPE"),
    ("m 1\n", "no TYPE declaration"),
    ("# TYPE m counter\n# TYPE n counter\nm 1\nn 1\nm 2\n", "interleave"),
    ('# TYPE m counter\nm{l="a", l="b"} 1\n', "duplicate label"),
    ("# TYPE m gauge\nm NaN\n", "non-finite"),
    ("# TYPE m gauge\nm +Inf\n", "non-finite"),
    ("# TYPE m counter\nm -4\n", "negative counter"),
    ("# TYPE m counter\nm{} garbage\n", "unparseable value"),
    ("# TYPE h histogram\nh_bucket 1\nh_sum 1\nh_count 1\n", "le label"),
    ('# TYPE h histogram\nh_bucket{le="1"} 5\nh_bucket{le="+Inf"} 3\n'
     "h_sum 1\nh_count 3\n", "cumulative"),
    ('# TYPE h histogram\nh_bucket{le="1"} 1\nh_sum 1\nh_count 1\n', "+Inf"),
    ('# TYPE h histogram\nh_bucket{le="+Inf"} 2\nh_sum 1\nh_count 3\n',
     "!= _count"),
])
def test_promcheck_flags_each_violation(doctored, needle):
    from repro.obs.promcheck import check_exposition

    errs = check_exposition(doctored)
    assert any(needle in e for e in errs), (doctored, errs)


def test_promcheck_accepts_plain_metric_named_like_histogram_series():
    from repro.obs.promcheck import check_exposition

    # x_count with its own TYPE is a family, not an orphan histogram leg
    assert check_exposition("# TYPE x_count counter\nx_count 4\n") == []


def test_metric_values_reject_nonfinite():
    c = Counter("c")
    with pytest.raises(ValueError):
        c.inc(float("nan"))
    with pytest.raises(ValueError):
        c.inc(float("inf"))
    g = Gauge("g")
    with pytest.raises(ValueError):
        g.set(float("nan"))
    with pytest.raises(ValueError):
        g.inc(float("inf"))


# ---------------------------------------------------------------------------
# in-situ attribution
# ---------------------------------------------------------------------------


def test_attrib_sampling_on_engine_matches_counters_and_gate(tmp_path):
    eng = _engine(attrib_every=2)
    out = tmp_path / "attrib_trace.json"
    m = eng.run(realtime=False, trace=str(out))
    at = eng._attrib
    assert m["statuses"] == {"ok": 3}
    assert len(at.samples) == m["steps"] // 2 >= 1
    assert eng.registry.counter("repro_attrib_steps_total").value() == len(at.samples)
    for s in at.samples:
        assert {r["index"] for r in s["layers"]} == set(range(s["n_layers"]))
        assert sum(r["share"] for r in s["layers"]) == pytest.approx(1.0)
        assert all(r["seconds"] > 0 for r in s["layers"])
    # attribution shows up in the exposition alongside engine counters
    text = eng.prometheus_text()
    assert "repro_attrib_layer_seconds_total" in text
    from repro.obs.promcheck import check_exposition

    assert check_exposition(text) == []
    # the trace still satisfies the gate; its engine spans are the host
    # phases on the fused-step track (the re-execution's shares are not
    # put on it) and counter samples come every step
    d = json.loads(out.read_text())
    assert ci.check_trace(d) == []
    from repro.obs.trace import ENGINE_PID, STEP_TID

    spans = [e for e in d["traceEvents"]
             if e.get("ph") == "X" and e.get("pid") == ENGINE_PID]
    assert {e["tid"] for e in spans} == {STEP_TID}
    assert {e["name"] for e in spans} == set(PHASES) | {"step", "admit", "summary"}
    counters = [e for e in d["traceEvents"] if e.get("ph") == "C"]
    assert {e["name"] for e in counters} == {
        "pages", "slots", "tokens_per_s_window", "preemptions_total",
        "shed_total"}
    summ = at.summary()
    assert summ["n_samples"] == len(at.samples)
    assert sum(p["mean_share"] for p in summ["pairs"]) == pytest.approx(1.0)


def test_attrib_bit_pairs_from_mixed_plan():
    from repro.configs import get_config
    from repro.obs.attrib import LayerAttributor, layer_bit_pair, pair_label
    from repro.plan.apply import apply_plan
    from repro.plan.search import plan_from_bits
    from repro.serving import Engine, EngineConfig

    cfg = get_config("gemma3-1b", smoke=True)
    plan = plan_from_bits(cfg, arch="gemma3-1b",
                          bits=[(5, 4), (8, 4), (2, 2)], n_slots=2)
    params = T_init_mixed = None
    from repro.models import transformer as T

    params = T.init_params(jax.random.PRNGKey(0), cfg)
    params, head = apply_plan(params, cfg, plan)
    # pair metadata read straight from the packed layer trees
    assert [layer_bit_pair(p) for p in params["layers"]] == [(5, 4), (8, 4), (2, 2)]
    assert pair_label((5, 4)) == "w5a4" and pair_label(None) == "fp"
    eng = Engine(cfg, params,
                 EngineConfig(n_slots=2, page_size=8, max_len=32,
                              chunk_tokens=4, attrib_every=2),
                 head=head)
    rng = jax.random.PRNGKey(1)
    for _ in range(2):
        rng, k = jax.random.split(rng)
        eng.submit(jax.random.randint(k, (6,), 1, cfg.vocab).tolist(), 4)
    eng.run(realtime=False)
    s = eng._attrib.samples[0]
    assert [r["pair"] for r in s["layers"]] == ["w5a4", "w8a4", "w2a2"]
    assert sum(r["share"] for r in s["layers"]) == pytest.approx(1.0)


def test_attrib_rejects_bad_config():
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.obs.attrib import LayerAttributor
    from repro.serving import Engine, EngineConfig

    cfg = get_config("llama3.2-3b", smoke=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError):
        LayerAttributor(cfg, params, reps=0)
    with pytest.raises(ValueError):
        Engine(cfg, params, EngineConfig(n_slots=2, page_size=8, max_len=32,
                                         attrib_every=-1))
    with pytest.raises(ValueError):
        Engine(cfg, params, EngineConfig(n_slots=2, page_size=8, max_len=32,
                                         trace_checkpoint_every=-1))


def test_trace_checkpointing_writes_partial_trace(tmp_path, monkeypatch):
    out = tmp_path / "ckpt_trace.json"
    eng = _engine(trace_checkpoint_every=2)
    saves = []
    orig = TraceRecorder.save
    monkeypatch.setattr(
        TraceRecorder, "save",
        lambda self, path: saves.append(path) or orig(self, path))
    m = eng.run(realtime=False, trace=str(out))
    # a crash-durable save fired every 2 steps, plus the final seal
    assert len(saves) == m["steps"] // 2 + 1
    assert all(str(p) == str(out) for p in saves)
    final = json.loads(out.read_text())
    assert final["repro"]["statuses"] == {"ok": 3}
    assert ci.check_trace(final) == []
    # no path -> checkpointing has nowhere to write, run still succeeds
    saves.clear()
    eng2 = _engine(trace_checkpoint_every=2)
    eng2.run(realtime=False, trace=TraceRecorder())
    assert saves == []


# ---------------------------------------------------------------------------
# telemetry endpoint
# ---------------------------------------------------------------------------


def test_telemetry_server_routes_and_errors():
    import urllib.error
    import urllib.request

    from repro.obs import TelemetryServer
    from repro.obs.promcheck import check_exposition

    reg = MetricsRegistry()
    reg.counter("t_total", "things").inc(2, kind="a")
    tr = TraceRecorder()
    tr.instant("tick")

    def boom():
        raise RuntimeError("scrape-time failure")

    with TelemetryServer(metrics_fn=reg.prometheus_text,
                         livez_fn=lambda: {"steps": 3},
                         trace_fn=tr.segment) as srv:
        assert srv.port > 0
        text = urllib.request.urlopen(srv.url + "/metrics").read().decode()
        assert check_exposition(text) == []
        live = json.loads(urllib.request.urlopen(srv.url + "/livez").read())
        assert live == {"steps": 3}
        seg = json.loads(
            urllib.request.urlopen(srv.url + "/trace?since=0").read())
        assert len(seg["events"]) == 1 and seg["missed"] == 0
        cursor = seg["cursor"]
        seg2 = json.loads(urllib.request.urlopen(
            srv.url + f"/trace?since={cursor}").read())
        assert seg2["events"] == [] and seg2["cursor"] == cursor
        with pytest.raises(urllib.error.HTTPError) as e404:
            urllib.request.urlopen(srv.url + "/nope")
        assert e404.value.code == 404
    # unwired routes 404; broken callables become 500, not thread death
    with TelemetryServer(metrics_fn=boom) as srv:
        with pytest.raises(urllib.error.HTTPError) as e404:
            urllib.request.urlopen(srv.url + "/livez")
        assert e404.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as e500:
            urllib.request.urlopen(srv.url + "/metrics")
        assert e500.value.code == 500
        # the thread survived the 500: a second scrape still answers
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(srv.url + "/metrics")


# ---------------------------------------------------------------------------
# live windowed rates across run() boundaries (vclock persistence)
# ---------------------------------------------------------------------------


def test_live_metrics_windows_across_multiple_runs():
    eng = _engine()
    eng.warmup()
    eng.run(realtime=False, max_steps=4)
    v1 = eng._vclock
    full1 = eng.live_metrics(window=v1 + 1.0)["steps_per_s_window"]
    assert full1 == pytest.approx(4 / (v1 + 1.0))
    eng.run(realtime=False)  # drain: the virtual clock keeps advancing
    v2 = eng._vclock
    assert v2 > v1
    steps = eng.live_metrics(window=v2 + 1.0)["steps"]
    # a window spanning both runs sees all steps: _vclock never reset,
    # so first-run samples are not spuriously pruned as "old"
    spanning = eng.live_metrics(window=v2 + 1.0)["steps_per_s_window"]
    assert spanning == pytest.approx(steps / (v2 + 1.0))
    # a narrow window sees only the tail of the second run
    narrow = eng.live_metrics(window=2.0)["steps_per_s_window"]
    assert narrow <= 1.0  # at most 1 step per virtual-second by construction
    assert narrow * 2.0 < steps
