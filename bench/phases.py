#!/usr/bin/env python3
"""Device idle put down to the engine's host phases, from a profiler trace.

The engine opens a profiler annotation ``engine.<phase>`` around each of
its host phases (``repro.obs.trace.phase``): ``admit``, ``step`` and,
inside it, ``batch``, ``upload``, ``dispatch``, ``device_wait``,
``logits_copy`` and ``sample``, then ``summary``.  :func:`idle_by_span`
splits each long device-idle interval of a reduced trace by the
innermost such span open at each instant, where ``xplane.reduce`` gives
each whole gap to one host event.

Run on the chip, this measures one cell: set-up as ``bench/run.py`` does
it, an untraced stretch of ``--seconds``, then two traced stretches of
the traffic's ``trace_steps`` steps, the first with the profiler's
default options (as the benchmark traces), the second without its
Python tracer.  It prints one JSON line (also written under
``chiprun_out/``):

    python bench/phases.py --workload mamba2.chat --seed 7 --seconds 20
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import json  # noqa: E402
import pathlib  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(REPO / "src"), str(REPO)]

from bench import xplane  # noqa: E402
from bench.xplane import Event  # noqa: E402

PREFIX = "engine."
# idle inside these is not the host's: the whole step, and the wait on the device
NOT_HOST = ("engine.step", "engine.device_wait")
# the host work that sampling on the device would remove
LOGITS = ("engine.logits_copy", "engine.sample")


def long_idle(red: xplane.Reduced) -> list[tuple[float, float]]:
    """The device-idle intervals of at least ``BETWEEN_OPS_S`` in the
    window, from ``red.ops`` as ``xplane.reduce`` computes them.  The ops
    of several devices are not told apart in ``red.ops``, so one device
    only."""
    if red.n_devices != 1:
        raise ValueError(f"idle by span reads one device, not {red.n_devices}")
    merged = xplane.union((e.start, e.end) for e in red.ops)
    return [(s, e) for s, e in xplane.complement(merged, red.window)
            if e - s >= xplane.BETWEEN_OPS_S]


def idle_by_span(red: xplane.Reduced, host: list[Event], prefix: str) -> dict:
    """Long device-idle seconds by the innermost host span whose name
    starts with ``prefix`` open at each instant (the latest start among
    those covering it); the rest under ``None``."""
    spans = [h for h in xplane.clip(host, red.window)
             if h.name.startswith(prefix) and h.dur > 0]
    out: dict = {}
    for a, b in long_idle(red):
        mine = [h for h in spans if h.start < b and h.end > a]
        cuts = sorted({a, b, *(t for h in mine for t in (h.start, h.end) if a < t < b)})
        for p, q in zip(cuts, cuts[1:]):
            # every span in ``mine`` covers all of [p, q] or none of it
            open_ = [h for h in mine if h.start <= p and h.end >= q]
            name = max(open_, key=lambda h: (h.start, -h.end)).name if open_ else None
            out[name] = out.get(name, 0.0) + (q - p)
    return out


def _per_step_ms(red, host, n_steps: int, keep) -> float | None:
    if n_steps <= 0 or not any(h.name.startswith(PREFIX)
                               for h in xplane.clip(host, red.window)):
        return None
    parts = idle_by_span(red, host, PREFIX)
    return 1e3 * sum(v for k, v in parts.items() if k is not None and keep(k)) / n_steps


def engine_host_idle_ms(red, host, n_steps: int) -> float | None:
    """Device-idle ms per step under any engine phase but the step itself
    and the device wait; ``None`` where the trace holds no engine span."""
    return _per_step_ms(red, host, n_steps, lambda k: k not in NOT_HOST)


def logits_host_idle_ms(red, host, n_steps: int) -> float | None:
    """Device-idle ms per step under the logits copy and host sampling;
    ``None`` where the trace holds no engine span."""
    return _per_step_ms(red, host, n_steps, lambda k: k in LOGITS)


def summarize(red, host, n_steps: int, step_s: list[float]) -> dict:
    """The split of one traced stretch, in ms per step."""
    gaps = long_idle(red)
    long_s = sum(e - s for s, e in gaps)
    parts = idle_by_span(red, host, PREFIX)
    inside = [h for h in xplane.clip(host, red.window) if h.name.startswith(PREFIX)]
    phase_ms = {}
    for name in sorted({h.name for h in inside}):
        phase_ms[name] = 1e3 * sum(h.dur for h in inside if h.name == name) / n_steps
    # idle inside the device wait: before the step's first op (launch),
    # between its ops, or after its last op (the host's wake-up)
    wait = dict.fromkeys(("head", "within", "tail"), 0.0)
    for w in (h for h in inside if h.name == "engine.device_wait"):
        for a, b in gaps:
            if min(b, w.end) > max(a, w.start):
                part = "head" if a <= w.start else "tail" if b >= w.end else "within"
                wait[part] += min(b, w.end) - max(a, w.start)
    return {
        "steps": n_steps,
        "step_ms_median": 1e3 * statistics.median(step_s),
        "window_s": red.window_s,
        "busy_s": red.busy_s,
        "idle_share": 1.0 - red.busy_s / red.window_s,
        "idle_ms": 1e3 * (red.window_s - red.busy_s) / n_steps,
        "long_idle_ms": 1e3 * long_s / n_steps,
        "long_gaps": len(gaps),
        "split_ms": {str(k): 1e3 * v / n_steps
                     for k, v in sorted(parts.items(), key=lambda kv: -kv[1])},
        "split_sum_error": abs(sum(parts.values()) - long_s) / long_s if long_s else 0.0,
        "engine_host_idle_ms": engine_host_idle_ms(red, host, n_steps),
        "logits_host_idle_ms": logits_host_idle_ms(red, host, n_steps),
        "phase_host_ms": phase_ms,
        "device_wait_idle_ms": {k: 1e3 * v / n_steps for k, v in wait.items()},
    }


def traced(loop, n_steps: int, n_devices: int, options=None):
    """Profile ``n_steps`` steps of the running loop as the harness does;
    the reduced trace and the host events."""
    import shutil
    import tempfile

    import jax

    tmp = tempfile.mkdtemp(prefix="bench-phases-")
    try:
        jax.profiler.start_trace(tmp, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
                for _ in range(n_steps):
                    with jax.profiler.TraceAnnotation("bench.step"):
                        loop.step(traced=True)
        finally:
            jax.profiler.stop_trace()
        device, host = xplane.load(tmp, n_devices)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return xplane.reduce(device, host), host


def measure(cell, seed: int, seconds: float, devices) -> dict:
    """Set-up as ``harness.serve`` makes it, an untraced stretch, then
    the two traced ones; the harness's own traced stretch keeps no host
    events, so this one is made here."""
    import gc

    import jax

    from bench.harness import ClosedLoop
    from bench.model import check_layout, make_packed_params
    from bench.traffic import Traffic
    from repro.serving import EngineConfig, build_engine

    model = cell.model
    params = make_packed_params(model, seed)
    check_layout(model, params)
    eng = build_engine(model.cfg, EngineConfig(**model.engine), params=params, quant=None)
    del params
    eng.warmup()
    loop = ClosedLoop(eng, Traffic(cell.traffic, seed, model.cfg.vocab))
    loop.start()
    loop.step()
    gc.collect()
    gc.freeze()
    out = {"workload": cell.name, "seed": seed, "device": devices[0].device_kind,
           "setup_s": time.monotonic() - T_START}
    first, deadline = len(loop.steps), time.monotonic() + seconds
    while time.monotonic() < deadline:
        loop.step()
    out["untraced_steps"] = len(loop.steps) - first
    out["untraced_step_ms_median"] = 1e3 * statistics.median(
        r.t1 - r.t0 for r in loop.steps[first:])
    n = int(cell.traffic["trace_steps"])
    no_python = jax.profiler.ProfileOptions()
    no_python.python_tracer_level = 0
    for key, options in (("traced", None), ("traced_no_python", no_python)):
        first = len(loop.steps)
        red, host = traced(loop, n, len(devices), options)
        out[key] = summarize(red, host, n, [r.t1 - r.t0 for r in loop.steps[first:]])
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from repro.runtime.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"phases: no TPU (first device is {devices[0].platform})", file=sys.stderr)
        return 3
    enable_compile_cache()
    out = measure(cell, args.seed, args.seconds, devices[: cell.chips])
    line = json.dumps(out)
    dest = REPO / "chiprun_out" / f"phases-{args.workload}-{args.seed}.json"
    dest.parent.mkdir(exist_ok=True)
    dest.write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
