"""Observability: tracing, live metrics, attribution, and drift reporting.

Small, dependency-light modules thread telemetry through the serving
engine, the kernels, and the benches:

* :mod:`repro.obs.trace` — a bounded ring-buffer :class:`TraceRecorder`
  with a span/event API.  The engine opens one span per request
  lifecycle (queued → admitted → prefill chunks → decode → terminal
  status, with preemption/retry/chaos events attached) and one span per
  fused step (host dispatch vs device wait split out), plus per-step
  **counter tracks** (pool pressure, slot occupancy, windowed
  throughput); exports are Chrome trace-event JSON loadable in
  Perfetto, with ``M`` metadata naming the process/thread tracks.
* :mod:`repro.obs.metrics` — counter/gauge/histogram registry with
  Prometheus text exposition, the shared None-never-NaN
  :func:`percentile` helper, and :class:`WindowedSeries` for live
  windowed rates (``Engine.live_metrics()``).
* :mod:`repro.obs.promcheck` — strict text-exposition conformance
  parser; the tests and the CI scrape run every exposition through it.
* :mod:`repro.obs.attrib` — sampled in-situ profiler: every N engine
  steps the fused step is re-executed segmented per layer on a
  donation-safe state copy, attributing real device time to each layer
  and its ``(w_bits, a_bits)`` pair (registry counters).
* :mod:`repro.obs.server` — stdlib-HTTP telemetry endpoint on a
  background thread: ``/metrics`` (Prometheus text), ``/livez``
  (windowed live JSON), ``/trace`` (incremental trace-segment flush).
* :mod:`repro.obs.drift` — per-layer *measured* kernel time against the
  served plan's *predicted* ``T_mul``/cost fields (paper Eq. 6
  ``Op / T_mul``), standalone and **in-situ** (from attribution samples
  inside the fused step), reported as ``artifacts/plan_drift.json``.

Tracing and attribution are opt-in and true no-ops when disabled:
every hot-path hook is one ``is not None`` predicate, no allocation.
"""
from repro.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    WindowedSeries,
    percentile,
)
from repro.obs.server import TelemetryServer  # noqa: F401
from repro.obs.trace import TraceRecorder  # noqa: F401

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "TelemetryServer",
    "TraceRecorder",
    "WindowedSeries",
    "percentile",
]
