"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time, op
time by name, and idle gaps attributed to what the host was doing.

Device planes are ``/device:...``; on each, the ``XLA Ops`` line holds one
event per operation that ran, named by its HLO text (``%run.18 = s32[...]
custom-call(...), custom_call_target="tpu_custom_call"``).  A ``while``,
``conditional`` or ``call`` event spans the ops of its body, which have
events of their own, so it is left out: busy time is the union of the
ops that did the work.  Host planes hold the harness's own
``TraceAnnotation`` spans and JAX's host events, on the same clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

WINDOW_SPAN = "bench.traced"
CONTAINERS = ("while", "conditional", "call")
BETWEEN_OPS_S = 50e-6
BETWEEN_OPS = "device: between ops (< 50 us)"
_HLO = re.compile(r"^%?([\w.-]+) = .*? ([a-z][\w-]*)\(")


def hlo_name_op(text: str) -> tuple[str, str]:
    """(instruction name, opcode) of one line of HLO text; the text itself
    and no opcode where it is not HLO."""
    m = _HLO.match(text)
    return (m.group(1), m.group(2)) if m else (text, "")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # seconds
    end: float
    label: str = ""  # name and the string stats the profiler attached

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Reduced:
    window: tuple[float, float]
    n_devices: int
    busy_s: float  # mean over devices of the union of op intervals
    ops: list[Event]  # device ops clipped to the window, all devices
    gaps: list[tuple[str, float]]  # (host activity, idle seconds), summed by name

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def complement(busy, window) -> list[tuple[float, float]]:
    t0, t1 = window
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, min(s, t1)))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    return [(s, e) for s, e in gaps if e > s]


def clip(events, window) -> list[Event]:
    t0, t1 = window
    return [Event(e.name, max(e.start, t0), min(e.end, t1), e.label)
            for e in events if e.end > t0 and e.start < t1]


def attribute(gaps, host_events) -> list[str]:
    """For each gap, the name of the innermost host event that covers most
    of it ("no host span" where none does)."""
    if not host_events:
        return ["no host span"] * len(gaps)
    starts = np.array([h.start for h in host_events])
    ends = np.array([h.end for h in host_events])
    out = []
    for a, b in gaps:
        ov = np.minimum(b, ends) - np.maximum(a, starts)
        hit = np.flatnonzero(ov > 0)
        if not len(hit):
            out.append("no host span")
            continue
        # most overlap first, then the shortest event (the innermost)
        best = hit[np.lexsort((ends[hit] - starts[hit], -ov[hit]))[0]]
        out.append(host_events[best].name)
    return out


def reduce(device: dict[str, list[Event]], host: list[Event], window=None) -> Reduced:
    """``device``: events per device plane; ``host``: host events.  The
    window is the harness's ``bench.traced`` span unless given.  Gaps
    shorter than ``BETWEEN_OPS_S`` are the device's own between two ops
    (inside a loop, say) and are not looked up among host events."""
    if window is None:
        spans = [h for h in host if h.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
        window = (min(h.start for h in spans), max(h.end for h in spans))
    ops, busy, gaps = [], 0.0, {}
    inner = [h for h in host if h.name != WINDOW_SPAN and h.dur > 0]
    for events in device.values():
        mine = clip(events, window)
        ops.extend(mine)
        merged = union((e.start, e.end) for e in mine)
        busy += sum(e - s for s, e in merged)
        idle = complement(merged, window)
        short = sum(e - s for s, e in idle if e - s < BETWEEN_OPS_S)
        if short:
            gaps[BETWEEN_OPS] = gaps.get(BETWEEN_OPS, 0.0) + short
        long = [(s, e) for s, e in idle if e - s >= BETWEEN_OPS_S]
        for (s, e), name in zip(long, attribute(long, inner)):
            gaps[name] = gaps.get(name, 0.0) + (e - s)
    n = max(len(device), 1)
    return Reduced(window, len(device), busy / n, ops,
                   sorted(((k, v / n) for k, v in gaps.items()), key=lambda kv: -kv[1]))


def group(name: str) -> str:
    """An op's name without its instance number (``fusion.12`` -> ``fusion``)."""
    return re.sub(r"[.:]\d+$", "", name)


def top_ops(red: Reduced, k: int = 10, names: dict[str, str] | None = None) -> list[list]:
    """The ``k`` op groups that took most device time (mean over devices);
    ``names`` maps a group name to a pattern whose ops it gathers."""
    rx = {n: re.compile(p) for n, p in (names or {}).items()}
    tot: dict[str, float] = {}
    for e in red.ops:
        g = next((n for n, r in rx.items() if r.search(e.label or e.name)), None) or group(e.name)
        tot[g] = tot.get(g, 0.0) + e.dur
    return [[n, s / red.n_devices] for n, s in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def op_seconds(red: Reduced, pattern: str) -> tuple[float, int]:
    """Summed device seconds (mean over devices) and count of the ops
    whose name or stats match ``pattern``."""
    rx = re.compile(pattern)
    hits = [e for e in red.ops if rx.search(e.label or e.name)]
    return sum(e.dur for e in hits) / red.n_devices, len(hits)


def _label(ev) -> str:
    parts = [ev.name]
    try:
        stats = dict(ev.stats)
    except (TypeError, ValueError):
        stats = {}
    parts += [str(v) for v in stats.values() if isinstance(v, str)]
    return " ".join(parts)


def load(trace_dir: str, n_devices: int) -> tuple[dict[str, list[Event]], list[Event]]:
    """Device op events of the first ``n_devices`` device planes and every
    host event, from the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    device: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            if len(device) >= n_devices:
                continue
            evs = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    name, op = hlo_name_op(e.name)
                    if op not in CONTAINERS:
                        evs.append(Event(name, e.start_ns * 1e-9, e.end_ns * 1e-9, _label(e)))
            device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                         for e in line.events]
    return device, host
