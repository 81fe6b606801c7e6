"""One run of one cell: set-up, the measured window, the check, the line.

The harness drives the engine through its public entry -- ``build_engine``
with pre-packed weights, ``submit``, ``warmup``, and ``run(max_steps=
n_steps + 1)`` one step at a time -- and keeps its own monotonic clock on
the client's side: after each step it stamps every token that step
produced.  It never reads the engine's own latencies.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time

import jax
import numpy as np

from bench import counts, reference, xplane
from bench.model import Model, check_layout, load_model, make_packed_params
from bench.traffic import Traffic, seed32

ROOT = pathlib.Path(__file__).resolve().parent
REPO = ROOT.parent
BAD_STATUSES = ("failed", "shed", "cancelled")


# -- the cell, from BENCHMARK.json and the files it names -----------------


@dataclasses.dataclass
class Cell:
    name: str
    model: Model
    traffic: dict
    chips: int
    limits: dict
    end_to_end: list[dict]  # the metrics this cell reports with --trace 0
    per_layer: list[dict]  # ... and with --trace 1


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, *, bench: dict | None = None, smoke: bool = False) -> Cell:
    if bench is None:
        bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    return Cell(
        name=name,
        model=load_model(w["config"], smoke=smoke),
        traffic=json.loads((ROOT / "traffic" / f"{w['traffic']}.json").read_text()),
        chips=int(w["chips"]),
        limits=json.loads((ROOT / "limits" / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
    )


def reader(kind: str, metric: str):
    """``read`` of ``<kind>/<metric>.py``, loaded by path."""
    path = ROOT / kind / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- the closed loop ------------------------------------------------------


@dataclasses.dataclass
class Tracked:
    req: object  # repro.serving.lifecycle.Request
    client: int
    t_submit: float
    stamps: list[float] = dataclasses.field(default_factory=list)
    rows: list[np.ndarray] = dataclasses.field(default_factory=list)  # logits of each token


@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float
    chunks: list[tuple[int, int]]  # (start position, tokens fed) per slot
    n_sampled: int
    traced: bool = False


class ClosedLoop:
    """``clients`` closed-loop clients with zero think time: each sends its
    next request as soon as the previous one reaches a terminal status.

    After each step it keeps a copy of the logits row that chose each new
    token, read from the host copy the engine itself made for its argmax
    (``Engine.last_logits``), for the correctness check: no device work
    and no extra sync in the window."""

    def __init__(self, eng, traffic: Traffic):
        self.eng = eng
        self.traffic = traffic
        self.live: dict[int, Tracked] = {}
        self.done: list[Tracked] = []
        self.steps: list[StepRecord] = []

    def _submit(self, client: int, now: float) -> None:
        spec = self.traffic.next_for(client)
        req = self.eng.submit(spec.prompt, spec.max_new_tokens)
        self.live[req.rid] = Tracked(req, client, now)

    def start(self) -> None:
        now = time.monotonic()
        for c in range(self.traffic.clients):
            self._submit(c, now)

    def step(self, traced: bool = False) -> StepRecord:
        before = {rid: (t.req.n_fed, t.req.slot) for rid, t in self.live.items()}
        t0 = time.monotonic()
        self.eng.run(max_steps=self.eng.n_steps + 1)
        t1 = time.monotonic()
        chunks, n_new = [], 0
        logits = self.eng.last_logits
        for rid, t in list(self.live.items()):
            req = t.req
            fed0, slot0 = before[rid]
            fed = req.n_fed - fed0
            if fed > 0:
                chunks.append((fed0, fed))
            new = len(req.out_tokens) - len(t.stamps)
            if new > 0:
                # one token per step; a request that finished gave its slot back
                slot = req.slot if req.slot >= 0 else slot0
                t.rows.append(np.array(logits[0, slot]))
                t.stamps.extend([t1] * new)
                n_new += new
            if req.status is not None:
                del self.live[rid]
                self.done.append(t)
                self._submit(t.client, t1)
        rec = StepRecord(t0, t1, chunks, n_new, traced)
        self.steps.append(rec)
        return rec

    def tracked(self) -> list[Tracked]:
        return self.done + list(self.live.values())


# -- the window's numbers -------------------------------------------------


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    tokens: int
    itl_s: list[float]
    ttft_s: list[float]
    memory_peak_bytes: int
    setup_s: float

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def window_stats(loop: ClosedLoop, t0: float, t1: float, peak: int, setup_s: float) -> Window:
    """Tokens, inter-token gaps and first-token times inside [t0, t1]."""
    tokens, itl, ttft = 0, [], []
    for t in loop.tracked():
        st = t.stamps
        tokens += sum(1 for s in st if t0 < s <= t1)
        itl += [b - a for a, b in zip(st, st[1:]) if a > t0 and b <= t1]
        if st and t0 < st[0] <= t1:
            ttft.append(st[0] - t.t_submit)
    return Window(t0, t1, tokens, itl, ttft, peak, setup_s)


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


# -- correctness ----------------------------------------------------------


def pick_sample(loop: ClosedLoop, k: int, seed: int) -> list[tuple[list[int], list[int], list]]:
    """(prompt, served tokens, their logits rows) of up to ``k`` requests,
    drawn from the seed among those finished ``ok``, topped up with
    in-flight ones that have served a token; the longest is always in."""
    ok = [t for t in loop.done if t.req.status == "ok"]
    flying = [t for t in loop.live.values() if t.req.out_tokens]
    pool = ok if len(ok) >= k else ok + flying
    if not pool:
        return []
    size = lambda t: len(t.req.prompt) + len(t.req.out_tokens)  # noqa: E731
    longest = max(range(len(pool)), key=lambda i: (size(pool[i]), -i))
    rest = [i for i in range(len(pool)) if i != longest]
    rng = np.random.default_rng([seed, 2])
    pick = [longest] + list(rng.permutation(rest)[: k - 1])
    return [(list(pool[i].req.prompt), list(pool[i].req.out_tokens), pool[i].rows)
            for i in pick]


def ref_shape(cell: Cell) -> tuple[int, int]:
    """The reference's fixed block: the sample size by the traffic's
    longest sequence, rounded up to 128, so it compiles once per cell."""
    t = cell.traffic
    longest = t["prompt"]["max"] + t["output"]["max"]
    return int(t["sample_requests"]), -(-longest // 128) * 128


def reference_rows(cell: Cell, seed: int, sample, lowp: str | None = None):
    """The reference's logits rows at every served position of the
    sample, and the (sequence, position, token) of each; ``lowp``
    computes them in that lower precision (the control)."""
    seqs, targets = reference.served_targets([(p, t) for p, t, _ in sample])
    if not targets:
        return None, targets
    ref = reference.Reference(cell.model, lowp=lowp)
    return ref.logits_at(seed32(seed), seqs, targets, ref_shape(cell)), targets


def compare(ref, rows, tokens) -> dict[str, float]:
    """The numbers the check compares, over every served token of the
    sample, against the float32 reference rows ``ref`` at the same
    positions:

    * ``logit_nmse_max``: the widest share of a reference row's energy
      (about its mean) by which the compared row departs from it;
    * ``served_gap_max``: the widest gap by which a served token's
      reference logit lies below the reference's best.
    """
    if ref is None:
        return {"logit_nmse_max": float("inf"), "served_gap_max": float("inf")}
    return {"logit_nmse_max": float(reference.nmse(rows, ref).max()),
            "served_gap_max": float(reference.gaps(ref, tokens).max())}


def served_rows(sample, vocab: int) -> np.ndarray:
    """The logits rows the timed path produced for the sample's tokens,
    in the order of :func:`reference.served_targets`."""
    return np.stack([r[:vocab] for _, _, rs in sample for r in rs])


def program_readings(cell: Cell, seed: int, sample) -> dict[str, float]:
    """:func:`compare` of the rows the timed path produced and the tokens
    it served."""
    ref, targets = reference_rows(cell, seed, sample)
    if ref is None:
        return compare(None, None, None)
    return compare(ref, served_rows(sample, ref.shape[1]), [t[2] for t in targets])


# -- one run ---------------------------------------------------------------


@dataclasses.dataclass
class Served:
    window: Window
    loop_steps: list[StepRecord]
    sample: list
    attempted: int
    failed: int
    trace: xplane.Reduced | None
    n_steps: int


def serve(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
          devices) -> Served:
    """Set-up, window and (traced) stretch; returns host data only, so the
    engine and its weights are freed when this returns."""
    from repro.serving import EngineConfig, build_engine

    model, spec = cell.model, cell.traffic
    params = make_packed_params(model, seed)
    check_layout(model, params)
    eng = build_engine(model.cfg, EngineConfig(**model.engine), params=params, quant=None)
    del params
    eng.warmup()
    traffic = Traffic(spec, seed, model.cfg.vocab)
    loop = ClosedLoop(eng, traffic)
    loop.start()
    loop.step()
    # what set-up left (modules, traced programs, caches) is frozen out of
    # the cyclic collector, as a server does once it is warm, so that no
    # full collection over it stalls a step in the window
    gc.collect()
    gc.freeze()
    t0 = time.monotonic()
    setup_s = t0 - t_start
    deadline = t0 + seconds
    trace_at = t0 + seconds / 3 if trace else None
    red, t1 = None, t0
    while time.monotonic() < deadline:
        if trace_at is not None and time.monotonic() >= trace_at:
            red = traced_stretch(loop, int(spec["trace_steps"]), len(devices))
            trace_at = None
        else:
            t1 = loop.step().t1
    if trace and red is None:
        red = traced_stretch(loop, int(spec["trace_steps"]), len(devices))
    t1 = max(t1, loop.steps[-1].t1)
    peak = memory_peak(devices)
    win = window_stats(loop, t0, t1, peak, setup_s)
    attempted = sum(1 for t in loop.tracked() if t.t_submit <= t1)
    failed = sum(1 for t in loop.done if t.req.status in BAD_STATUSES)
    sample = pick_sample(loop, int(spec["sample_requests"]), seed)
    n_steps = eng.n_steps
    loop.eng = None
    del eng
    gc.unfreeze()
    gc.collect()
    return Served(win, loop.steps, sample, attempted, failed, red, n_steps)


def traced_stretch(loop: ClosedLoop, n_steps: int, n_devices: int) -> xplane.Reduced:
    """Profile ``n_steps`` steps of the running loop and reduce the trace."""
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
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
    return xplane.reduce(device, host)


@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric reader may read."""

    cell: Cell
    trace: xplane.Reduced
    steps: list[StepRecord]  # the traced steps
    peaks: dict
    window: list[StepRecord] = dataclasses.field(default_factory=list)  # every step in the window

    @property
    def dims(self):
        return self.cell.model.dims


def measure(cell: Cell, seed: int, seconds: float, trace: bool, peaks: dict,
            t_start: float, devices) -> dict:
    """One run; returns the result line as a dict (``checks`` last)."""
    s = serve(cell, seed, seconds, trace, t_start, devices)
    read = program_readings(cell, seed, s.sample)
    read["failed_requests"] = s.failed
    checks = {name: {"value": read[name], "limit": float(lim["limit"])}
              for name, lim in cell.limits.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": s.window.memory_peak_bytes}
    if trace:
        kind, ctx, wanted = "metrics", LayerContext(
            cell, s.trace, [r for r in s.loop_steps if r.traced], peaks,
            [r for r in s.loop_steps if r.t0 >= s.window.t0]), cell.per_layer
        device.update(busy_s=s.trace.busy_s, window_s=s.trace.window_s)
    else:
        kind, ctx, wanted = "e2e", s.window, cell.end_to_end
    metrics = {}
    for m in wanted:
        v = reader(kind, m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {"correct": correct, "attempted": s.attempted, "failed": s.failed,
           "metrics": metrics, "device": device}
    if trace:
        kernels = {"packed_matmul": counts.PACKED_KERNEL}
        out["breakdown"] = {"device_ops": xplane.top_ops(s.trace, names=kernels),
                            "idle_gaps": [[n, v] for n, v in s.trace.gaps[:10]]}
    out["checks"] = checks
    return out


def emit(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
