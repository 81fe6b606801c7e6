"""Serving benchmark: continuous batching vs the static fixed-batch loop,
and chunked on-demand admission vs worst-case reservation.

Synthetic Poisson-arrival workloads (exponential inter-arrival gaps,
mixed prompt/generation lengths) driven through the SAME jitted paged
decode step under competing scheduler configurations:

* policy sweep — ``continuous`` (slots refill the moment a sequence
  finishes) vs ``static`` (gang admission: the whole batch must drain
  before any waiting request starts);
* long-prompt admit sweep — ``reserve`` (worst-case pages at admit,
  one-token prefill: the PR-2 engine) vs ``chunked on-demand``
  (multi-token prefill chunks + just-in-time pages with lowest-progress
  preemption) on a long-prompt mix under a deliberately tight page pool,
  where reservation head-of-line blocking shows up directly in TTFT.

* chaos sweep — the deterministic fault injector
  (:mod:`repro.serving.chaos`) armed at rate >= 0.2 for all three fault
  families (step faults, transient allocation failures, NaN-poisoned
  logits) on BOTH an attention and an SSM arch, under the virtual clock;
  every surviving request is compared token-for-token against a
  fault-free reference run of the identical workload, and page/slot
  accounting is checked for leaks;
* deadline sweep — a mixed-SLO workload (interactive / standard / batch
  classes plus a pre-run cancellation) over a bounded waiting queue
  under backlog, reporting per-class completion, shed reasons, and
  deadline compliance of every ``ok`` request.

Every cell reports generated tokens/s, p50/p99 end-to-end request
latency, p50/p99 TTFT, preemption count, and mean slot occupancy.
Results land in ``BENCH_serving.json`` at the repo root (committed PR
over PR); ``--smoke`` runs one backlogged rate per sweep and writes
``BENCH_serving_smoke.json`` instead so CI can never clobber the
committed trajectory file.  ``--smoke --chaos`` runs ONLY the chaos +
deadline sweeps and writes ``BENCH_serving_chaos_smoke.json`` (the CI
chaos gate); full runs always include them.  Flags that a mode ignores
are *errors*, not silent no-ops, and every scenario a mode skips is
logged explicitly (``skipped,...`` lines + the artifact's ``skipped``
list) — a CI smoke run measures exactly what it claims.

``--trace`` additionally exports Chrome trace JSON (Perfetto-loadable)
under ``artifacts/traces/``: a traced lifecycle run per engine family
(and, with ``--chaos``, the chaos arm's trace, whose injection events
the trace gate reconciles against the injected-fault counters).

``--smoke --attrib`` runs ONLY the in-situ attribution + live-telemetry
sweep: both engine families with per-layer attribution sampling armed
and a telemetry endpoint scraped mid-run (every scrape parsed by the
``repro.obs.promcheck`` conformance checker), writing
``BENCH_serving_attrib_smoke.json`` for the ``--kind attrib`` gate.

  python benchmarks/serving_bench.py                 # full sweep (3 rates)
  python benchmarks/serving_bench.py --rates 8,64    # custom full sweep
  python benchmarks/serving_bench.py --smoke         # CI artifact
  python benchmarks/serving_bench.py --smoke --trace # CI trace artifact
  python benchmarks/serving_bench.py --smoke --chaos # CI chaos artifact
  python benchmarks/serving_bench.py --smoke --attrib # CI obs artifact
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(_ROOT), str(_ROOT / "src")):  # support `python benchmarks/serving_bench.py`
    if _p not in sys.path:
        sys.path.insert(0, _p)

BENCH_JSON = _ROOT / "BENCH_serving.json"
BENCH_JSON_SMOKE = _ROOT / "BENCH_serving_smoke.json"  # never the committed file
BENCH_JSON_CHAOS_SMOKE = _ROOT / "BENCH_serving_chaos_smoke.json"  # chaos CI gate
BENCH_JSON_ATTRIB_SMOKE = _ROOT / "BENCH_serving_attrib_smoke.json"  # obs CI gate
BENCH_JSON_MESH_SMOKE = _ROOT / "BENCH_serving_mesh_smoke.json"  # mesh CI gate
TRACES_DIR = _ROOT / "artifacts" / "traces"  # --trace output (CI-gated, not committed)

# the long-prompt admit sweep's chunk budget (on-demand arm)
CHUNK_TOKENS = 8

# chaos sweep: every fault family injected at this rate (the CI gate
# requires >= 0.2), on one attention and one SSM arch
CHAOS_RATE = 0.2
CHAOS_ARCHS = (("llama3.2-3b", "attn"), ("mamba2-130m", "ssm"))

# attrib sweep: in-situ attribution sampling period (engine steps)
ATTRIB_EVERY = 2


def make_workload(
    n_requests: int,
    rate: float,
    *,
    seed: int,
    vocab: int,
    prompt_range: tuple[int, int] = (4, 24),
    gen_range: tuple[int, int] = (4, 64),
) -> list[dict]:
    """Poisson arrivals with mixed lengths (where slots free early)."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, size=n_requests)
    arrivals = np.cumsum(gaps)
    out = []
    for i in range(n_requests):
        p_len = int(rng.integers(*prompt_range))
        out.append(
            {
                "prompt": rng.integers(1, vocab, size=p_len).tolist(),
                "max_new_tokens": int(rng.integers(*gen_range)),
                "arrival": float(arrivals[i]),
            }
        )
    return out


def run_engine(arch: str, workload: list[dict], *, n_slots: int, page_size: int,
               max_len: int, packed_head: bool = False, policy: str = "continuous",
               admit: str = "reserve", chunk_tokens: int = 1, n_pages: int = 0) -> dict:
    from repro.configs import get_config
    from repro.serving import EngineConfig, build_engine

    cfg = get_config(arch, smoke=True)
    eng = build_engine(
        cfg,
        EngineConfig(
            n_slots=n_slots, page_size=page_size, max_len=max_len,
            n_pages=n_pages, policy=policy, admit=admit,
            chunk_tokens=chunk_tokens, packed_head=packed_head,
        ),
    )
    for w in workload:
        eng.submit(w["prompt"], w["max_new_tokens"], arrival=w["arrival"])
    eng.warmup()  # compile outside the timed run; every arm starts hot
    return eng.run(realtime=True)


ROW_KEYS = (
    "engine", "admit", "chunk_tokens", "tokens_per_s", "latency_p50",
    "latency_p99", "ttft_p50", "ttft_p99", "steps", "slot_occupancy",
    "generated_tokens", "preemptions", "wall",
)


def policy_sweep(args, rates: list[float], n_requests: int) -> tuple[list[dict], dict]:
    """continuous vs static gang admission on the mixed-length workload."""
    from repro.configs import get_config

    vocab = get_config(args.arch, smoke=True).vocab
    results = []
    for rate in rates:
        for policy in ("static", "continuous"):
            # identical workload per policy: same seed => same arrivals/lengths
            wl = make_workload(n_requests, rate, seed=args.seed, vocab=vocab)
            m = run_engine(
                args.arch, wl, n_slots=args.slots, page_size=args.page_size,
                max_len=args.max_len, packed_head=args.packed_head, policy=policy,
            )
            row = {"rate_rps": rate, "n_requests": n_requests,
                   **{k: m[k] for k in ROW_KEYS}}
            results.append(row)
            print(
                f"serve_{policy}_rate{rate:g},{m['tokens_per_s']:.1f},"
                f"p50={m['latency_p50']:.2f}s;p99={m['latency_p99']:.2f}s;"
                f"occupancy={m['slot_occupancy']:.2f};steps={m['steps']}"
            )
    speedups = {}
    for rate in rates:
        by = {r["engine"]: r for r in results if r["rate_rps"] == rate}
        speedups[str(rate)] = round(
            by["continuous"]["tokens_per_s"] / by["static"]["tokens_per_s"], 3
        )
        print(f"speedup_rate{rate:g},0.0,continuous/static={speedups[str(rate)]}x")
    return results, speedups


def long_prompt_sweep(args, rates: list[float], n_requests: int, smoke: bool
                      ) -> tuple[list[dict], dict, dict]:
    """reserve-at-admit vs chunked on-demand under a tight page pool.

    Long prompts make one-token prefill the TTFT wall and worst-case
    reservation the occupancy wall; the pool is sized so only ~2 worst
    cases fit at once, forcing the on-demand arm to actually preempt.
    The geometry is therefore PINNED here (and recorded in the artifact
    under ``long_prompt.workload``), not taken from --slots/--page-size/
    --max-len, which shape only the policy sweep; --packed-head applies
    to both sweeps.
    """
    from repro.configs import get_config

    vocab = get_config(args.arch, smoke=True).vocab
    if smoke:
        shape = dict(prompt_range=(16, 33), gen_range=(4, 13), max_len=64,
                     page_size=8, n_pages=13, n_slots=4)
    else:
        shape = dict(prompt_range=(24, 57), gen_range=(4, 25), max_len=96,
                     page_size=8, n_pages=21, n_slots=4)
    arms = (
        {"admit": "reserve", "chunk_tokens": 1, "name": "reserve"},
        {"admit": "on-demand", "chunk_tokens": CHUNK_TOKENS, "name": "chunked-on-demand"},
    )
    results = []
    for rate in rates:
        for arm in arms:
            wl = make_workload(
                n_requests, rate, seed=args.seed + 1, vocab=vocab,
                prompt_range=shape["prompt_range"], gen_range=shape["gen_range"],
            )
            m = run_engine(
                args.arch, wl, n_slots=shape["n_slots"], page_size=shape["page_size"],
                max_len=shape["max_len"], n_pages=shape["n_pages"],
                packed_head=args.packed_head,
                admit=arm["admit"], chunk_tokens=arm["chunk_tokens"],
            )
            row = {"rate_rps": rate, "n_requests": n_requests, "arm": arm["name"],
                   **{k: m[k] for k in ROW_KEYS}}
            results.append(row)
            print(
                f"longprompt_{arm['name']}_rate{rate:g},{m['tokens_per_s']:.1f},"
                f"ttft_p99={m['ttft_p99']:.2f}s;preemptions={m['preemptions']};"
                f"occupancy={m['slot_occupancy']:.2f}"
            )
    ttft_ratio = {}
    for rate in rates:
        by = {r["arm"]: r for r in results if r["rate_rps"] == rate}
        ttft_ratio[str(rate)] = round(
            by["chunked-on-demand"]["ttft_p99"] / by["reserve"]["ttft_p99"], 3
        )
        print(
            f"longprompt_ttft_rate{rate:g},0.0,"
            f"on-demand/reserve_p99_ttft={ttft_ratio[str(rate)]}x"
        )
    return results, ttft_ratio, shape


def _lifecycle_engine(arch: str, *, chaos=None, **ecfg_kw):
    """Engine under the deterministic virtual clock (chaos/deadline sweeps)."""
    from repro.configs import get_config
    from repro.serving import EngineConfig, build_engine

    cfg = get_config(arch, smoke=True)
    return build_engine(cfg, EngineConfig(**ecfg_kw), chaos=chaos)


def trace_sweep(args, smoke: bool) -> dict:
    """Traced end-to-end run on BOTH engine families (the trace-smoke gate).

    The chaos sweep's tight on-demand geometry (without chaos) guarantees
    the trace exercises preemption/requeue alongside the ordinary
    queued → prefill-chunk → decode → ok lifecycle; the exported Chrome
    traces land under ``artifacts/traces/`` and must pass every
    ``check_invariants.py --kind trace`` gate (terminal-span uniqueness,
    span nesting, step-count == metrics, injection accounting).
    """
    from repro.configs import get_config

    n_requests = 8 if smoke else 16
    shape = dict(n_slots=4, page_size=8, max_len=32, n_pages=9,
                 admit="on-demand", chunk_tokens=4)
    out = {}
    for arch, family in CHAOS_ARCHS:
        vocab = get_config(arch, smoke=True).vocab
        # long-ish sequences: worst case 4 pages/slot vs 8 usable pages, so
        # the pool oversubscribes and the trace records organic
        # preemption/requeue alongside the ordinary lifecycle
        wl = make_workload(n_requests, 2.0, seed=args.seed + 5, vocab=vocab,
                           prompt_range=(8, 17), gen_range=(8, 16))
        eng = _lifecycle_engine(arch, **shape)
        for w in wl:
            eng.submit(w["prompt"], w["max_new_tokens"], arrival=w["arrival"])
        eng.warmup()
        path = TRACES_DIR / f"trace_serving_{family}.json"
        m = eng.run(realtime=False, trace=str(path))
        out[family] = {
            "path": str(path.relative_to(_ROOT)),
            "steps": m["steps"],
            "statuses": m["statuses"],
            "preemptions": m["preemptions"],
        }
        print(
            f"trace_{family},0.0,steps={m['steps']};"
            f"preemptions={m['preemptions']};path={out[family]['path']}"
        )
    return out


def chaos_sweep(args, smoke: bool) -> list[dict]:
    """All three fault families at ``CHAOS_RATE`` on attn + ssm archs.

    Each arch runs the SAME workload twice under the virtual clock: once
    fault-free (the greedy reference) and once with the injector armed.
    The tight on-demand page pool forces organic preemptions on top of
    the injected ones, so fault recovery composes with the PR-5 replay
    machinery rather than being tested in isolation.  Every ``ok``
    request must match the reference token-for-token, and the drained
    engine must hold zero leaked pages/slots — exactly what the
    ``check_invariants.py`` chaos gate enforces on this artifact.
    """
    from repro.configs import get_config
    from repro.serving import ChaosConfig

    n_requests = 8 if smoke else 16
    # geometry: worst case 3 pages/request vs 8 usable => preemption under
    # load; max_request_retries is generous because a NaN strike costs a
    # replay (correctness), not a failure — "failed" is for giving up
    shape = dict(n_slots=4, page_size=8, max_len=32, n_pages=9,
                 admit="on-demand", chunk_tokens=4, max_request_retries=64)
    rows = []
    for arch, family in CHAOS_ARCHS:
        vocab = get_config(arch, smoke=True).vocab
        wl = make_workload(n_requests, 2.0, seed=args.seed + 2, vocab=vocab,
                           prompt_range=(4, 13), gen_range=(4, 11))

        def run_one(chaos, trace=None):
            eng = _lifecycle_engine(arch, chaos=chaos, **shape)
            for w in wl:
                eng.submit(w["prompt"], w["max_new_tokens"], arrival=w["arrival"])
            eng.warmup()
            m = eng.run(realtime=False, trace=trace)
            return eng, m

        ref_eng, ref_m = run_one(None)
        assert ref_m["statuses"] == {"ok": n_requests}, (
            f"fault-free reference must complete everything: {ref_m['statuses']}"
        )
        ref_out = {r.rid: list(r.out_tokens) for r in ref_eng.finished}
        chaos = ChaosConfig(seed=args.seed + 3, step_fault_rate=CHAOS_RATE,
                            alloc_fault_rate=CHAOS_RATE, nan_rate=CHAOS_RATE)
        # the chaos arm is the traced one: its trace must carry exactly one
        # injection event per counted injected fault (the chaos trace gate)
        trace_path = TRACES_DIR / f"trace_chaos_{family}.json" if args.trace else None
        eng, m = run_one(chaos, trace=str(trace_path) if trace_path else None)
        mismatch = sum(
            1 for r in eng.finished
            if r.status == "ok" and r.out_tokens != ref_out[r.rid]
        )
        row = {
            "arch": arch, "family": family, "fault_rate": CHAOS_RATE,
            "n_requests": n_requests,
            "statuses": m["statuses"],
            "n_token_mismatch": mismatch,
            "leaked_pages": eng.allocator.n_usable - eng.allocator.n_free,
            "leaked_slots": eng.ecfg.n_slots - eng.scheduler.n_free_slots,
            "injected": m["injected"],
            "step_retries": m["step_retries"],
            "quarantines": m["quarantines"],
            "hard_recoveries": m["hard_recoveries"],
            "preemptions": m["preemptions"],
            "steps": m["steps"],
            "ref_steps": ref_m["steps"],
            "generated_tokens_ok": m["generated_tokens_ok"],
        }
        if trace_path is not None:
            row["trace"] = str(trace_path.relative_to(_ROOT))
        rows.append(row)
        print(
            f"chaos_{family},0.0,"
            f"injected={m['injected']};statuses={m['statuses']};"
            f"mismatch={mismatch};quarantines={m['quarantines']};"
            f"steps={m['steps']}(ref {ref_m['steps']})"
        )
    return rows


def _scrape_loop(url: str, stop, out: dict) -> None:
    """Background scraper: poll /metrics + /livez until told to stop,
    recording scrape counts, conformance violations, and livez shape."""
    import urllib.request

    from repro.obs.promcheck import check_exposition

    while True:
        try:
            text = urllib.request.urlopen(url + "/metrics", timeout=5).read().decode()
            errs = check_exposition(text)
            out["n_scrapes"] += 1
            if errs:
                out["parse_errors"].extend(errs[:5])
            live = json.loads(
                urllib.request.urlopen(url + "/livez", timeout=5).read().decode()
            )
            if not isinstance(live.get("steps"), int):
                out["livez_ok"] = False
        except Exception as exc:  # noqa: BLE001 — recorded, gated on
            out["scrape_errors"].append(f"{type(exc).__name__}: {exc}")
        if stop.is_set():
            return  # final post-run scrape already done
        stop.wait(0.002)


def attrib_sweep(args, smoke: bool) -> list[dict]:
    """In-situ attribution + live telemetry on BOTH engine families.

    Each family runs the trace sweep's tight on-demand geometry with
    attribution sampling every ``ATTRIB_EVERY`` steps and a
    :class:`repro.obs.server.TelemetryServer` attached; a scraper thread
    polls ``/metrics`` and ``/livez`` *mid-run*, validating every scrape
    under the :mod:`repro.obs.promcheck` conformance parser.  The
    artifact records the raw attribution samples (per-layer seconds +
    shares), the attribution counters, the Perfetto counter-track
    series, and the scrape results — everything the
    ``check_invariants.py --kind attrib`` gate needs: shares sum to 1
    per sampled step, sampled-step count equals the attrib counter,
    every served layer attributed, monotone counter tracks, clean
    scrapes.
    """
    from repro.configs import get_config
    from repro.obs.server import TelemetryServer

    n_requests = 8 if smoke else 16
    shape = dict(n_slots=4, page_size=8, max_len=32, n_pages=9,
                 admit="on-demand", chunk_tokens=4,
                 attrib_every=ATTRIB_EVERY)
    rows = []
    for arch, family in CHAOS_ARCHS:
        cfg = get_config(arch, smoke=True)
        wl = make_workload(n_requests, 2.0, seed=args.seed + 6, vocab=cfg.vocab,
                           prompt_range=(8, 17), gen_range=(8, 16))
        eng = _lifecycle_engine(arch, **shape)
        for w in wl:
            eng.submit(w["prompt"], w["max_new_tokens"], arrival=w["arrival"])
        eng.warmup()
        path = TRACES_DIR / f"trace_attrib_{family}.json"

        def trace_segment(since, eng=eng):
            tr = eng._trace
            return tr.segment(since) if tr is not None else ([], since, 0)

        import threading

        scrape = {"n_scrapes": 0, "parse_errors": [], "scrape_errors": [],
                  "livez_ok": True}
        stop = threading.Event()
        with TelemetryServer(metrics_fn=eng.prometheus_text,
                             livez_fn=eng.live_metrics,
                             trace_fn=trace_segment) as srv:
            t = threading.Thread(target=_scrape_loop,
                                 args=(srv.url, stop, scrape), daemon=True)
            t.start()
            m = eng.run(realtime=False, trace=str(path))
            stop.set()  # loop does one final post-run scrape, then exits
            t.join(timeout=10.0)
        # counter-track series, in emission order, straight from the
        # sealed trace file (what Perfetto will actually plot)
        trace_doc = json.loads(path.read_text())
        counters: dict[str, list[dict]] = {}
        for e in trace_doc["traceEvents"]:
            if e.get("ph") == "C":
                counters.setdefault(e["name"], []).append(e["args"])
        at = eng._attrib
        row = {
            "arch": arch,
            "family": family,
            "attrib_every": ATTRIB_EVERY,
            "n_layers": cfg.n_layers,
            "steps": m["steps"],
            "statuses": m["statuses"],
            "preemptions": m["preemptions"],
            "attrib_steps": eng.registry.counter("repro_attrib_steps_total").value(),
            "n_samples": len(at.samples),
            "samples": at.samples,
            "summary": at.summary(),
            "counter_tracks": counters,
            "telemetry": scrape,
            "trace": str(path.relative_to(_ROOT)),
        }
        rows.append(row)
        print(
            f"attrib_{family},0.0,steps={m['steps']};"
            f"samples={len(at.samples)};scrapes={scrape['n_scrapes']};"
            f"parse_errors={len(scrape['parse_errors'])};path={row['trace']}"
        )
    return rows


def deadline_sweep(args, smoke: bool) -> dict:
    """Mixed-SLO workload over a bounded queue under backlog.

    Three classes round-robin across a backlogged Poisson workload on
    the virtual clock: ``interactive`` (tight TTFT + total budgets),
    ``standard`` (loose total budget), ``batch`` (unbounded).  The
    waiting queue is bounded, so overflow sheds the least-slack request;
    one batch request is cancelled before the run to exercise the
    cooperative-cancel path.  The gate: every ``ok`` request met its
    deadline, at least one request was shed (the sweep is sized to
    overload), and every request carries a terminal status.
    """
    from collections import Counter

    from repro.configs import get_config
    from repro.obs.metrics import percentile
    from repro.serving import SLO

    vocab = get_config(args.arch, smoke=True).vocab
    n_requests = 12 if smoke else 24
    classes = (
        SLO("interactive", ttft_budget=10.0, total_budget=26.0),
        SLO("standard", total_budget=150.0),
        SLO("batch"),
    )
    wl = make_workload(n_requests, 4.0, seed=args.seed + 4, vocab=vocab,
                       prompt_range=(4, 13), gen_range=(8, 17))
    eng = _lifecycle_engine(
        args.arch, n_slots=2, page_size=8, max_len=32,
        chunk_tokens=4, max_waiting=6,
    )
    reqs = []
    for i, w in enumerate(wl):
        reqs.append(eng.submit(w["prompt"], w["max_new_tokens"],
                               arrival=w["arrival"], slo=classes[i % len(classes)]))
    cancelled = next(r for r in reqs if r.slo == "batch")
    eng.cancel(cancelled)  # pre-run cancellation, honoured at first policing
    eng.warmup()
    m = eng.run(realtime=False)

    per_class = []
    for slo in classes:
        mine = [r for r in eng.finished if r.slo == slo.name]
        ok = [r for r in mine if r.status == "ok"]
        ttfts = [r.t_first_token - r.arrival for r in ok if r.t_first_token is not None]
        per_class.append({
            "slo": slo.name,
            "ttft_budget": slo.ttft_budget,
            "total_budget": slo.total_budget,
            "n": len(mine),
            "n_ok": len(ok),
            "n_shed": sum(1 for r in mine if r.status == "shed"),
            "n_cancelled": sum(1 for r in mine if r.status == "cancelled"),
            "shed_reasons": dict(Counter(
                r.shed_reason for r in mine if r.status == "shed")),
            "deadline_violations_ok": sum(
                1 for r in ok
                if r.deadline is not None and r.t_finish > r.deadline
            ),
            "ttft_p50": percentile(ttfts, 50),  # None-never-NaN contract
        })
        print(
            f"deadline_{slo.name},0.0,"
            f"ok={per_class[-1]['n_ok']}/{per_class[-1]['n']};"
            f"shed={per_class[-1]['n_shed']};"
            f"violations={per_class[-1]['deadline_violations_ok']}"
        )
    return {
        "n_requests": n_requests,
        "max_waiting": 6,
        "statuses": m["statuses"],
        "classes": per_class,
    }


def mesh_sweep(args, smoke: bool) -> dict:
    """Mesh-parallel serving A/B on BOTH engine families (the mesh gate).

    The SAME backlogged workload runs through three engine arms built by
    the one :func:`repro.serving.api.build_engine` front door: single
    (``dp=mp=1``), data-parallel only (``dp``, per-replica dispatch of
    the identical compiled step — bit-exact), and the full ``dp x mp``
    ``shard_map`` mesh (sliced-then-packed weights, one psum per block).
    Arms run under the virtual clock (tokens per virtual time unit, so
    the dp speedup is a scheduling fact, not host noise) with f32 compute
    as the identity oracle: greedy tokens must match the single-device
    arm request-for-request, and every replica must drain with zero
    leaked pages/slots — exactly what ``check_invariants.py --kind
    mesh`` enforces on this artifact.
    """
    import dataclasses

    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.serving import EngineConfig, MeshConfig, build_engine

    mesh = MeshConfig.parse(args.mesh)
    n_requests = 8 if smoke else 16
    shape = dict(n_slots=4, page_size=8, max_len=32, chunk_tokens=4)
    arms = [("single", MeshConfig())]
    if mesh.dp > 1:
        arms.append((f"dp{mesh.dp}", MeshConfig(dp=mesh.dp)))
    if mesh.mp > 1:
        arms.append((f"{mesh.dp}x{mesh.mp}", mesh))
    rows = []
    for arch, family in CHAOS_ARCHS:
        # f32 compute: the mesh arm's psum/slice numerics stay far inside
        # the greedy-argmax tie margin, so token identity is a hard gate
        cfg = dataclasses.replace(get_config(arch, smoke=True), dtype=jnp.float32)
        wl = make_workload(n_requests, 4.0, seed=args.seed + 7, vocab=cfg.vocab,
                           prompt_range=(4, 13), gen_range=(4, 11))
        arm_rows, tokens_by_arm = [], {}
        for name, mcfg in arms:
            eng = build_engine(cfg, EngineConfig(mesh=mcfg, **shape))
            for w in wl:
                eng.submit(w["prompt"], w["max_new_tokens"], arrival=w["arrival"])
            eng.warmup()
            m = eng.run(realtime=False)
            tokens_by_arm[name] = {r.rid: list(r.out_tokens) for r in eng.finished}
            arm_rows.append({
                "arm": name, "dp": eng.dp, "mp": eng.mp,
                "tokens_per_s": m["tokens_per_s"],
                "steps": m["steps"],
                "statuses": m["statuses"],
                "preemptions": m["preemptions"],
                "replica_quarantines": m["replica_quarantines"],
                "leaked_pages_per_replica": [
                    rep.allocator.n_usable - rep.allocator.n_free
                    for rep in eng.replicas
                ],
                "leaked_slots_per_replica": [
                    eng.ecfg.n_slots - rep.scheduler.n_free_slots
                    for rep in eng.replicas
                ],
            })
        ref = tokens_by_arm["single"]
        for row in arm_rows:
            row["token_identical"] = tokens_by_arm[row["arm"]] == ref
        base_tps = arm_rows[0]["tokens_per_s"]
        row = {
            "arch": arch, "family": family, "n_requests": n_requests,
            "workload": {k: list(v) if isinstance(v, tuple) else v
                         for k, v in shape.items()},
            "arms": arm_rows,
            "dp_speedup": {
                r["arm"]: round(r["tokens_per_s"] / base_tps, 3)
                for r in arm_rows[1:]
            },
        }
        rows.append(row)
        for r in arm_rows:
            print(
                f"mesh_{family}_{r['arm']},{r['tokens_per_s']:.1f},"
                f"steps={r['steps']};identical={r['token_identical']};"
                f"leaks={sum(r['leaked_pages_per_replica'])}"
            )
    return {"spec": args.mesh, "dp": mesh.dp, "mp": mesh.mp, "results": rows}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="one backlogged rate per sweep (CI artifact)")
    ap.add_argument("--chaos", action="store_true",
                    help="with --smoke: run ONLY the chaos + deadline sweeps "
                    "and write BENCH_serving_chaos_smoke.json (the CI chaos "
                    "gate); full runs always include those sweeps")
    ap.add_argument("--attrib", action="store_true",
                    help="with --smoke: run ONLY the in-situ attribution + "
                    "live-telemetry sweep and write "
                    "BENCH_serving_attrib_smoke.json (the CI obs gate)")
    ap.add_argument("--mesh", metavar="DPxMP", default=None,
                    help="with --smoke: run ONLY the mesh-parallel A/B "
                    "(single vs dp vs dp x mp engine arms, token-identity "
                    "checked) and write BENCH_serving_mesh_smoke.json (the "
                    "CI mesh gate); MP > 1 needs DP*MP JAX devices")
    ap.add_argument("--rates", default=None,
                    help="comma-separated arrival rates for the full sweep "
                    "(incompatible with --smoke, which fixes its rate)")
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--requests", type=int, default=0, help="0 = per-mode default")
    ap.add_argument("--packed-head", action="store_true")
    ap.add_argument("--trace", action="store_true",
                    help="export Chrome traces under artifacts/traces/: a "
                    "traced lifecycle run per engine family (plus, with "
                    "--chaos, the chaos arm's injection trace)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.smoke and args.rates is not None:
        # never silently ignore a flag: a smoke run that *looked* like it
        # measured --rates would let a regression at those rates merge green
        ap.error("--smoke fixes the rate sweep; drop --rates (or drop --smoke)")
    if args.chaos and not args.smoke:
        # full runs ALWAYS include the chaos + deadline sweeps; --chaos
        # exists only to carve out the focused CI smoke artifact
        ap.error("--chaos selects the chaos-only smoke artifact; add --smoke "
                 "(full runs include the chaos sweep unconditionally)")
    if args.attrib and not args.smoke:
        ap.error("--attrib selects the attribution-only smoke artifact; add "
                 "--smoke")
    if args.attrib and args.chaos:
        ap.error("--attrib and --chaos write different CI artifacts; pick one")
    if args.attrib and args.trace:
        ap.error("--attrib always writes its own traces (trace_attrib_*.json); "
                 "drop --trace")
    if args.mesh is not None:
        if not args.smoke:
            ap.error("--mesh selects the mesh-only smoke artifact; add --smoke")
        if args.chaos or args.attrib or args.trace:
            ap.error("--mesh writes its own CI artifact; drop "
                     "--chaos/--attrib/--trace")
        import os

        # parse the spec with string ops only: importing repro.serving here
        # would pull in jax before XLA_FLAGS is set
        parts = [int(p) for p in args.mesh.lower().split("x")]
        mesh_mp = parts[1] if len(parts) > 1 else 1
        if mesh_mp > 1 and "jax" not in sys.modules:
            # shard_map arms need dp*mp devices; force host devices before
            # the first jax import (the CI job also sets this in its env)
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags + " --xla_force_host_platform_device_count=8"
                ).strip()

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    skipped: list[str] = []  # every scenario a mode drops, logged explicitly
    print("name,tokens_per_s,derived")

    if args.mesh is not None:
        skipped += [
            "policy_sweep (mesh-only artifact; run --smoke without --mesh)",
            "long_prompt_sweep (mesh-only artifact)",
            "chaos_sweep (covered by `serving_bench.py --smoke --chaos`; "
            "mesh-vs-single identity under chaos is gated by "
            "tests/multidevice_checks.py)",
            "deadline_sweep (covered by `serving_bench.py --smoke --chaos`)",
        ]
        payload = {
            "arch": args.arch,
            "smoke": True,
            "mesh_only": True,
            "mesh": mesh_sweep(args, smoke=True),
            "skipped": skipped,
        }
        target = BENCH_JSON_MESH_SMOKE
    elif args.attrib:
        skipped += [
            "policy_sweep (attrib-only artifact; run --smoke without --attrib)",
            "long_prompt_sweep (attrib-only artifact)",
            "chaos_sweep (covered by `serving_bench.py --smoke --chaos`)",
            "deadline_sweep (covered by `serving_bench.py --smoke --chaos`)",
        ]
        payload = {
            "arch": args.arch,
            "smoke": True,
            "attrib_only": True,
            "attrib": attrib_sweep(args, smoke=True),
            "skipped": skipped,
        }
        target = BENCH_JSON_ATTRIB_SMOKE
    elif args.chaos:
        skipped += [
            "policy_sweep (chaos-only artifact; run --smoke without --chaos)",
            "long_prompt_sweep (chaos-only artifact; run --smoke without --chaos)",
        ]
        payload = {
            "arch": args.arch,
            "smoke": True,
            "chaos_only": True,
            "chaos": {"fault_rate": CHAOS_RATE,
                      "results": chaos_sweep(args, smoke=True)},
            "deadlines": deadline_sweep(args, smoke=True),
            "skipped": skipped,
        }
        if args.trace:
            payload["traces"] = {
                r["family"]: r["trace"] for r in payload["chaos"]["results"]
            }
        target = BENCH_JSON_CHAOS_SMOKE
    else:
        # low rate = arrival-bound (throughput parity, latency still wins);
        # high rate = backlogged, where slot recycling shows up in tokens/s.
        # smoke runs ONLY the backlogged rate: that is where the CI invariant
        # (continuous >= static tokens/s) actually binds
        if args.smoke:
            rates = [32.0]
            skipped.append("rates 8.0,128.0 (smoke runs only the backlogged rate)")
        elif args.rates is not None:
            rates = [float(r) for r in args.rates.split(",") if r]
            if not rates:
                ap.error("--rates got no parseable rates")
        else:
            rates = [8.0, 32.0, 128.0]
        n_requests = args.requests or (10 if args.smoke else 48)

        results, speedups = policy_sweep(args, rates, n_requests)
        lp_rates = [rates[-1]] if args.smoke else rates
        lp_requests = max(6, n_requests // 2) if args.smoke else n_requests // 2
        lp_results, ttft_ratio, lp_shape = long_prompt_sweep(
            args, lp_rates, lp_requests, args.smoke
        )

        payload = {
            "arch": args.arch,
            "slots": args.slots,
            "page_size": args.page_size,
            "max_len": args.max_len,
            "smoke": args.smoke,
            "results": results,
            "continuous_over_static_tokens_per_s": speedups,
            "long_prompt": {
                "chunk_tokens": CHUNK_TOKENS,
                # geometry pinned by the sweep itself — the top-level
                # slots/page_size/max_len describe only the policy sweep
                "workload": {**{k: list(v) if isinstance(v, tuple) else v
                                for k, v in lp_shape.items()},
                             "packed_head": args.packed_head},
                "results": lp_results,
                "on_demand_over_reserve_p99_ttft": ttft_ratio,
            },
        }
        if args.trace:
            payload["traces"] = trace_sweep(args, args.smoke)
        if args.smoke:
            # the chaos artifact is a separate CI job so a fault-injection
            # regression can't hide behind a green perf smoke (and vice versa)
            skipped += [
                "chaos_sweep (covered by `serving_bench.py --smoke --chaos`)",
                "deadline_sweep (covered by `serving_bench.py --smoke --chaos`)",
                "gather_backend=kernel arm (token identity + A/B covered by "
                "`kernel_bench.py --gather --smoke` and the paged-gather-smoke job)",
            ]
        else:
            payload["chaos"] = {"fault_rate": CHAOS_RATE,
                                "results": chaos_sweep(args, smoke=False)}
            payload["deadlines"] = deadline_sweep(args, smoke=False)
        payload["skipped"] = skipped
        target = BENCH_JSON_SMOKE if args.smoke else BENCH_JSON

    for s in skipped:
        print(f"skipped,0.0,{s}")
    target.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"bench_json,0.0,written={target.name}")


if __name__ == "__main__":
    main()
