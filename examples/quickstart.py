"""Quickstart: the DeepBurning-MixQ pipeline end to end in ~2 minutes.

1. DSP Packing Optimizer -> T_mul lookup tables (paper §IV / Fig. 4)
2. DSP-aware differentiable NAS on VGG-Tiny (paper §V / Fig. 5-6)
3. Accelerator customization via Bayesian-ridge + DP (paper §VI / Table I)
4. Bit-exact packed inference through the Pallas kernel path
5. Continuous-batching serving (paged KV + packed LM head)
6. Deployment-plan compiler: search -> autotune -> serve mixed precision
7. 1-bit overpacking: denser placements, bits recovered in-kernel (§IV-B-1)
8. Chunked prefill + on-demand admission with preemption/requeue
9. Fault-hardened serving: deadlines, cancellation, shedding, chaos
10. Observability: request/step tracing (Perfetto), live metrics, plan drift
11. In-situ per-layer attribution + live telemetry endpoint (/metrics)
12. Pallas paged-attention gather: block-table-driven KV streaming

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.customize import allocate, sample_space, train_predictors
from repro.core.nas import op_dsp, search
from repro.core.packing import DSP48E2, best_packing, build_lut, compare_luts
from repro.kernels.packed_matmul.ops import packed_dense, packed_dense_reference
from repro.models import convnets

# -- 1. packing ------------------------------------------------------------
print("== DSP Packing Optimizer ==")
for w, a in ((8, 8), (4, 4), (2, 2)):
    cfg = best_packing(DSP48E2, w, a, kernel_len=3)
    print(f"  w{w}a{a}: {cfg.t_mul:.1f} muls/DSP via {cfg.strategy} packing"
          f" (overpack={bool(cfg.overlap)}, separated={cfg.separated or 'no'})")
ours = build_lut(DSP48E2, kernel_len=3)
hik = build_lut(DSP48E2, kernel_len=3, method="hikonv")
cmp = compare_luts(ours, hik)
print(f"  vs HiKonv on 3x3: {cmp['better']}/49 cells improved, {cmp['worse']} worse")

# -- 2. NAS ------------------------------------------------------------------
print("== DSP-aware NAS (VGG-Tiny, synthetic CIFAR) ==")
luts = {k: build_lut(DSP48E2, kernel_len=k) for k in (1, 3)}
spec = convnets.vgg_tiny(in_hw=(16, 16))
res = search(spec, luts, eta=0.3, steps=60, batch=16, n_data=128)
print(f"  selected bits: {res.bits}")
full = convnets.vgg_tiny()
print(f"  Op_dsp = {op_dsp(full, res.bits, luts)/1e6:.2f}M "
      f"(uniform w4a4 = {op_dsp(full, [(4,4)]*7, luts)/1e6:.2f}M)")

# -- 3. customization --------------------------------------------------------
print("== Accelerator customization (Ultra96-V2 model) ==")
space = sample_space(full, res.bits, luts)
preds = train_predictors([c for st in space for c in st][::5])
alloc = allocate(space, preds)
alloc_lut = allocate(space, preds, allow_lut_arith=True)
print(f"  Mix-HP : {alloc.fps:8.1f} FPS  DSP={alloc.dsp_used:.0f} kLUT={alloc.lut_used/1e3:.1f}")
print(f"  Mix-LUT: {alloc_lut.fps:8.1f} FPS  DSP={alloc_lut.dsp_used:.0f} kLUT={alloc_lut.lut_used/1e3:.1f}")

# -- 4. packed kernel --------------------------------------------------------
print("== Bit-exact packed inference (Pallas, interpret mode) ==")
x = jax.random.uniform(jax.random.PRNGKey(0), (8, 64))
w = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
got = packed_dense(x, w, w_bits=2, a_bits=2)
want = packed_dense_reference(x, w, w_bits=2, a_bits=2)
print(f"  w2a2 packed matmul exact vs oracle: {np.array_equal(np.asarray(got), np.asarray(want))}")
# serving fast path: pack the weights once, then call with the packed params
from repro.kernels.packed_matmul.ops import prepack_dense

pre = prepack_dense(w, w_bits=2, a_bits=2)
got_pre = packed_dense(x, pre)
print(f"  prepacked fast path exact: {np.array_equal(np.asarray(got_pre), np.asarray(want))}")

# -- 5. serving --------------------------------------------------------------
print("== Continuous-batching serving (paged KV + packed LM head) ==")
from repro.configs import get_config
from repro.models import transformer as T
from repro.serving import Engine, EngineConfig

cfg = get_config("llama3.2-3b", smoke=True)
params = T.init_params(jax.random.PRNGKey(0), cfg)
eng = Engine(cfg, params, EngineConfig(n_slots=2, page_size=4, max_len=32,
                                       packed_head=True))
rng = np.random.default_rng(0)
for _ in range(4):
    eng.submit(rng.integers(1, cfg.vocab, size=rng.integers(2, 8)).tolist(),
               max_new_tokens=int(rng.integers(3, 8)))
eng.warmup()  # compile outside the timed run
m = eng.run(realtime=True)
print(f"  {m['n_requests']} requests, {m['generated_tokens']} tokens @ "
      f"{m['tokens_per_s']:.1f} tok/s, occupancy {m['slot_occupancy']:.2f}, "
      f"0 leaked pages: {eng.allocator.n_free == eng.allocator.n_usable}")
# same engine from the shell:
#   PYTHONPATH=src python -m repro.launch.serve --engine continuous \
#       --packed --packed-head --wbits 4 --abits 4

# -- 6. deployment plans -----------------------------------------------------
print("== Compile a deployment plan and serve it (per-layer mixed precision) ==")
from repro.plan import apply_plan, autotune_plan, search_plan, summarize

# search the per-layer bit space under a footprint budget (the packing
# LUT + cost model score candidates; artifacts land in artifacts/plans/)
plan = search_plan(cfg, arch="llama3.2-3b", objective="footprint", budget_frac=0.85)
# microbenchmark block_k per unique matmul shape on this machine
plan = autotune_plan(plan, cfg, reps=1)
plan_path = plan.save(name="quickstart")
print(f"  {summarize(plan)}")
print(f"  saved {plan_path}")
# apply: per-layer quantize + prepack (MoE + LM head included), then the
# same continuous-batching engine serves genuinely mixed precision
mp_params, mp_head = apply_plan(params, cfg, plan)
eng = Engine(cfg, mp_params, EngineConfig(n_slots=2, page_size=4, max_len=32),
             head=mp_head)
for _ in range(4):
    eng.submit(rng.integers(1, cfg.vocab, size=rng.integers(2, 8)).tolist(),
               max_new_tokens=int(rng.integers(3, 8)))
eng.warmup()
m = eng.run(realtime=True)
print(f"  {m['n_requests']} mixed-precision requests @ {m['tokens_per_s']:.1f} tok/s "
      f"({plan.n_distinct_bit_pairs} distinct bit pairs)")
# from the shell:
#   PYTHONPATH=src python -m repro.plan.compile --arch llama3.2-3b --autotune
#   PYTHONPATH=src python -m repro.launch.serve --plan artifacts/plans/<stem>.json

# -- 7. overpacking ----------------------------------------------------------
print("== 1-bit overpacking (overlap=1, paper §IV-B-1 / Fig. 3) ==")
# Overpacking steals one guard bit per segment: adjacent products share a
# bit, and the kernel recovers each stolen MSB from the *operands* — the
# true LSB of the next segment is the XOR over the accumulation chunk of
# (weight LSB AND activation LSB), computed as one extra integer dot of
# the activation LSBs against a masked view of the packed weights (bit
# d*stride of the packed word IS segment d's LSB), then a bottom-up peel.
from repro.kernels.packed_matmul.ops import choose_config

for wb, ab in ((2, 3), (4, 4)):
    sel = choose_config(wb, ab)
    base = choose_config(wb, ab, allow_overpack=False)
    what = (f"{sel.n_seg} vs {base.n_seg} weights/int32 word"
            if sel.n_seg > base.n_seg else
            f"acc_chunk {sel.acc_chunk} vs {base.acc_chunk} (half the peel rounds)")
    print(f"  w{wb}a{ab}: overpacked placement wins {what}")
# the serving path picks overpacked placements automatically: prepack
# (zero extra storage — the LSB planes are masked views) and compare
wb, ab = 2, 3  # packs 3 channels per int32 word; no-overpack tops out at 2
pre = prepack_dense(w, w_bits=wb, a_bits=ab)
got = packed_dense(x, pre)
want = packed_dense_reference(x, w, w_bits=wb, a_bits=ab)
print(f"  w{wb}a{ab} overpacked kernel bit-exact vs unpacked oracle: "
      f"{np.array_equal(np.asarray(got), np.asarray(want))} "
      f"(packed words: {pre.w_packed.shape[1]} vs {-(-w.shape[1] // 2)} no-overpack)")
# density record across all pairs: python benchmarks/packing_efficiency.py

# -- 8. chunked prefill + preemption -----------------------------------------
print("== Chunked prefill + on-demand admission with preemption/requeue ==")
# Long prompts used to stall the batch: one prompt token per step, and
# worst-case page reservation at admit left the pool under-used.  With
# chunk_tokens=C the engine feeds each prefilling slot up to C prompt
# tokens per fused step (decode slots ride along with 1 valid lane), and
# admit="on-demand" grows pages just in time — on pool exhaustion the
# lowest-progress slot is preempted: pages freed, request requeued with
# its generated prefix, replayed chunked, resuming token-identically.
long_prompt = rng.integers(1, cfg.vocab, size=24).tolist()
runs = {}
for chunk in (1, 8):
    eng = Engine(cfg, params, EngineConfig(n_slots=1, page_size=4, max_len=32,
                                           chunk_tokens=chunk))
    req = eng.submit(long_prompt, max_new_tokens=4)
    m = eng.run(realtime=False)
    runs[chunk] = (m["steps"], req.out_tokens)
print(f"  24-token prompt, 4 generated: {runs[1][0]} steps unchunked vs "
      f"{runs[8][0]} chunked (C=8); same tokens: {runs[1][1] == runs[8][1]}")
# force preemption: pool of 5 usable pages for 3 requests
eng = Engine(cfg, params, EngineConfig(n_slots=3, page_size=4, max_len=32,
                                       n_pages=6, chunk_tokens=4,
                                       admit="on-demand"))
reqs = [eng.submit(rng.integers(1, cfg.vocab, size=n).tolist(), 6)
        for n in (9, 6, 11)]
m = eng.run(realtime=False)
print(f"  undersized pool: {m['preemptions']} preemptions, all "
      f"{m['n_requests']} requests completed, 0 leaked pages: "
      f"{eng.allocator.n_free == eng.allocator.n_usable}")
# from the shell (and in benchmarks/serving_bench.py's long-prompt sweep):
#   PYTHONPATH=src python -m repro.launch.serve --chunk-tokens 8 --admit on-demand

# -- 9. fault-hardened serving ------------------------------------------------
print("== Deadlines, cancellation, load shedding, and chaos ==")
# Every request now ends in exactly one terminal status: ok | cancelled |
# shed | failed.  Deadlines come either explicit (seconds from arrival,
# resolved to absolute) or via an SLO class; the scheduler sheds work it
# can no longer serve in time instead of burning slots on it, and a
# bounded queue sheds the least-slack request on overflow.
from repro.serving import SLO, ChaosConfig

interactive = SLO("interactive", ttft_budget=10.0, total_budget=26.0)
eng = Engine(cfg, params, EngineConfig(n_slots=2, page_size=4, max_len=32,
                                       chunk_tokens=4, max_waiting=4))
doomed = eng.submit(long_prompt, 4, deadline=0.0)       # already expired
kept = [eng.submit(rng.integers(1, cfg.vocab, size=6).tolist(), 4,
                   slo=interactive) for _ in range(3)]
victim = eng.submit(rng.integers(1, cfg.vocab, size=6).tolist(), 4)
victim.cancel()                                          # user hung up
m = eng.run(realtime=False)
print(f"  statuses: {m['statuses']}  (doomed={doomed.status}, "
      f"victim={victim.status}, shed_reason={doomed.shed_reason})")
# chaos harness: seeded injected faults (step exceptions, transient alloc
# failures, NaN-poisoned logits) at rate 0.2 each — the engine retries,
# quarantines the poisoned slot, preempts/requeues, and every surviving
# request must decode token-identical to the fault-free greedy reference.
chaos = ChaosConfig(seed=0, step_fault_rate=0.2, alloc_fault_rate=0.2,
                    nan_rate=0.2)
eng = Engine(cfg, params, EngineConfig(n_slots=2, page_size=4, max_len=32,
                                       chunk_tokens=4, max_request_retries=64),
             chaos=chaos)
c_prompts = [rng.integers(1, cfg.vocab, size=n).tolist() for n in (9, 6, 11)]
c_reqs = [eng.submit(p, 5) for p in c_prompts]
m = eng.run(realtime=False)
print(f"  chaos: injected={m['injected']} retries={m['step_retries']} "
      f"quarantines={m['quarantines']} statuses={m['statuses']}")
print(f"  zero leaked pages after chaos: "
      f"{eng.allocator.n_free == eng.allocator.n_usable}")
# CI runs this harness as a gated job:
#   python benchmarks/serving_bench.py --smoke --chaos
#   python benchmarks/check_invariants.py BENCH_serving_chaos_smoke.json

# -- 10. observability --------------------------------------------------------
print("== Tracing, live metrics, and plan drift ==")
# run(trace=...) opens one async span per request (queued -> prefill ->
# decode, surviving preemption/requeue) and one X span per fused step
# split into its host phases (batch, upload, dispatch, device_wait,
# logits_copy, sample); the saved JSON loads directly in
# Perfetto (https://ui.perfetto.dev) or chrome://tracing.  Disabled
# tracing costs the hot path one `is not None` check.
import tempfile

from repro.obs.trace import TraceRecorder

eng = Engine(cfg, params, EngineConfig(n_slots=2, page_size=4, max_len=32,
                                       chunk_tokens=4))
for n in (9, 6, 11):
    eng.submit(rng.integers(1, cfg.vocab, size=n).tolist(), 5)
# live metrics mid-run: run a few steps, peek, resume — metrics() needs
# no wall argument any more (the engine tracks its own run clock)
eng.warmup()
eng.run(realtime=False, max_steps=4)
live = eng.live_metrics()
print(f"  mid-run: {live['active_slots']} active slots, "
      f"{live['tokens_per_s_window']:.1f} tok/s over the last "
      f"{live['window']:.0f} step window")
tr = TraceRecorder()
m = eng.run(realtime=False, trace=tr)       # resume, traced to the end
trace_path = tr.save(tempfile.mkdtemp() + "/quickstart_trace.json")
steps_traced = sum(1 for e in tr.events if e.get("name") == "step")
print(f"  traced {steps_traced} fused steps, "
      f"{len([e for e in tr.events if e['ph'] == 'e' and e['name'] == 'request'])} "
      f"request terminals -> {trace_path} (open in Perfetto)")
# Prometheus text exposition — scrape-ready counters/gauges/histograms
# (serve --metrics-out FILE writes the same thing)
expo = eng.prometheus_text()
print("  exposition sample: " +
      next(l for l in expo.splitlines() if l.startswith("repro_requests_total")))
# plan drift: re-measure a mixed plan's per-layer kernel cost and compare
# against the compiler's DSP-op prediction — rank inversions mean the
# plan was optimized against a cost model the backend disagrees with
from repro.obs.drift import build_report
from repro.plan.search import plan_from_bits

cfg_d = get_config("gemma3-1b", smoke=True)  # 3 layers, one pair each
dplan = plan_from_bits(cfg_d, arch="gemma3-1b", bits=[(5, 4), (8, 4), (2, 2)],
                       n_slots=2)
rep = build_report(dplan, cfg_d, n_slots=2, reps=1)
print(f"  drift over {rep['n_layers']} layers ({rep['n_distinct_bit_pairs']} "
      f"bit pairs): {rep['rank_inversions']}/{rep['n_layer_pairs']} rank "
      f"inversions, max drift {rep['max_drift']:.2f}x")
# full reports land in artifacts/plan_drift.json (gated + rendered into
# EXPERIMENTS.md):
#   python -m repro.obs.drift --plan artifacts/plans/drift-mixed.json
#   python benchmarks/serving_bench.py --smoke --trace   # CI trace-smoke job

# -- 11. in-situ attribution + live telemetry ---------------------------------
print("== In-situ per-layer attribution + live telemetry endpoint ==")
# attrib_every=N re-runs every Nth step segmented per layer on a copy of
# the pre-step state (the fused step donates its input, so the copy is
# what keeps re-execution safe) and attributes device time to each layer
# and its (w_bits, a_bits) pair — inside the serving engine, not a
# standalone microbenchmark.  Every traced step also emits Perfetto
# counter tracks (free pages, active/waiting slots, windowed tok/s,
# preemption + shed totals).
import json as _json
import urllib.request

from repro.obs import TelemetryServer

d_params, d_head = apply_plan(T.init_params(jax.random.PRNGKey(0), cfg_d),
                              cfg_d, dplan)
eng = Engine(cfg_d, d_params,
             EngineConfig(n_slots=2, page_size=4, max_len=32, chunk_tokens=4,
                          attrib_every=2),
             head=d_head)
for n in (9, 6, 11):
    eng.submit(rng.integers(1, cfg_d.vocab, size=n).tolist(), 5)
# the telemetry endpoint is engine-agnostic: hand it callables and scrape
# /metrics (Prometheus 0.0.4), /livez (windowed JSON), /trace (segments)
with TelemetryServer(metrics_fn=eng.prometheus_text,
                     livez_fn=eng.live_metrics) as srv:
    m = eng.run(realtime=False)
    scraped = urllib.request.urlopen(srv.url + "/metrics").read().decode()
    live = _json.loads(urllib.request.urlopen(srv.url + "/livez").read())
summ = eng._attrib.summary()
print(f"  {summ['n_samples']} sampled steps over {m['steps']} "
      f"(every 2): per-pair mean shares " + ", ".join(
          f"{p['pair']}={p['mean_share']:.1%}" for p in summ["pairs"]))
print("  scraped mid-serve: " +
      next(l for l in scraped.splitlines()
           if l.startswith("repro_attrib_pair_seconds_total")))
print(f"  /livez: steps={live['steps']} active={live['active_slots']}")
# the same wiring from the shell — serve with a live endpoint, then
# curl http://127.0.0.1:9100/metrics while it runs; --trace writes the
# counter tracks and step phases for Perfetto, checkpointed mid-run:
#   PYTHONPATH=src python -m repro.launch.serve --engine continuous \
#       --telemetry-port 9100 --attrib-every 8 \
#       --trace artifacts/traces/serve.json --trace-checkpoint-every 64
# CI gates this end to end (benchmarks/serving_bench.py --smoke --attrib
# scrapes both engine families mid-run, then check_invariants --kind attrib)

# -- 12. Pallas paged-attention gather ----------------------------------------
print("== Pallas paged-gather kernel (scalar-prefetch block tables) ==")
# The decode attention reads its K/V through a page pool indexed by a
# per-slot block table.  gather="kernel" swaps the XLA pool[block_table]
# gather for a Pallas kernel whose grid index map is driven by the
# prefetched block table itself: grid step (s, b) streams page
# block_table[s, b] from the pool into a VMEM tile, dequantizing int8 KV
# (per-page-row scales), suppressing null pages (page 0), and fusing the
# per-lane causal/window mask — one pass, no [S, T, D] gather
# materialized in HBM first.  On fp pools the two backends are bit-exact.
from repro.kernels.paged_gather import ref as pg_ref
from repro.kernels.paged_gather.kernel import paged_gather_raw
from repro.kernels.paged_gather.ref import xla_gather_reference

case = pg_ref.GatherCase(n_slots=3, n_blocks=4, page_size=8, width=16,
                         chunk=2, window=5, int8=True, seed=7)
ops_g = pg_ref.make_operands(case)
kin = dict(block_table=ops_g["block_table"], pos=ops_g["pos"],
           window=ops_g["window"], pool_k=ops_g["pool_k"],
           pool_v=ops_g["pool_v"], k_scale=ops_g["k_scale"],
           v_scale=ops_g["v_scale"], chunk=case.chunk, out_dtype=jnp.float32)
k_k, v_k, m_k = paged_gather_raw(**kin)
k_r, v_r, m_r = xla_gather_reference(**kin)
assert all(np.array_equal(a, b) for a, b in ((k_k, k_r), (v_k, v_r), (m_k, m_r)))
print(f"  kernel == XLA reference bit-exact on int8 pool "
      f"(S={case.n_slots} NB={case.n_blocks} PS={case.page_size} "
      f"C={case.chunk} window={case.window})")
# the engine flips backends with one knob; token streams are identical
# (tests force preemption/replay across both and compare stream-for-stream)
toks = {}
for backend in ("xla", "kernel"):
    eng = Engine(cfg_d, d_params,
                 EngineConfig(n_slots=2, page_size=4, max_len=32,
                              chunk_tokens=4, gather_backend=backend),
                 head=d_head)
    req = eng.submit(list(range(1, 8)), 6)
    eng.run(realtime=False)
    toks[backend] = req.out_tokens
assert toks["xla"] == toks["kernel"]
print(f"  engine token streams identical across gather backends: "
      f"{toks['kernel']}")
# A/B timings + the correctness ledger live in the paged-gather-smoke job:
#   PYTHONPATH=src python benchmarks/kernel_bench.py --gather --smoke
#   PYTHONPATH=src python benchmarks/check_invariants.py --kind gather \
#       BENCH_gather_smoke.json

# -- 13. mesh-parallel serving (one front door: repro.serving.api) -----------
print("== Mesh-parallel serving via build_engine ==")
# build_engine is how every consumer (serve.py, serving_bench.py, tests)
# constructs engines now: quantization mode, deployment plans, chaos, and
# mesh options all enter through it — never through Engine(...) wiring by
# hand.  MeshConfig(dp=R) runs R data-parallel replicas, each with its own
# page pool, block tables, and scheduler shard; the same compiled step is
# dispatched per replica, so tokens are BIT-identical to a single-replica
# engine (asserted below).  dp works on a single device; mp>1 (tensor
# parallelism: head-sharded attention, N-sharded packed weights via
# per-shard prepack_dense, expert-sharded MoE) needs real or XLA host
# devices — see tests/multidevice_checks.py, which sets
# XLA_FLAGS=--xla_force_host_platform_device_count=8 before importing jax.
from repro.serving import MeshConfig, build_engine

mesh_toks = {}
for mesh in (MeshConfig(), MeshConfig(dp=2)):
    eng = build_engine(cfg, EngineConfig(n_slots=2, page_size=4, max_len=32,
                                         chunk_tokens=4, mesh=mesh),
                       params=params)
    reqs = [eng.submit(list(range(1, 2 + ln)), 5) for ln in (5, 7, 4, 6)]
    m = eng.run(realtime=False)
    eng.assert_no_leaks()  # audits every replica's page/slot books
    mesh_toks[mesh.dp] = [r.out_tokens for r in reqs]
    print(f"  dp={mesh.dp}: {m['n_requests']} requests @ "
          f"{m['tokens_per_s']:.1f} tok/s, "
          f"replica quarantines {m['replica_quarantines']}")
assert mesh_toks[1] == mesh_toks[2]
print("  dp=2 token streams bit-identical to single-replica: True")
# the same knob from the shell (serve + the A/B bench + the CI gate):
#   PYTHONPATH=src python -m repro.launch.serve --mesh 2x2 --packed
#   PYTHONPATH=src python benchmarks/serving_bench.py --smoke --mesh 2x2
#   PYTHONPATH=src python benchmarks/check_invariants.py --kind mesh \
#       BENCH_serving_mesh_smoke.json
print("quickstart complete.")
