"""Serving CLI: continuous-batching engine (default) or the legacy
fixed-batch decode loop (``--engine static``).

``--engine continuous`` drives :class:`repro.serving.Engine`: requests
(synthesized here from ``--batch``/``--prompt-len``/``--tokens``) flow
through an admission scheduler into a paged KV/SSM cache, and one jitted
step advances every active slot per iteration, refilling slots as
sequences finish.  ``--chunk-tokens N`` prefills prompts N tokens per
step (chunked prefill) instead of one, and ``--admit on-demand`` swaps
worst-case page reservation for just-in-time page growth with
lowest-progress preemption/requeue on pool exhaustion.  ``--mesh DPxMP``
shards the engine across a data x model mesh (per-replica page pools and
schedulers; sliced-then-packed weights, sharded heads/experts) — engine
construction goes through :func:`repro.serving.api.build_engine`, the
unified front door.  ``--engine
static`` keeps the original monolithic ``[L, B, T, ...]``-cache loop as
the A/B baseline.

Weight options apply to both engines: ``--int8`` stores projection
weights as int8 levels+scales; ``--packed`` quantizes AND segment-packs
every projection — including rank-4 ``[L, E, d, f]`` MoE expert tensors
— once at load (:func:`repro.kernels.packed_matmul.ops.prepack_dense`),
so each decode step calls straight into the Pallas Kernel-Packing
matmul; ``--packed-head`` additionally prepacks the tied LM head so the
final logits matmul runs sub-8-bit too.

``--plan path.json`` loads a deployment-plan artifact
(``python -m repro.plan.compile``) instead: per-layer mixed-precision
quantize + prepack (three or more distinct bit pairs in one model),
autotuned kernel block shapes, and the plan's LM-head entry — the
engine then serves genuinely mixed precision.

Lifecycle/fault flags (continuous engine only): ``--deadline`` /
``--ttft-deadline`` shed requests that blow their latency budget,
``--max-waiting`` bounds the queue with least-slack shedding, and
``--chaos-step-rate`` / ``--chaos-alloc-rate`` / ``--chaos-nan-rate``
(+ ``--chaos-seed``) arm the deterministic fault injector — the run
ends with a per-status summary instead of crashing.  ``--trace out.json``
records the full request lifecycle and per-step dispatch/device-wait
timeline as Chrome trace JSON (open at https://ui.perfetto.dev), and
``--metrics-out FILE`` dumps the engine's Prometheus text exposition.

Live telemetry (continuous engine only): ``--telemetry-port P`` serves
``/metrics`` (Prometheus text), ``/livez`` (windowed live rates JSON)
and ``/trace?since=N`` (incremental trace flush) on a background thread
while the run is in flight; ``--attrib-every N`` samples in-situ
per-layer attribution every N steps (per-layer/bit-pair time shares in
``/metrics``, summary printed after the run);
``--trace-checkpoint-every N`` rewrites the ``--trace`` file every N
steps so a crashed run still leaves a loadable trace.

The summary line names the device (platform, kind, count).  A
continuous run exits non-zero when any request ends ``failed``, or when
a fused step failed for a reason other than injected chaos (a hard
recovery with chaos off): such a run is not a result.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m --tokens 64
  PYTHONPATH=src python -m repro.launch.serve --packed --wbits 4 --abits 4
  PYTHONPATH=src python -m repro.launch.serve --engine static --int8
  PYTHONPATH=src python -m repro.launch.serve --plan artifacts/plans/ci-plan.json
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.registry import ARCHS
from repro.launch import steps as S
from repro.models import transformer as T
from repro.parallel.sharding import ShardingRules
from repro.runtime.compile_cache import enable_compile_cache

# weight preparation lives with the unified engine-construction API now;
# re-exported here because callers historically imported it from serve
from repro.serving.api import quantize_params_int8, quantize_params_packed  # noqa: F401


def _serve_static(args, cfg, params, head) -> dict:
    """Legacy fixed-batch decode loop (monolithic [L, B, T, ...] cache)."""
    rules = ShardingRules(enabled=False)
    if head is None:
        step_fn = S.make_serve_step(cfg, rules)
    else:
        def step_fn(p, c, t, pos):
            return T.forward_decode(p, cfg, c, t, pos, head=head)
    serve_step = jax.jit(step_fn, donate_argnums=(1,))

    B = args.batch
    cache = T.init_cache(cfg, B, args.max_len, enc_len=16)
    if cfg.family == "encdec":
        enc = jax.random.normal(jax.random.PRNGKey(1), (B, 16, cfg.d_model))
        cache.update(T.encode_for_decode(params, cfg, enc))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, 1), 0, cfg.vocab)

    # warmup/compile
    logits, cache = serve_step(params, cache, tokens, jnp.asarray(0, jnp.int32))
    t0 = time.time()
    for t in range(1, args.tokens):
        nxt = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        logits, cache = serve_step(params, cache, nxt, jnp.asarray(t, jnp.int32))
    jax.block_until_ready(logits)
    dt = time.time() - t0
    tps = (args.tokens - 1) * B / dt
    return {"tokens_per_s": tps, "latency_ms_per_step": dt / (args.tokens - 1) * 1e3}


def peak_bytes_in_use(device=None) -> int | None:
    """``memory_stats()["peak_bytes_in_use"]`` of ``device`` (default: the
    first device), or None where the backend keeps no such count."""
    device = device if device is not None else jax.devices()[0]
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def _serve_continuous(args, cfg, plan=None) -> dict:
    """Continuous-batching engine over a synthetic same-arrival workload.

    Weight preparation is *declared* (``quant=``/``plan=``) rather than
    pre-applied, so ``--mesh DPxMP`` engines get sliced-then-packed
    per-rank shards from the same flags.  ``build_engine`` initializes
    the float weights itself (seed 0, on the host), so no float tree
    outlives weight preparation.
    """
    from repro.serving import EngineConfig, build_engine

    ecfg = EngineConfig.from_cli(args)
    quant = "packed" if args.packed else ("int8" if args.int8 else None)
    eng = build_engine(
        cfg, ecfg, quant=quant, w_bits=args.wbits, a_bits=args.abits,
        plan=plan, seed=0,
    )
    rng = jax.random.PRNGKey(2)
    for i in range(args.requests or 2 * args.batch):
        rng, k = jax.random.split(rng)
        prompt = jax.random.randint(k, (args.prompt_len,), 0, cfg.vocab).tolist()
        eng.submit(
            prompt, args.tokens,
            deadline=args.deadline, ttft_deadline=args.ttft_deadline,
        )
    eng.warmup()  # compile outside the timed run, like the static loop
    print(f"peak device bytes after warmup: {peak_bytes_in_use()}")
    server = None
    if ecfg.obs.telemetry_port is not None:
        from repro.obs.server import TelemetryServer

        def trace_segment(since):
            tr = eng._trace  # armed by run(trace=...); None until then
            return tr.segment(since) if tr is not None else ([], since, 0)

        server = TelemetryServer(
            metrics_fn=eng.prometheus_text,
            livez_fn=eng.live_metrics,
            trace_fn=trace_segment,
            port=ecfg.obs.telemetry_port,
        )
        print(f"telemetry at {server.url} (/metrics /livez /trace)")
    try:
        m = eng.run(realtime=True, trace=args.trace)
    finally:
        if server is not None:
            server.close()
    print(f"peak device bytes after run: {peak_bytes_in_use()}")
    m["latency_ms_per_step"] = m["wall"] / max(1, m["steps"]) * 1e3
    if eng._attrib is not None:
        summ = eng._attrib.summary()
        m["attrib"] = summ
        pairs = ", ".join(
            f"{p['pair']}: {p['mean_share']:.1%} ({p['n_layers']} layers)"
            for p in summ["pairs"]
        )
        print(f"attribution ({summ['n_samples']} sampled steps): {pairs}")
    if args.trace:
        print(f"trace written to {args.trace} (load at https://ui.perfetto.dev)")
    if args.metrics_out:
        import pathlib

        p = pathlib.Path(args.metrics_out)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(eng.prometheus_text())
        print(f"metrics exposition written to {p}")
    return m


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    # default=None so an explicitly-passed arch is distinguishable from the
    # default when checking it against a --plan artifact's arch
    ap.add_argument("--arch", choices=ARCHS, default=None,
                    help="architecture (default llama3.2-3b, or the plan's arch)")
    ap.add_argument(
        "--engine", choices=("continuous", "static"), default=None,
        help="continuous-batching engine (default for attn/ssm archs) or the "
        "legacy fixed-batch loop (default for encdec/hybrid)",
    )
    ap.add_argument("--batch", type=int, default=8, help="decode slots (batch size)")
    ap.add_argument("--tokens", type=int, default=32, help="generated tokens per request")
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=0,
                    help="continuous engine: total requests (default 2x batch)")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=16, help="KV page size (tokens)")
    ap.add_argument("--pages", type=int, default=0,
                    help="KV page-pool budget (0 = full residency)")
    ap.add_argument("--chunk-tokens", type=int, default=1,
                    help="continuous engine: prefill chunk budget per slot per "
                    "step (1 = legacy one-token-per-step prefill)")
    ap.add_argument("--admit", choices=("reserve", "on-demand"), default="reserve",
                    help="continuous engine: worst-case page reservation at "
                    "admit, or on-demand growth with lowest-progress preemption")
    ap.add_argument("--mesh", metavar="DPxMP", default=None,
                    help="continuous engine: shard across a data x model mesh "
                    "(e.g. 2x2: two data replicas with their own page pools/"
                    "schedulers, two tensor/expert-parallel model shards; "
                    "needs DP*MP JAX devices when MP > 1)")
    ap.add_argument("--int8", action="store_true", help="mixed-precision int8 weights")
    ap.add_argument(
        "--plan", metavar="JSON",
        help="deployment plan artifact (repro.plan.compile): per-layer mixed-"
        "precision quantize + prepack, autotuned block shapes, packed LM head",
    )
    ap.add_argument(
        "--packed", action="store_true",
        help="sub-8-bit weights, bit-packed once at load (Kernel-Packing serve path)",
    )
    ap.add_argument("--wbits", type=int, default=4, help="--packed weight bits")
    ap.add_argument("--abits", type=int, default=4, help="--packed activation bits")
    ap.add_argument("--packed-head", action="store_true",
                    help="prepack the LM head too (w8a8 unless --packed sets bits)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="continuous engine: per-request total deadline "
                    "(seconds after arrival); expired requests are shed")
    ap.add_argument("--ttft-deadline", type=float, default=None,
                    help="continuous engine: time-to-first-token deadline "
                    "(seconds after arrival)")
    ap.add_argument("--max-waiting", type=int, default=0,
                    help="continuous engine: waiting-queue bound (0 = "
                    "unbounded); overflow sheds the least-slack request")
    ap.add_argument("--chaos-step-rate", type=float, default=0.0,
                    help="chaos: P(fused step raises) per attempt")
    ap.add_argument("--chaos-alloc-rate", type=float, default=0.0,
                    help="chaos: P(page alloc transiently fails) per call")
    ap.add_argument("--chaos-nan-rate", type=float, default=0.0,
                    help="chaos: P(sampling logits NaN-poisoned) per slot/step")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="chaos: fault-injection RNG seed")
    ap.add_argument("--trace", metavar="JSON", default=None,
                    help="continuous engine: write a Perfetto-loadable Chrome "
                    "trace (request spans + step/dispatch/device-wait timing)")
    ap.add_argument("--metrics-out", metavar="FILE", default=None,
                    help="continuous engine: write Prometheus text exposition "
                    "of the engine metrics registry after the run")
    ap.add_argument("--telemetry-port", type=int, default=None,
                    help="continuous engine: serve /metrics, /livez and "
                    "/trace on this port (0 = ephemeral) for the duration "
                    "of the run")
    ap.add_argument("--attrib-every", type=int, default=0,
                    help="continuous engine: every N steps, re-execute the "
                    "step segmented per layer and attribute device time to "
                    "each layer / bit pair (0 = off)")
    ap.add_argument("--attrib-reps", type=int, default=1,
                    help="timing repetitions per attribution segment "
                    "(min-of-reps)")
    ap.add_argument("--trace-checkpoint-every", type=int, default=0,
                    help="with --trace: rewrite the partial trace to disk "
                    "every N steps (crash-durable traces; 0 = only at end)")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args(argv)

    plan = None
    smoke = not args.full
    if args.plan:
        from repro.plan import DeployPlan, summarize

        if args.packed or args.int8 or args.packed_head:
            raise SystemExit(
                "--plan already fixes per-layer quantization and the LM head; "
                "drop --packed/--int8/--packed-head"
            )
        plan = DeployPlan.load(args.plan)
        if args.arch is not None and args.arch != plan.arch:
            raise SystemExit(
                f"--arch {args.arch} conflicts with plan arch {plan.arch}"
            )
        args.arch = plan.arch
        if args.full and plan.smoke:
            raise SystemExit(
                "--full conflicts with a smoke-compiled plan; recompile with "
                "`repro.plan.compile --full`"
            )
        smoke = plan.smoke  # the plan's layer shapes fix the config variant
        print(f"plan: {summarize(plan)}")
    elif args.arch is None:
        args.arch = "llama3.2-3b"

    cfg = get_config(args.arch, smoke=smoke)
    engine = args.engine
    if engine is None:
        engine = "continuous" if cfg.family in ("attn", "ssm") else "static"
    if engine != "continuous" and (
        args.chunk_tokens != 1 or args.admit != "reserve" or args.mesh is not None
    ):
        raise SystemExit(
            "--chunk-tokens/--admit/--mesh drive the continuous engine; they "
            "have no effect on --engine static — drop them or switch engines"
        )
    lifecycle_flags = (
        args.deadline is not None or args.ttft_deadline is not None
        or args.max_waiting or args.chaos_step_rate or args.chaos_alloc_rate
        or args.chaos_nan_rate
    )
    if engine != "continuous" and lifecycle_flags:
        raise SystemExit(
            "--deadline/--ttft-deadline/--max-waiting/--chaos-* drive the "
            "continuous engine's request lifecycle; they have no effect on "
            "--engine static — drop them or switch engines"
        )
    if engine != "continuous" and (args.trace or args.metrics_out):
        raise SystemExit(
            "--trace/--metrics-out record the continuous engine's request "
            "lifecycle and step timeline; they have no effect on --engine "
            "static — drop them or switch engines"
        )
    if engine != "continuous" and (
        args.telemetry_port is not None or args.attrib_every
        or args.trace_checkpoint_every
    ):
        raise SystemExit(
            "--telemetry-port/--attrib-every/--trace-checkpoint-every drive "
            "the continuous engine's observability; they have no effect on "
            "--engine static — drop them or switch engines"
        )
    if args.trace_checkpoint_every and not args.trace:
        raise SystemExit(
            "--trace-checkpoint-every rewrites the --trace file mid-run; "
            "add --trace PATH or drop it"
        )
    enable_compile_cache()
    if engine == "continuous":
        # weight prep is declared to build_engine (so --mesh engines get
        # sliced-then-packed per-rank shards), not pre-applied here
        out = _serve_continuous(args, cfg, plan=plan)
    else:
        from repro.serving.api import host_device

        head = None
        with jax.default_device(host_device()):  # prepare on the host
            params = T.init_params(jax.random.PRNGKey(0), cfg)
            if plan is not None:
                from repro.plan import apply_plan

                params, head = apply_plan(params, cfg, plan)
            elif args.packed:
                params = quantize_params_packed(
                    params, w_bits=args.wbits, a_bits=args.abits
                )
            elif args.int8:
                params = quantize_params_int8(params)
        params, head = jax.device_put((params, head), jax.devices()[0])
        if head is None and args.packed_head:
            from repro.models.layers import prepack_lm_head

            wb, ab = (args.wbits, args.abits) if args.packed else (8, 8)
            head = prepack_lm_head(params["embed"], w_bits=wb, a_bits=ab)
        out = _serve_static(args, cfg, params, head)

    if plan is not None:
        mode = f"plan[{plan.n_distinct_bit_pairs} bit pairs]"
    else:
        mode = "packed" if args.packed else ("int8" if args.int8 else "fp")
    if args.packed_head:
        mode += "+packed_head"
    tps = out["tokens_per_s"]
    tps_str = f"{tps:.1f}" if tps is not None else "n/a"
    mesh_str = f" mesh={args.mesh}" if args.mesh else ""
    dev = jax.devices()[0]
    print(
        f"arch={cfg.name} engine={engine} weights={mode} batch={args.batch}"
        f"{mesh_str} tokens/s={tps_str} "
        f"latency={out['latency_ms_per_step']:.1f} ms/step "
        f"platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(jax.devices())}"
    )
    if "statuses" in out:
        parts = " ".join(f"{k}={v}" for k, v in sorted(out["statuses"].items()))
        faults = out.get("injected", {})
        print(
            f"statuses: {parts or 'none'}  "
            f"(retries={out.get('step_retries', 0)} "
            f"quarantines={out.get('quarantines', 0)} "
            f"injected={faults})"
        )
        chaos_on = bool(
            args.chaos_step_rate or args.chaos_alloc_rate or args.chaos_nan_rate
        )
        n_failed = out["statuses"].get("failed", 0)
        if n_failed or (out["hard_recoveries"] and not chaos_on):
            raise SystemExit(
                f"serve: {n_failed} request(s) failed, "
                f"{out['hard_recoveries']} hard recoveries"
            )
    return out


if __name__ == "__main__":
    main()
