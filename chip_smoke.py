#!/usr/bin/env python3
"""Bring-up smoke: packed llama3.2-3b serving end to end on a TPU.

Drives the main serving path once through its public entry points
(``repro.serving.build_engine`` -> ``Engine.submit/warmup/run``): the
continuous-batching engine with w4a4 bit-packed projections, at the full
published widths of llama3.2-3b with random weights made from ``--seed``.
It is a check that the system runs on the chip and computes the right
numbers, not a benchmark; the timings it prints are bring-up readings.

One chip (no arguments), in this order:

1. device — the first JAX device must be a TPU, and Pallas must compile
   (``kernels.common.default_interpret()`` is False);
2. kernels — every packed-matmul kernel of the serve path runs once at
   every (K, N) the model uses, bit-exact against
   ``packed_dense_reference``;
3. reference — a 2-layer cut of the same config: engine logits against
   a float32 jax.numpy forward with the same fake quantization (plain
   XLA dots, no Pallas) at ``highest`` matmul precision, within
   :data:`REF_TOL`;
4. serve — 16 requests through the 28-layer engine: all ``ok``, exact
   token counts, finite logits, no leaked pages or slots, no hard
   recoveries.

``--chips 4`` runs only the mesh path and what it is compared with: the
same requests through ``--mesh 1x4`` (mp=4), ``--mesh 4`` (dp=4) and the
one-chip engine — one wave of :data:`MESH_REQUESTS` requests with
:data:`MESH_NEW_TOKENS` new tokens each, so every engine fills its slots
once.  dp=4 must be token-identical to one chip; mp=4's first-token
logits must lie within :data:`MP_TOL` of one chip's.

Every failed phase exits non-zero.  The last line of standard output is
one JSON object naming the device, printed only when every phase passed.

    python chip_smoke.py [--seed 0] [--chips 4]
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "llama3.2-3b"
W_BITS, A_BITS = 4, 4
N_REQUESTS, NEW_TOKENS = 16, 32
MESH_REQUESTS, MESH_NEW_TOKENS = 8, 16
PROMPT_LENS = (128, 512)  # inclusive range of prompt lengths
ENGINE_SHAPE = dict(n_slots=8, chunk_tokens=64, max_len=1024)
KERNEL_ROWS = 512  # rows per fused step: n_slots * chunk_tokens
# 2-layer reference check: the engine runs bf16 activations, the
# reference float32, so 4-bit activation levels flip near rounding
# boundaries (a CPU rehearsal of this check measured 0.106); the bound is
# on max |engine - reference| over the max |reference| logit
REF_TOL = 0.25
# mp=4 vs one chip: bf16 partial sums reduced in a different order flip
# 4-bit activation levels the same way (a four-chip run measured 0.115
# with 126 of 128 tokens equal)
MP_TOL = 0.25


class SmokeFailure(RuntimeError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# -- phase 1: device -----------------------------------------------------


def device_phase(n_chips: int) -> dict:
    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devs)}")
    check(dev.platform == "tpu", f"no TPU: first device is {dev.platform}")
    check(len(devs) >= n_chips, f"need {n_chips} chips, found {len(devs)}")
    from repro.kernels.common import default_interpret

    check(not default_interpret(), "Pallas would run in interpret mode")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}


# -- phase 2: kernels ----------------------------------------------------


def model_matmul_shapes(cfg) -> list[tuple[int, int]]:
    """Every (K, N) of the decode step's projections."""
    q, kv = cfg.n_heads * cfg.hd, cfg.kv_heads * cfg.hd
    return sorted({
        (cfg.d_model, q), (cfg.d_model, kv), (q, cfg.d_model),
        (cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model),
    })


def kernel_phase(cfg, seed: int) -> None:
    from repro.kernels.packed_matmul.ops import (
        packed_dense, packed_dense_reference, prepack_dense,
    )

    key = jax.random.PRNGKey(seed)
    for k_dim, n_dim in model_matmul_shapes(cfg):
        kx, kw, key = jax.random.split(key, 3)
        x = jax.random.uniform(kx, (KERNEL_ROWS, k_dim))
        w = jax.random.normal(kw, (k_dim, n_dim)) / np.sqrt(k_dim)
        pre = prepack_dense(w, w_bits=W_BITS, a_bits=A_BITS)
        want = np.asarray(packed_dense_reference(x, w, w_bits=W_BITS, a_bits=A_BITS))
        # default dispatch (K-blocked kernel) and whole-K (fused kernel)
        for name, block_k in (("blocked", None), ("fused", k_dim)):
            got = np.asarray(packed_dense(x, pre, block_k=block_k))
            same = np.array_equal(got, want)
            log(f"kernel {name:7s} K={k_dim} N={n_dim} M={KERNEL_ROWS}: "
                f"bit-exact={same}")
            check(same, f"{name} kernel differs from the reference at K={k_dim} N={n_dim}")
        del pre


# -- phase 3: 2-layer reference ------------------------------------------


def reference_logits(params, cfg, prompt) -> np.ndarray:
    """float32 forward of one prompt (plain jnp, same fake quantization as
    the packed path); returns the last position's logits."""
    from repro.core.quant import fake_quant_act, fake_quant_weight

    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731

    def qdense(w, x):
        xq = fake_quant_act(jax.nn.sigmoid(x), A_BITS)
        return xq @ fake_quant_weight(f32(w), W_BITS)

    def norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * f32(g)

    def rope(x, pos):
        half = x.shape[-1] // 2
        freqs = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos[:, None, None].astype(jnp.float32) * freqs
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    tokens = jnp.asarray(prompt)
    n, H, G, hd = len(prompt), cfg.n_heads, cfg.kv_heads, cfg.hd
    pos = jnp.arange(n)
    causal = pos[None, :] <= pos[:, None]
    x = f32(params["embed"])[tokens]
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        at, ml = lp["attn"], lp["mlp"]
        h = norm(x, at["ln"]["g"])
        q = rope(qdense(at["wq"]["w"], h).reshape(n, H, hd), pos)
        k = rope(qdense(at["wk"]["w"], h).reshape(n, G, hd), pos)
        v = qdense(at["wv"]["w"], h).reshape(n, G, hd)
        k, v = jnp.repeat(k, H // G, 1), jnp.repeat(v, H // G, 1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        o = jnp.einsum("hqk,khd->qhd", p, v).reshape(n, H * hd)
        x = x + qdense(at["wo"]["w"], o)
        h = norm(x, ml["ln"]["g"])
        act = jax.nn.silu(qdense(ml["w_gate"]["w"], h)) * qdense(ml["w_up"]["w"], h)
        x = x + qdense(ml["w_down"]["w"], act)
    x = norm(x, params["final_ln"]["g"])
    return np.asarray(x[-1] @ f32(params["embed"]).T)


def first_token_logits(eng, reqs) -> dict[int, np.ndarray]:
    """Step the engine to completion, keeping each request's logits row
    at the step that sampled its first token."""
    first: dict[int, np.ndarray] = {}
    while any(r.status is None for r in reqs):
        eng.run(realtime=False, max_steps=eng.n_steps + 1)
        for r in reqs:
            if r.rid not in first and r.out_tokens:
                first[r.rid] = np.array(eng.last_logits[r.replica, r.slot])
    return first


def reference_phase(cfg, seed: int) -> None:
    from repro.models import transformer as T
    from repro.serving import EngineConfig, build_engine
    from repro.serving.api import host_device

    cut = dataclasses.replace(cfg, n_layers=2)
    with jax.default_device(host_device()):
        params = T.init_params(jax.random.PRNGKey(seed), cut)
    eng = build_engine(cut, EngineConfig(**ENGINE_SHAPE), params=params,
                       quant="packed", w_bits=W_BITS, a_bits=A_BITS)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cut.vocab, PROMPT_LENS[0]).tolist()
               for _ in range(ENGINE_SHAPE["n_slots"])]
    # two tokens, so each request still holds its slot when the step that
    # sampled its first token returns
    reqs = [eng.submit(p, 2) for p in prompts]
    eng.warmup()
    got = first_token_logits(eng, reqs)
    del eng
    gc.collect()
    dev_params = jax.device_put(params, jax.devices()[0])
    errs, agree = [], 0
    with jax.default_matmul_precision("highest"):
        for r, prompt in zip(reqs, prompts):
            want = reference_logits(dev_params, cut, prompt)
            errs.append(float(np.max(np.abs(got[r.rid] - want)) / np.max(np.abs(want))))
            agree += int(np.argmax(got[r.rid]) == np.argmax(want))
    log(f"reference (2-layer, {len(prompts)} prompts of {PROMPT_LENS[0]} tokens): "
        f"max |engine - ref| / max |ref| = {max(errs):.4g} (tolerance {REF_TOL}), "
        f"argmax agreement {agree}/{len(prompts)}")
    check(max(errs) <= REF_TOL, f"2-layer logits off the reference: {max(errs):.4g}")


# -- phase 4: serve ------------------------------------------------------


def make_prompts(cfg, seed: int, n: int = N_REQUESTS) -> list[list[int]]:
    rng = np.random.default_rng(seed + 1)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, n)
    return [rng.integers(0, cfg.vocab, int(length)).tolist() for length in lens]


def check_run(eng, reqs, label: str) -> None:
    statuses = [r.status for r in reqs]
    check(all(s == "ok" for s in statuses), f"{label}: statuses {statuses}")
    counts = [len(r.out_tokens) for r in reqs]
    check(all(c == r.max_new_tokens for c, r in zip(counts, reqs)),
          f"{label}: token counts {counts}")
    check(eng.last_logits is not None and np.isfinite(eng.last_logits).all(),
          f"{label}: non-finite logits")
    m = eng.metrics()
    check(m["quarantines"] == 0 and m["preemptions"] == 0,
          f"{label}: quarantines={m['quarantines']} preemptions={m['preemptions']}")
    check(eng.hard_recoveries == 0 and not eng.fault_log,
          f"{label}: hard recoveries {eng.hard_recoveries}: {eng.fault_log}")
    eng.assert_no_leaks()  # raises on a leaked page or slot


def serve(cfg, ecfg, prompts, *, params=None, quant="packed", seed=0,
          new_tokens=NEW_TOKENS, step_wise=False):
    """Build, warm up and drain one engine; returns (engine, requests,
    first-token logits or None)."""
    from repro.launch.serve import peak_bytes_in_use
    from repro.serving import build_engine

    t0 = time.perf_counter()
    eng = build_engine(cfg, ecfg, params=params, quant=quant,
                       w_bits=W_BITS, a_bits=A_BITS, seed=seed)
    t_build = time.perf_counter() - t0
    reqs = [eng.submit(p, new_tokens) for p in prompts]
    t0 = time.perf_counter()
    eng.warmup()
    t_compile = time.perf_counter() - t0
    first = None
    t0 = time.perf_counter()
    if step_wise:
        first = first_token_logits(eng, reqs)
    else:
        eng.run(realtime=True)
    wall = time.perf_counter() - t0
    n_tok = sum(len(r.out_tokens) for r in reqs)
    log(f"bring-up reading, not a benchmark [{eng.dp}x{eng.mp}]: weight prep "
        f"{t_build:.1f} s, compile+warmup {t_compile:.1f} s, steps {eng.n_steps}, "
        f"{n_tok} tokens in {wall:.2f} s = {n_tok / wall:.2f} tokens/s, "
        f"peak HBM bytes {[peak_bytes_in_use(d) for d in jax.devices()[: eng.dp * eng.mp]]}")
    return eng, reqs, first


def serve_phase(cfg, seed: int) -> None:
    from repro.serving import EngineConfig

    prompts = make_prompts(cfg, seed)
    eng, reqs, _ = serve(cfg, EngineConfig(**ENGINE_SHAPE), prompts, seed=seed)
    check_run(eng, reqs, "serve")
    log(f"serve: {len(reqs)} requests ok, {NEW_TOKENS} tokens each, "
        f"prompts {min(map(len, prompts))}-{max(map(len, prompts))} tokens, "
        f"no leaks, hard_recoveries=0")


# -- --chips 4: mesh path ------------------------------------------------


def mesh_phase(cfg, seed: int) -> None:
    from repro.models import transformer as T
    from repro.serving import EngineConfig, MeshConfig
    from repro.serving.api import host_device, quantize_params_packed

    prompts = make_prompts(cfg, seed, MESH_REQUESTS)
    with jax.default_device(host_device()):
        params = T.init_params(jax.random.PRNGKey(seed), cfg)
        packed = quantize_params_packed(params, w_bits=W_BITS, a_bits=A_BITS)

    def run(mesh: MeshConfig, weights, quant):
        ecfg = EngineConfig(mesh=mesh, **ENGINE_SHAPE)
        eng, reqs, first = serve(cfg, ecfg, prompts, params=weights, quant=quant,
                                 new_tokens=MESH_NEW_TOKENS, step_wise=True)
        check_run(eng, reqs, f"mesh {mesh.dp}x{mesh.mp}")
        for d in jax.devices():
            st = d.memory_stats() or {}
            log(f"  {d}: bytes_in_use={st.get('bytes_in_use')} "
                f"peak_bytes_in_use={st.get('peak_bytes_in_use')}")
        tokens = [list(r.out_tokens) for r in reqs]
        first = [first[r.rid] for r in reqs]
        del eng, reqs
        gc.collect()
        return tokens, first

    # mp == 1 engines share one host-packed tree; mp=4 slices the float
    # tree per rank before packing
    one_tok, one_first = run(MeshConfig(), packed, None)
    dp_tok, _ = run(MeshConfig(dp=4), packed, None)
    del packed
    gc.collect()
    mp_tok, mp_first = run(MeshConfig(dp=1, mp=4), params, "packed")
    same_dp = sum(a == b for a, b in zip(one_tok, dp_tok))
    log(f"dp=4 vs one chip: {same_dp}/{len(one_tok)} requests token-identical")
    check(same_dp == len(one_tok), "dp=4 tokens differ from one chip")
    err = max(float(np.max(np.abs(a - b)) / np.max(np.abs(a)))
              for a, b in zip(one_first, mp_first))
    agree = sum(int(a == b) for ra, rb in zip(one_tok, mp_tok) for a, b in zip(ra, rb))
    log(f"mp=4 vs one chip: first-token logits max |diff| / max |one chip| = "
        f"{err:.4g} (tolerance {MP_TOL}), token agreement {agree}/"
        f"{MESH_REQUESTS * MESH_NEW_TOKENS}")
    check(err <= MP_TOL, f"mp=4 first-token logits off one chip: {err:.4g}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh path (mp=4, dp=4) and the "
                    "one-chip engine it is compared with")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    try:
        dev_info = device_phase(args.chips)
        from repro.configs import get_config
        from repro.runtime.compile_cache import enable_compile_cache

        log(f"compile cache: {enable_compile_cache()}")
        cfg = get_config(ARCH, smoke=False)
        if args.chips == 4:
            mesh_phase(cfg, args.seed)
        else:
            kernel_phase(cfg, args.seed)
            reference_phase(cfg, args.seed)
            serve_phase(cfg, args.seed)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev_info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
