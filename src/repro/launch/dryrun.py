import os

# a CPU-only tool: the fake 512-device mesh lives on the host platform
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script builds the production mesh (16x16 single-pod,
2x16x16 multi-pod), lowers the train/serve step with full-size
ShapeDtypeStruct inputs (zero allocation), compiles, and records:

  * memory_analysis()      -> per-device bytes (proves it fits)
  * cost_analysis()        -> HLO FLOPs / bytes for the roofline terms
  * HLO collective parse   -> per-collective bytes (all-gather,
    all-reduce, reduce-scatter, all-to-all, collective-permute)

Results land in artifacts/dryrun/<arch>__<shape>__<mesh>.json and feed
EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all            # every cell, both meshes
"""
import argparse
import json
import pathlib
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, cells_for, get_config
from repro.launch.cost import analyze_hlo_collectives, jaxpr_cost
from repro.configs.registry import ARCHS
from repro.launch import steps as S
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.parallel.sharding import ShardingRules

ARTIFACTS = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

# per-arch training execution knobs (microbatches bound activation memory;
# remat_block bounds scan checkpoint memory)
TRAIN_KNOBS = {
    "nemotron-4-340b": dict(n_micro=16, remat_block=8),
    "llama4-scout-17b-a16e": dict(n_micro=8, remat_block=8),
    "qwen3-moe-30b-a3b": dict(n_micro=4, remat_block=8),
    "yi-6b": dict(n_micro=4, remat_block=8),
    "qwen2-vl-7b": dict(n_micro=4, remat_block=4),
    "llama3.2-3b": dict(n_micro=2, remat_block=4),
    "gemma3-1b": dict(n_micro=2, remat_block=1),
    "zamba2-1.2b": dict(n_micro=2, remat_block=1),
    "whisper-tiny": dict(n_micro=1, remat_block=1),
    "mamba2-130m": dict(n_micro=4, remat_block=4),
}

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
               "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")


parse_collective_bytes = analyze_hlo_collectives  # while-aware (launch/cost.py)


def run_cell(arch: str, shape_name: str, mesh_kind: str, *, fsdp: bool = True,
             serve_int8: bool = False, overrides: dict | None = None) -> dict:
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.size
    knobs = dict(TRAIN_KNOBS.get(arch, {}))
    cfg = get_config(arch)
    repl = {"remat_block": knobs.get("remat_block", 1)}
    step_overrides = {}
    if overrides:
        for k in ("n_micro", "param_dtype", "moment_dtype"):
            if k in overrides:
                step_overrides[k] = overrides[k]
        repl.update({k: v for k, v in overrides.items() if k not in step_overrides})
    import dataclasses as _dc

    cfg = _dc.replace(cfg, **repl)
    if serve_int8:
        from repro.models.layers import QuantConfig

        cfg = _dc.replace(cfg, quant=QuantConfig(serve_int8=True))

    long_ctx = shape.seq_len >= 500_000
    # fsdp: ZeRO-style param sharding over the data axis — needed for the
    # large archs in BOTH training (optimizer state) and serving (weights;
    # XLA re-gathers per layer inside the scan, ZeRO-3 style)
    rules = ShardingRules(
        mesh=mesh,
        batch=(("pod", "data") if mesh_kind == "multi" else "data") if not long_ctx else None,
        fsdp=("data" if fsdp else None),
        seq_mp=("model" if not long_ctx else ("data", "model")),
    )
    if long_ctx:
        # batch=1: nothing to data-parallel; KV/state shards over everything
        rules = ShardingRules(
            mesh=mesh, batch=None, fsdp=None,
            seq_mp=(("pod", "data", "model") if mesh_kind == "multi" else ("data", "model")),
        )

    record: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "chips": n_chips,
        "kind": shape.kind, "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "fsdp": rules.fsdp is not None, "serve_int8": serve_int8,
        "overrides": overrides or {},
    }
    t0 = time.time()
    with jax.set_mesh(mesh):
        params_shape = S.params_spec_tree(cfg)
        if shape.kind != "train":
            # serving stores weights in bf16 (int8 via --serve-int8)
            params_shape = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype
                ),
                params_shape,
            )
        p_specs = S.param_shardings(params_shape, rules)
        if serve_int8 and shape.kind != "train":
            params_shape, p_specs = S.int8_serving_transform(params_shape, p_specs)
        if shape.kind == "train":
            step_cfg = S.TrainStepConfig(
                n_micro=int(step_overrides.get("n_micro", knobs.get("n_micro", 1))),
                param_dtype=str(step_overrides.get("param_dtype", "f32")),
                moment_dtype=str(step_overrides.get("moment_dtype", "f32")),
            )
            step = S.make_train_step(cfg, rules, step_cfg)
            opt_shape = S.opt_state_spec_tree(step.optimizer, params_shape)
            o_specs = S.param_shardings_opt(opt_shape, p_specs)
            batch = S.train_input_specs(cfg, shape)
            b_specs = S.batch_shardings(cfg, rules)
            fn = jax.jit(
                step,
                in_shardings=(p_specs, o_specs, b_specs),
                out_shardings=(P(), p_specs, o_specs),
                donate_argnums=(0, 1),  # params/opt update in place
            )
            lowered = fn.lower(params_shape, opt_shape, batch)
            record["n_micro"] = step_cfg.n_micro
        elif shape.kind == "prefill":
            step = S.make_prefill_step(cfg, rules)
            batch = S.train_input_specs(cfg, shape)
            b_specs = S.batch_shardings(cfg, rules)
            fn = jax.jit(
                step,
                in_shardings=(p_specs, b_specs),
                out_shardings=P(),
            )
            lowered = fn.lower(params_shape, batch)
        else:  # decode
            B = shape.global_batch
            enc_len = max(1, shape.seq_len // 2) if cfg.family == "encdec" else None
            cache_shape = S.cache_spec_tree(cfg, B, shape.seq_len, enc_len=enc_len)
            c_specs = S.cache_shardings(cache_shape, cfg, rules)
            step = S.make_serve_step(cfg, rules)
            tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            fn = jax.jit(
                step,
                in_shardings=(p_specs, c_specs, P(rules.batch, None), P()),
                out_shardings=(P(), c_specs),
                donate_argnums=(1,),  # KV/SSM cache updates in place
            )
            lowered = fn.lower(params_shape, cache_shape, tok, pos)

        record["lower_s"] = round(time.time() - t0, 1)
        try:
            if shape.kind == "train":
                jx = jax.make_jaxpr(step)(params_shape, opt_shape, batch)
            elif shape.kind == "prefill":
                jx = jax.make_jaxpr(step)(params_shape, batch)
            else:
                jx = jax.make_jaxpr(step)(params_shape, cache_shape, tok, pos)
            record["jaxpr_cost"] = {k: float(v) for k, v in jaxpr_cost(jx).items()}
        except Exception as e:  # noqa: BLE001
            record["jaxpr_cost"] = {"error": f"{type(e).__name__}: {e}"}
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 1)

        mem = compiled.memory_analysis()
        record["memory"] = {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "per_device_total_gb": round(
                (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 2**30, 3),
        }
        cost = compiled.cost_analysis() or {}
        record["cost"] = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        }
        hlo = compiled.as_text()
        record["collectives"] = analyze_hlo_collectives(hlo)
        record["hlo_bytes"] = len(hlo)
    return record


def save(record: dict) -> pathlib.Path:
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    name = f"{record['arch']}__{record['shape']}__{record['mesh']}"
    if record.get("serve_int8"):
        name += "__int8"
    if record.get("overrides"):
        name += "__" + "_".join(f"{k}-{v}" for k, v in sorted(record["overrides"].items()))
    path = ARTIFACTS / (name + ".json")
    path.write_text(json.dumps(record, indent=1))
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--serve-int8", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field override k=v (int values)")
    args = ap.parse_args()
    overrides = {}
    for kv in args.override:
        k, v = kv.split("=")
        overrides[k] = int(v) if v.lstrip("-").isdigit() else (v == "True" if v in ("True", "False") else v)

    cells = []
    if args.all:
        for arch in ARCHS:
            for shape in cells_for(arch):
                for mesh in ("single", "multi"):
                    cells.append((arch, shape, mesh))
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape, args.mesh)]

    ok = fail = 0
    for arch, shape, mesh in cells:
        name = f"{arch}__{shape}__{mesh}"
        path = ARTIFACTS / (name + ".json")
        if args.skip_existing and path.exists():
            print(f"[skip] {name}")
            ok += 1
            continue
        try:
            rec = run_cell(arch, shape, mesh, serve_int8=args.serve_int8,
                           overrides=overrides or None)
            p = save(rec)
            print(
                f"[ok] {name}: compile={rec['compile_s']}s "
                f"mem/dev={rec['memory']['per_device_total_gb']}GB "
                f"flops={rec.get('jaxpr_cost',{}).get('flops',0):.3e} "
                f"coll={rec['collectives']['total_bytes']:.3e}B -> {p.name}"
            )
            ok += 1
        except Exception as e:  # noqa: BLE001 - record and continue
            fail += 1
            print(f"[FAIL] {name}: {type(e).__name__}: {e}")
            traceback.print_exc(limit=3)
    print(f"dry-run complete: {ok} ok, {fail} failed")
    if fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
