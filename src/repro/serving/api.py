"""Unified engine construction: the one front door to the serving engine.

Every caller — the serving CLI (:mod:`repro.launch.serve`), the serving
benchmark (``benchmarks/serving_bench.py``), and the tests — builds
engines through :func:`build_engine`, so weight preparation (int8,
globally packed, or a per-layer deployment plan) and mesh sharding
compose in exactly one place instead of being re-derived per call site:

* ``mesh.mp == 1``: weights are quantized/packed globally, byte-for-byte
  the same params the pre-API call sites produced.
* ``mesh.mp > 1``: weights are **sliced first, then packed** — each
  rank's tensor-parallel slice is quantized against the *global* tanh
  normalizer (:func:`repro.plan.apply._tp_tmax_tree`), so per-shard
  packed words equal slices of the single-device prepack and no
  repacking ever follows a collective.  The stacked shards ride into
  :class:`~repro.serving.engine.Engine` via ``shard_params``.

Mesh options (``mesh_shape`` / ``EngineConfig.mesh``) enter the engine
*only* through this API or :meth:`EngineConfig.from_cli` — nothing else
threads ``dp``/``mp`` into construction.

    from repro.serving import EngineConfig, MeshConfig, build_engine
    eng = build_engine(cfg, EngineConfig(mesh=MeshConfig(dp=2, mp=2)),
                       quant="packed", w_bits=4, a_bits=8)
    eng.submit([1, 2, 3], max_new_tokens=16)
    eng.warmup()
    metrics = eng.run(realtime=False)
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.parallel.sharding import ShardingRules
from repro.serving.chaos import ChaosConfig
from repro.serving.engine import Engine, EngineConfig

QUANT_MODES = (None, "int8", "packed")


def host_device():
    """The host CPU device.  :func:`build_engine` initializes and packs
    weights here, so the accelerator never holds the float tree and the
    packed tree at the same time; the engine then places only what its
    step reads."""
    return jax.devices("cpu")[0]


def quantize_params_int8(params):
    """Convert every matmul weight to int8 levels + scales.

    Per-out-channel symmetric int8 over the contraction dim (-2);
    keepdims preserves the stacked layer axis for the decode scan.  The
    per-column scales make these dicts mesh-sliceable as-is
    (:func:`repro.parallel.sharding.slice_decode_params`).
    """
    from repro.plan.apply import MOE_WEIGHT_RE, PROJ_WEIGHT_RE

    def one(path, leaf):
        pstr = "/".join(str(getattr(k, "key", k)) for k in path)
        matched = re.search(PROJ_WEIGHT_RE, pstr) or re.search(MOE_WEIGHT_RE, pstr)
        if matched and leaf.ndim >= 2:
            n = 127
            scale = jnp.max(jnp.abs(leaf), axis=-2, keepdims=True) / n + 1e-12
            levels = jnp.clip(jnp.round(leaf / scale), -n, n).astype(jnp.int8)
            return {"levels": levels, "scale": scale.astype(jnp.float32)}
        return leaf

    return jax.tree_util.tree_map_with_path(one, params)


def quantize_params_packed(params, *, w_bits: int, a_bits: int, verbose: bool = True):
    """One-time quantize + bit-pack of every projection weight at load.

    Attention/MLP projection matrices ([K, N] or scan-stacked [L, K, N])
    and MoE expert tensors ([E, d, f] or scan-stacked [L, E, d, f])
    become :class:`PackedDenseParams` leaves; ``models.layers.dense`` and
    ``models.moe._expert_ffn`` detect them and dispatch each decode-step
    matmul straight into the Pallas Kernel-Packing kernel.  Any
    projection-shaped tensor left in float is counted and reported so
    silent precision gaps are visible.

    This is the *global* (one bit pair) special case of
    ``repro.plan.apply``; per-layer mixed precision comes from a
    :class:`~repro.plan.plan.DeployPlan` via
    :func:`repro.plan.apply.apply_plan`, which shares the tree walk below
    so uniform plans stay bit-identical to this path.
    """
    from repro.plan.apply import prepack_tree

    skipped: list[str] = []
    out = prepack_tree(params, w_bits=w_bits, a_bits=a_bits, skipped=skipped)
    if skipped and verbose:
        print(f"quantize_params_packed: {len(skipped)} projection tensors left in float: "
              + ", ".join(skipped))
    return out


def _packed_shards(params, cfg, mp: int, *, w_bits: int, a_bits: int):
    """Per-rank slice -> quantize+pack (global normalizers) -> stack."""
    from repro.plan.apply import _tp_tmax_tree, prepack_tree
    from repro.parallel.sharding import slice_decode_params, stack_decode_shards

    global_layers = params["layers"]
    shards = []
    for rank in range(mp):
        sliced = slice_decode_params(params, cfg, mp, rank)
        sliced["layers"] = prepack_tree(
            sliced["layers"], w_bits=w_bits, a_bits=a_bits,
            t_max_tree=_tp_tmax_tree(global_layers, sliced["layers"]),
        )
        shards.append(sliced)
    return stack_decode_shards(shards)


def _plan_shards(params, cfg, plan, mp: int):
    """Per-rank apply_plan (sliced-then-packed) -> stacked shards + head."""
    from repro.parallel.sharding import stack_decode_shards
    from repro.plan.apply import apply_plan

    shards, heads = [], []
    for rank in range(mp):
        p_r, h_r = apply_plan(params, cfg, plan, verbose=rank == 0, tp=(mp, rank))
        shards.append(p_r)
        heads.append(h_r)
    head = None if heads[0] is None else stack_decode_shards(heads)
    return stack_decode_shards(shards), head


def build_engine(
    cfg: T.ModelConfig,
    ecfg: EngineConfig = EngineConfig(),
    *,
    params=None,
    head=None,
    quant: str | None = None,
    w_bits: int = 4,
    a_bits: int = 8,
    plan=None,
    rules: ShardingRules | None = None,
    chaos: ChaosConfig | None = None,
    seed: int = 0,
) -> Engine:
    """Construct a serving :class:`Engine`, quantized and mesh-sharded.

    ``params`` are *float* decode params (default: ``init_params`` with
    ``seed``); weight preparation is declared, not pre-applied:

    * ``quant=None`` serves them as-is;
    * ``quant="int8"`` stores projections as int8 levels + scales;
    * ``quant="packed"`` quantizes and bit-packs every projection at
      ``(w_bits, a_bits)`` for the Pallas packed-matmul serve path;
    * ``plan`` (a :class:`~repro.plan.plan.DeployPlan`, exclusive with
      ``quant``) applies per-layer mixed precision plus the plan's LM
      head.

    With ``ecfg.mesh.mp > 1`` each mode additionally produces per-rank
    tensor-parallel shards (sliced **before** quantize/pack, against
    global normalizers — see the module docstring); ``ecfg.packed_head``
    and plan LM heads shard on vocab rows.  Pre-quantized ``params`` are
    accepted for single-shard engines (back-compat with callers that
    already ran ``quantize_params_*``) but mesh construction needs the
    float tree, so pass ``quant=``/``plan=`` instead of pre-applying.

    ``head`` injects prepacked LM-head weights (``[mp, ...]``-stacked
    when ``mp > 1``); ``chaos`` is the deprecated keyword shim — prefer
    ``ecfg.chaos``.

    Weight preparation runs on :func:`host_device` (arrays the caller
    already placed on an accelerator stay there); the engine moves the
    prepared weights to its own devices.
    """
    if quant not in QUANT_MODES:
        raise ValueError(f"quant must be one of {QUANT_MODES}, got {quant!r}")
    if plan is not None and quant is not None:
        raise ValueError(
            "a deployment plan already fixes per-layer quantization; "
            "pass plan= or quant=, not both"
        )
    with jax.default_device(host_device()):
        params, head, shard_params = _prepare_weights(
            cfg, ecfg, params, head, quant, w_bits, a_bits, plan, seed
        )
    return Engine(
        cfg, params, ecfg, rules=rules, head=head, chaos=chaos,
        shard_params=shard_params,
    )


def _prepare_weights(cfg, ecfg, params, head, quant, w_bits, a_bits, plan, seed):
    """Float params -> ``(params, head, shard_params)`` for :class:`Engine`."""
    if params is None:
        params = T.init_params(jax.random.PRNGKey(seed), cfg)
    mp = ecfg.mesh.mp
    shard_params = None
    if plan is not None:
        from repro.plan.apply import apply_plan

        if head is not None:
            raise ValueError("plan.lm_head and head= are exclusive — pass one")
        if mp > 1:
            shard_params, head = _plan_shards(params, cfg, plan, mp)
        else:
            params, head = apply_plan(params, cfg, plan)
    elif quant == "int8":
        # per-column scales slice exactly, so the engine's default
        # slice_decode_params path handles the mesh case
        params = quantize_params_int8(params)
    elif quant == "packed":
        if mp > 1:
            shard_params = _packed_shards(
                params, cfg, mp, w_bits=w_bits, a_bits=a_bits
            )
        else:
            params = quantize_params_packed(
                params, w_bits=w_bits, a_bits=a_bits, verbose=False
            )
    return params, head, shard_params
