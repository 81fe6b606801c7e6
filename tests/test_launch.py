"""Launch-layer tests: cost accounting, HLO collective parsing, drivers,
and a (slow) real dry-run cell in a 512-device subprocess."""
import json
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.cost import analyze_hlo_collectives, jaxpr_cost

ROOT = pathlib.Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# scan-aware jaxpr cost counter
# ---------------------------------------------------------------------------


def test_jaxpr_cost_counts_scan_bodies():
    def f(x, w):
        def body(c, _):
            return c @ w, None

        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    jx = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((64, 64), jnp.float32), jax.ShapeDtypeStruct((64, 64), jnp.float32)
    )
    cost = jaxpr_cost(jx)
    assert cost["dot_flops"] == 10 * 2 * 64**3


def test_jaxpr_cost_sees_through_remat_and_jit():
    @jax.checkpoint
    def block(x, w):
        return jax.nn.relu(x @ w)

    def f(x, w):
        return jax.jit(block)(x, w).sum()

    jx = jax.make_jaxpr(jax.grad(f))(
        jnp.ones((32, 32)), jnp.ones((32, 32))
    )
    cost = jaxpr_cost(jx)
    # forward + remat recompute + 2 transpose matmuls >= 3 matmuls of flops
    assert cost["dot_flops"] >= 3 * 2 * 32**3


def test_xla_cost_analysis_undercounts_loops():
    """Documents WHY we ship our own counter (while bodies counted once)."""

    def f(x, w):
        def body(c, _):
            return c @ w, None

        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    co = jax.jit(f).lower(x, x).compile()
    ca = co.cost_analysis()
    xla_flops = ca.get("flops", 0)
    assert xla_flops < 2 * 2 * 64**3  # ~1 body, not 10


# ---------------------------------------------------------------------------
# while-aware HLO collective parser
# ---------------------------------------------------------------------------

FAKE_HLO = """HloModule test

%body.1 (p: (s32[], f32[128])) -> (s32[], f32[128]) {
  %ag.1 = f32[128]{0} all-gather(%gte.1), replica_groups={}
  %ar.1 = f32[64]{0} all-reduce(%gte.2), to_apply=%add
}

%cond.1 (p: (s32[], f32[128])) -> pred[] {
  %c = s32[] constant(24)
  %cmp = pred[] compare(%gte.0, %c), direction=LT
}

ENTRY %main (a: f32[128]) -> f32[128] {
  %big = bf16[1024]{0} all-reduce(%a2), to_apply=%add
  %w = (s32[], f32[128]) while(%t), condition=%cond.1, body=%body.1
}
"""


def test_collective_parser_multiplies_while_trips():
    out = analyze_hlo_collectives(FAKE_HLO)
    assert out["all-gather"]["count"] == 24
    assert out["all-gather"]["bytes"] == 24 * 128 * 4
    # 24 loop all-reduces + 1 entry all-reduce
    assert out["all-reduce"]["count"] == 25
    assert out["all-reduce"]["bytes"] == 24 * 64 * 4 + 1024 * 2
    assert out["total_bytes"] == out["all-gather"]["bytes"] + out["all-reduce"]["bytes"]


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------


def test_train_driver_loss_decreases(tmp_path):
    from repro.launch.train import main

    out = main(
        [
            "--arch", "mamba2-130m", "--steps", "40", "--batch", "4", "--seq", "32",
            "--n-micro", "1", "--ckpt-dir", str(tmp_path),
        ]
    )
    assert out["steps"] == 40
    assert out["loss"] < 6.0  # down from ~ln(512)=6.24 on the smoke vocab


def test_serve_driver_bf16_and_int8():
    from repro.launch.serve import main

    a = main(["--arch", "llama3.2-3b", "--batch", "2", "--tokens", "4"])
    b = main(["--arch", "llama3.2-3b", "--batch", "2", "--tokens", "4", "--int8"])
    assert a["tokens_per_s"] > 0 and b["tokens_per_s"] > 0


def test_train_step_grad_compression_runs():
    import dataclasses

    from repro.configs import get_config
    from repro.launch import steps as S
    from repro.models.transformer import init_params
    from repro.parallel.sharding import ShardingRules

    cfg = get_config("llama3.2-3b", smoke=True)
    step = S.make_train_step(
        cfg, ShardingRules(enabled=False), S.TrainStepConfig(n_micro=2, compress_grads="int8")
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_state = step.optimizer.init(params)
    batch = {
        "tokens": jnp.zeros((4, 16), jnp.int32),
        "labels": jnp.zeros((4, 16), jnp.int32),
    }
    loss, new_p, _ = jax.jit(step)(params, opt_state, batch)
    assert np.isfinite(float(loss))


# ---------------------------------------------------------------------------
# real dry-run cell (slow; 512 virtual devices in a subprocess)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_dryrun_cell_end_to_end(tmp_path):
    proc = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", "mamba2-130m", "--shape", "long_500k", "--mesh", "single",
        ],
        env={"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=1200, cwd=str(ROOT),
    )
    assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-1500:]
    rec = json.loads(
        (ROOT / "artifacts" / "dryrun" / "mamba2-130m__long_500k__single.json").read_text()
    )
    assert rec["chips"] == 256
    assert rec["jaxpr_cost"]["flops"] > 0
    assert rec["memory"]["per_device_total_gb"] < 16.0
    assert "all-gather" in rec["collectives"]
