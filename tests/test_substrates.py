"""Substrate behaviour: optimizer, data, checkpointing, fault tolerance,
gradient compression, and the multi-device semantics suite (subprocess)."""
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.data.tokens import TokenStream
from repro.optim import AdamW, GradAccumulator, cosine_schedule, global_norm
from repro.optim.compression import compress_tree, quantize_int8, topk_mask
from repro.runtime import FaultTolerantRunner, RunnerConfig


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def test_adamw_converges_quadratic():
    opt = AdamW(lr=0.1)
    params = {"x": jnp.asarray([3.0, -2.0])}
    state = opt.init(params)

    @jax.jit
    def step(p, s):
        g = jax.grad(lambda q: jnp.sum(q["x"] ** 2))(p)
        return opt.update(g, s, p)

    for _ in range(150):
        params, state = step(params, state)
    assert float(jnp.abs(params["x"]).max()) < 1e-2


def test_grad_clipping_bounds_norm():
    opt = AdamW(lr=1.0, grad_clip_norm=1.0)
    params = {"x": jnp.zeros(4)}
    state = opt.init(params)
    g = {"x": jnp.full(4, 100.0)}
    new, _ = opt.update(g, state, params)
    # first Adam step magnitude is bounded by lr regardless of raw grad
    assert float(jnp.abs(new["x"]).max()) <= 1.0 + 1e-6


def test_cosine_schedule_shape():
    f = cosine_schedule(1.0, warmup=10, total=100)
    assert float(f(jnp.asarray(0))) == 0.0
    assert abs(float(f(jnp.asarray(10))) - 1.0) < 1e-6
    assert float(f(jnp.asarray(100))) < 1e-3


def test_grad_accumulator_mean():
    acc = GradAccumulator.init({"w": jnp.zeros(3)})
    acc = acc.add({"w": jnp.ones(3)})
    acc = acc.add({"w": 3 * jnp.ones(3)})
    np.testing.assert_allclose(np.asarray(acc.mean()["w"]), 2.0)


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------


def test_int8_compression_error_bounded():
    g = jax.random.normal(jax.random.PRNGKey(0), (256,))
    q = quantize_int8(g)
    assert float(jnp.abs(q - g).max()) <= float(jnp.abs(g).max()) / 127.0 + 1e-6


def test_topk_keeps_largest():
    g = jnp.asarray([0.1, -5.0, 0.2, 4.0, -0.05, 0.3, 1.0, -2.0] * 4)
    m = topk_mask(g, frac=0.25)
    kept = np.asarray(m) != 0
    assert kept.sum() >= 8
    assert bool(kept[1]) and bool(kept[3])  # largest magnitudes survive


def test_compress_tree_structure():
    tree = {"a": jnp.ones((8, 8)), "b": {"c": jnp.ones(17)}}
    out = compress_tree(tree, method="int8")
    assert jax.tree.structure(out) == jax.tree.structure(tree)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


def test_token_stream_deterministic_and_sharded():
    a = TokenStream(vocab=1024, seq_len=32, global_batch=8, seed=5, n_hosts=2, host_id=0)
    b = TokenStream(vocab=1024, seq_len=32, global_batch=8, seed=5, n_hosts=2, host_id=1)
    x0, x1 = a.batch(11), b.batch(11)
    assert x0["tokens"].shape == (4, 32)
    assert not np.array_equal(x0["tokens"], x1["tokens"])  # distinct host slices
    np.testing.assert_array_equal(a.batch(11)["tokens"], x0["tokens"])  # replayable
    # labels are next-token shifted
    np.testing.assert_array_equal(x0["labels"][:, :-1], x0["tokens"][:, 1:])


def test_token_stream_learnable_structure():
    """A bigram model must beat uniform entropy on this stream (sanity that
    training losses in examples are meaningful)."""
    ts = TokenStream(vocab=64, seq_len=512, global_batch=4, seed=0)
    b = ts.batch(0)
    toks, labs = np.asarray(b["tokens"]).ravel(), np.asarray(b["labels"]).ravel()
    counts = np.ones((64, 64))
    for t, l in zip(toks[:1500], labs[:1500]):
        counts[t, l] += 1
    probs = counts / counts.sum(1, keepdims=True)
    nll = -np.mean(np.log(probs[toks[1500:], labs[1500:]]))
    assert nll < np.log(64) * 0.9  # clearly below uniform


# ---------------------------------------------------------------------------
# checkpointing + fault tolerance
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    tree = {"w": jnp.arange(6.0).reshape(2, 3), "opt": {"m": jnp.ones(4)}}
    mgr.save(3, tree)
    mgr.save(9, jax.tree.map(lambda a: a * 2, tree))
    assert mgr.all_steps() == [3, 9]
    step, restored = mgr.restore(tree)
    assert step == 9
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]) * 2)


def test_checkpoint_gc_keeps_latest(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    tree = {"w": jnp.zeros(2)}
    for s in (1, 2, 3, 4):
        mgr.save(s, tree)
    assert mgr.all_steps() == [3, 4]


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(tmp_path)
    tree = {"w": jnp.ones((64, 64))}
    mgr.save_async(5, tree)
    mgr.wait()
    assert mgr.latest_step() == 5


def test_fault_tolerant_runner_recovers(tmp_path):
    """Inject a failure mid-run; the runner must restore and converge to the
    same final state as an uninterrupted run."""
    opt = AdamW(lr=0.05)

    def make_step():
        @jax.jit
        def step(state, batch):
            params, opt_state = state
            loss, g = jax.value_and_grad(
                lambda p: jnp.mean((p["w"] * batch["x"] - batch["y"]) ** 2)
            )(params)
            params, opt_state = opt.update(g, opt_state, params)
            return loss, (params, opt_state)

        return step

    def batches(step):
        k = jax.random.PRNGKey(step)
        x = jax.random.normal(k, (8,))
        return {"x": x, "y": 3.0 * x}

    params = {"w": jnp.zeros(8)}
    init = (params, opt.init(params))

    # uninterrupted reference
    ref = FaultTolerantRunner(make_step(), CheckpointManager(tmp_path / "ref"),
                              RunnerConfig(ckpt_every=4))
    state_ref, _ = ref.run(init, batches, 20)

    # failing run: dies at steps 7 and 13
    died = set()

    def injector(step):
        if step in (7, 13) and step not in died:
            died.add(step)
            raise RuntimeError("simulated host failure")

    ft = FaultTolerantRunner(make_step(), CheckpointManager(tmp_path / "ft"),
                             RunnerConfig(ckpt_every=4))
    state_ft, stats = ft.run(init, batches, 20, failure_injector=injector)
    assert stats.restarts == 2
    np.testing.assert_allclose(
        np.asarray(state_ref[0]["w"]), np.asarray(state_ft[0]["w"]), rtol=1e-5, atol=1e-6
    )


def test_straggler_detection():
    import time

    calls = []

    def slow_step(state, batch):
        if batch == 5:
            time.sleep(0.25)
        else:
            time.sleep(0.01)
        return jnp.zeros(()), state

    ft = FaultTolerantRunner(
        slow_step,
        CheckpointManager(pathlib.Path("artifacts/test_straggler")),
        RunnerConfig(ckpt_every=1000, straggler_factor=3.0),
        on_straggler=lambda s, dt: calls.append((s, dt)),
    )
    ft.run(None, lambda s: s, 10)
    assert ft.stats.stragglers >= 1
    assert any(s == 5 for s, _ in calls)


# ---------------------------------------------------------------------------
# multi-device semantics (8 virtual devices, subprocess)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_multidevice_suite():
    root = pathlib.Path(__file__).resolve().parents[1]
    proc = subprocess.run(
        [sys.executable, str(root / "tests" / "multidevice_checks.py")],
        env={"PYTHONPATH": str(root / "src"), "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=1200,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "ALL MULTIDEVICE CHECKS PASSED" in proc.stdout
