"""Multi-device semantics checks, run in a subprocess with 8 host devices.

Asserts the properties that make the distribution layer trustworthy:
  1. sharded train_step == single-device train_step (DP+TP invariance)
  2. MoE with real all_to_all expert parallelism == dense reference
  3. checkpoint saved from mesh A restores bit-exactly onto mesh B
  4. gradient compression roundtrip sanity under sharding
  5. mesh serving (dp=2 x mp=2) token-identical to the single-device
     engine — including under forced preemption and seeded chaos — with
     zero leaked pages/slots on every replica, for attn and ssm alike
  6. per-shard prepack_dense == a column slice of the global prepack
     (the sliced-then-packed invariant: no repacking after a collective)

The serving identity checks run the model in float32: the mp > 1 step
reduces partial products with one psum per block, and at bf16 the
reduction-order noise (~2e-3) can flip a greedy argmax on a near-tie.
f32 keeps every tie far above reduction noise, so token equality is
exact; dp-only sharding is bit-exact at any dtype (same compiled step
per replica) and is asserted in-process by tests/test_serving.py.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.launch import steps as S
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.models.moe import MoESpec, moe_init, moe_reference
from repro.parallel.sharding import ShardingRules, use_rules
from repro.checkpoint import CheckpointManager


def check_train_parity():
    cfg = get_config("yi-6b", smoke=True)
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    from repro.data.tokens import TokenStream

    ts = TokenStream(vocab=cfg.vocab, seq_len=16, global_batch=4, seed=1)
    batch = jax.tree.map(jnp.asarray, ts.batch(0))

    # single-device reference
    step_ref = S.make_train_step(cfg, ShardingRules(enabled=False), S.TrainStepConfig(n_micro=2))
    opt = step_ref.optimizer
    loss_ref, p_ref, _ = jax.jit(step_ref)(params, opt.init(params), batch)

    # sharded on a (2, 4) mesh
    mesh = make_host_mesh((2, 4), ("data", "model"))
    rules = ShardingRules(mesh=mesh, batch="data", fsdp=None)
    with jax.set_mesh(mesh):
        p_specs = S.param_shardings(jax.eval_shape(lambda: params), rules)
        o_specs = S.param_shardings_opt(None, p_specs)
        b_specs = S.batch_shardings(cfg, rules)
        step = S.make_train_step(cfg, rules, S.TrainStepConfig(n_micro=2))
        fn = jax.jit(step, in_shardings=(p_specs, o_specs, b_specs),
                     out_shardings=(P(), p_specs, o_specs))
        put = lambda tree, specs: jax.tree.map(
            lambda a, sp: jax.device_put(a, NamedSharding(mesh, sp)), tree, specs
        )
        params_sh = put(params, p_specs)
        opt_sh = put(opt.init(params), o_specs)
        batch_sh = put(batch, b_specs)
        loss_sh, p_sh, _ = fn(params_sh, opt_sh, batch_sh)
    np.testing.assert_allclose(float(loss_ref), float(loss_sh), rtol=2e-3)
    # Adam's first step is ~sign(g)*lr: where |g| is at bf16 reduction-noise
    # scale the sign can flip between reduction orders, bounding the diff by
    # 2*lr*(1+eps).  Allow that and require everything else to match tightly.
    lr = 3e-4
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-2, atol=2.5 * lr)
    print("train parity ok: loss", float(loss_ref), float(loss_sh))


def check_moe_all_to_all():
    mesh = make_host_mesh((2, 4), ("data", "model"))
    spec = MoESpec(d_model=16, d_ff=32, n_experts=8, top_k=2, capacity_factor=8.0)
    params = moe_init(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16)) * 0.5
    want = moe_reference(params, spec, x)

    cfg = dataclasses.replace(
        get_config("qwen3-moe-30b-a3b", smoke=True),
        d_model=16, n_experts=8, top_k=2, expert_d_ff=32, capacity_factor=8.0,
        mlp_kind="swiglu",
    )
    rules = ShardingRules(mesh=mesh, batch="data", fsdp=None)
    with jax.set_mesh(mesh), use_rules(rules):
        got = jax.jit(lambda p, v: T._moe_block(p, cfg, v))(params, x)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got), rtol=2e-3, atol=2e-3)
    print("moe all_to_all parity ok")


def check_checkpoint_reshard(tmp="artifacts/test_ckpt"):
    import shutil

    shutil.rmtree(tmp, ignore_errors=True)
    cfg = get_config("llama3.2-3b", smoke=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    mesh_a = make_host_mesh((2, 4), ("data", "model"))
    rules_a = ShardingRules(mesh=mesh_a, batch="data")
    with jax.set_mesh(mesh_a):
        specs = S.param_shardings(jax.eval_shape(lambda: params), rules_a)
        sharded = jax.tree.map(
            lambda a, sp: jax.device_put(a, NamedSharding(mesh_a, sp)), params, specs
        )
    mgr = CheckpointManager(tmp, keep=2)
    mgr.save(7, sharded)

    mesh_b = make_host_mesh((4, 2), ("data", "model"))  # elastic rescale
    rules_b = ShardingRules(mesh=mesh_b, batch="data")
    with jax.set_mesh(mesh_b):
        specs_b = S.param_shardings(jax.eval_shape(lambda: params), rules_b)
        sh_b = jax.tree.map(lambda sp: NamedSharding(mesh_b, sp), specs_b)
        step, restored = mgr.restore(params, shardings=sh_b)
    assert step == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("checkpoint reshard ok")


def check_moe_decode_psum():
    """Expert-sharded (token-replicated) MoE path under a real 4-way mesh."""
    mesh = make_host_mesh((2, 4), ("data", "model"))
    spec = MoESpec(d_model=16, d_ff=32, n_experts=8, top_k=2, capacity_factor=8.0)
    params = moe_init(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 1, 16)) * 0.5  # decode: S=1
    want = moe_reference(params, spec, x)
    cfg = dataclasses.replace(
        get_config("qwen3-moe-30b-a3b", smoke=True),
        d_model=16, n_experts=8, top_k=2, expert_d_ff=32, capacity_factor=8.0,
        mlp_kind="swiglu",
    )
    rules = ShardingRules(mesh=mesh, batch="data", fsdp=None)
    with jax.set_mesh(mesh), use_rules(rules):
        got = jax.jit(lambda p, v: T._moe_block(p, cfg, v))(params, x)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got), rtol=2e-3, atol=2e-3)
    print("moe decode psum parity ok")


def _serve_tokens(cfg, mesh, *, chaos=None):
    """Run the forced-preemption workload on one engine arm; return
    (per-rid token streams, metrics)."""
    from repro.serving import ChaosConfig, EngineConfig, build_engine  # noqa: F401

    ecfg = EngineConfig(n_slots=3, page_size=4, max_len=32, n_pages=6,
                        chunk_tokens=4, admit="on-demand", mesh=mesh)
    eng = build_engine(cfg, ecfg, chaos=chaos)
    rng = np.random.default_rng(17)
    for ln in (9, 6, 11, 9, 6, 11):
        eng.submit(rng.integers(1, cfg.vocab, size=ln).tolist(), 6, arrival=0.0)
    m = eng.run(realtime=False)
    eng.assert_no_leaks()  # audits every replica's pool + slots
    assert m["n_ok"] == 6, m["statuses"]
    return {r.rid: r.out_tokens for r in eng.finished}, m


def check_mesh_serving_token_identity():
    """dp=2 x mp=2 serving == single-device serving, token for token,
    while the undersized pool forces preemption + chunked replay on both
    arms, for the KV family and the recurrent-state SSM family."""
    from repro.serving import MeshConfig

    for arch in ("llama3.2-3b", "mamba2-130m"):
        cfg = dataclasses.replace(get_config(arch, smoke=True), dtype=jnp.float32)
        want, m_1 = _serve_tokens(cfg, MeshConfig())
        got, m_m = _serve_tokens(cfg, MeshConfig(dp=2, mp=2))
        assert m_1["preemptions"] > 0, "undersized pool must force preemption"
        assert m_m["preemptions"] > 0, "undersized pool must force preemption"
        assert want == got, f"{arch}: mesh tokens diverged from single-device"
        print(f"mesh serving identity ok ({arch}): "
              f"preempt {m_1['preemptions']}/{m_m['preemptions']}")


def check_mesh_serving_under_chaos():
    """Seeded fault injection (step faults, transient alloc failures,
    NaN-poisoned logits) on the mesh engine: the retry / quarantine /
    replay machinery must keep the token streams equal to the clean
    single-device ground truth."""
    from repro.serving import ChaosConfig, MeshConfig

    chaos = ChaosConfig(seed=3, step_fault_rate=0.1, alloc_fault_rate=0.1,
                        nan_rate=0.05)
    for arch in ("llama3.2-3b", "mamba2-130m"):
        cfg = dataclasses.replace(get_config(arch, smoke=True), dtype=jnp.float32)
        want, _ = _serve_tokens(cfg, MeshConfig())
        got, m = _serve_tokens(cfg, MeshConfig(dp=2, mp=2), chaos=chaos)
        injected = sum(m["injected"].values())
        assert injected > 0, "chaos harness injected nothing"
        assert want == got, f"{arch}: chaos-arm tokens diverged"
        print(f"mesh serving chaos identity ok ({arch}): {injected} faults")


def check_prepack_shard_equality():
    """A tensor-parallel shard packed against the *global* tanh
    normalizer equals a column slice of the single-device prepack —
    words, scales, and kernel outputs alike — so mesh engines never
    repack after a collective."""
    from repro.core.quant import weight_tanh_max
    from repro.kernels.packed_matmul.ops import (
        choose_config, packed_dense, prepack_dense,
    )

    mp = 2
    for w_bits, a_bits in ((4, 4), (4, 8)):  # packed words / unpacked fallback
        pack = choose_config(w_bits, a_bits)
        n_seg = pack.n_seg if pack is not None else 1
        K, Nl = 32, 4 * n_seg  # per-shard width stays word-aligned
        w = jax.random.normal(jax.random.PRNGKey(5), (K, mp * Nl)) * 0.4
        x = jax.random.uniform(jax.random.PRNGKey(6), (3, K))
        full = prepack_dense(w, w_bits=w_bits, a_bits=a_bits)
        t_max = weight_tanh_max(w)
        full_words = full.w_packed if pack is not None else full.w_lvl
        full_out = packed_dense(x, full)
        for r in range(mp):
            shard = prepack_dense(
                w[:, r * Nl:(r + 1) * Nl], w_bits=w_bits, a_bits=a_bits,
                t_max=t_max,
            )
            words = Nl // n_seg
            shard_words = shard.w_packed if pack is not None else shard.w_lvl
            np.testing.assert_array_equal(
                np.asarray(shard_words),
                np.asarray(full_words[:, r * words:(r + 1) * words]),
                err_msg=f"w{w_bits}a{a_bits} rank {r}: packed words differ "
                        "from global slice",
            )
            assert float(shard.w_scale) == float(full.w_scale)
            assert float(shard.w_zero) == float(full.w_zero)
            np.testing.assert_array_equal(
                np.asarray(packed_dense(x, shard)),
                np.asarray(full_out[:, r * Nl:(r + 1) * Nl]),
                err_msg=f"w{w_bits}a{a_bits} rank {r}: shard output differs "
                        "from global column slice",
            )
    print("prepack shard equality ok (packed words + unpacked fallback)")


if __name__ == "__main__":
    check_train_parity()
    check_moe_all_to_all()
    check_moe_decode_psum()
    check_checkpoint_reshard()
    check_prepack_shard_equality()
    check_mesh_serving_token_identity()
    check_mesh_serving_under_chaos()
    print("ALL MULTIDEVICE CHECKS PASSED")
