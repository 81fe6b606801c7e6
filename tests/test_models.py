"""Model substrate invariants: attention, SSD, MoE, quant layers."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L
from repro.models.mamba import (
    MambaSpec,
    mamba_decode,
    mamba_decode_chunk,
    mamba_init,
    mamba_train,
)
from repro.models.moe import MoESpec, moe_apply, moe_init, moe_reference


def test_rope_preserves_norm_and_relativity():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1, 6, 2, 8))
    pos = jnp.arange(6)[None, :]
    r = L.rope(x, pos)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x)), np.linalg.norm(np.asarray(r)), rtol=1e-5
    )
    # relative property: <rope(q,i), rope(k,j)> depends only on i-j
    q = jax.random.normal(key, (1, 1, 1, 8))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 1, 8))
    def score(i, j):
        qi = L.rope(q, jnp.asarray([[i]]))
        kj = L.rope(k, jnp.asarray([[j]]))
        return float(jnp.sum(qi * kj))
    assert abs(score(3, 1) - score(7, 5)) < 1e-4


def test_mrope_sections_differ():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1, 4, 1, 12))
    p_same = jnp.tile(jnp.arange(4)[None, :, None], (1, 1, 3))
    p_diff = p_same.at[..., 1].set(0)
    a = L.mrope(x, p_same)
    b = L.mrope(x, p_diff)
    assert not np.allclose(np.asarray(a), np.asarray(b))


def test_attention_train_decode_consistency():
    """Teacher-forced train forward logits == step-by-step decode."""
    spec = L.AttnSpec(d_model=32, n_heads=4, kv_heads=2, head_dim=8, q_chunk=64)
    params = L.attn_init(jax.random.PRNGKey(0), spec)
    B, S = 2, 8
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, 32)) * 0.5
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    full = L.attention_train(params, spec, x, pos)
    ck = jnp.zeros((B, S, 2 * 8))
    cv = jnp.zeros((B, S, 2 * 8))
    outs = []
    for t in range(S):
        o, ck, cv = L.attention_decode(params, spec, x[:, t : t + 1], ck, cv, jnp.asarray(t))
        outs.append(o)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(dec), rtol=2e-2, atol=2e-3)


def test_sliding_window_masks_past():
    spec = L.AttnSpec(d_model=16, n_heads=2, kv_heads=2, head_dim=8, q_chunk=64)
    params = L.attn_init(jax.random.PRNGKey(0), spec)
    B, S = 1, 12
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, 16))
    pos = jnp.arange(S)[None]
    full = L.attention_train(params, spec, x, pos, window=0)
    win = L.attention_train(params, spec, x, pos, window=3)
    # early positions (< window) see identical context; late ones differ
    np.testing.assert_allclose(np.asarray(full[:, :3]), np.asarray(win[:, :3]), rtol=1e-4, atol=1e-5)
    assert not np.allclose(np.asarray(full[:, -1]), np.asarray(win[:, -1]))


def test_attention_chunked_equals_unchunked():
    spec_c = L.AttnSpec(d_model=32, n_heads=4, kv_heads=4, head_dim=8, q_chunk=4)
    spec_f = dataclasses.replace(spec_c, q_chunk=512)
    params = L.attn_init(jax.random.PRNGKey(0), spec_c)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
    a = L.attention_train(params, spec_c, x, pos)
    b = L.attention_train(params, spec_f, x, pos)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_ssd_chunk_invariance_and_decode():
    s4 = MambaSpec(d_model=32, d_state=16, head_dim=8, chunk=4)
    s16 = MambaSpec(d_model=32, d_state=16, head_dim=8, chunk=16)
    p = mamba_init(jax.random.PRNGKey(0), s4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32)) * 0.5
    y4 = mamba_train(p, s4, x)
    y16 = mamba_train(p, s16, x)
    np.testing.assert_allclose(np.asarray(y4), np.asarray(y16), rtol=1e-4, atol=1e-5)
    ssm = jnp.zeros((2, s4.n_heads, 16, 8))
    conv = jnp.zeros((2, 3, s4.d_inner + 32))
    ys = []
    for t in range(16):
        yt, ssm, conv = mamba_decode(p, s4, x[:, t : t + 1], ssm, conv)
        ys.append(yt)
    np.testing.assert_allclose(
        np.asarray(y4), np.asarray(jnp.concatenate(ys, 1)), rtol=1e-3, atol=1e-4
    )


def _lane_block_oracle(p, s, x, ssm_state, conv_state):
    """The whole Mamba2 block on one lane's rows x [B, 1, d], every phase
    per lane: the recurrent step as the lane scan used to run it."""
    f32 = jnp.float32
    B = x.shape[0]
    H, P, N = s.n_heads, s.head_dim, s.d_state
    h = L.rmsnorm(p["ln"], x)
    z, xbc, dt = (L.dense(p[k], h) for k in ("in_z", "in_xbc", "in_dt"))
    window = jnp.concatenate([conv_state, xbc], axis=1)
    conv = jnp.einsum("bkc,kc->bc", window, p["conv_w"].astype(x.dtype))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(x.dtype))
    xs = xbc[:, : s.d_inner].reshape(B, H, P)
    b, c = xbc[:, s.d_inner : s.d_inner + N], xbc[:, s.d_inner + N :]
    dt = jax.nn.softplus(dt + p["dt_bias"]).reshape(B, H)
    g = jnp.exp((dt * -jnp.exp(p["a_log"])).astype(f32))
    contrib = jnp.einsum("bh,bs,bhp->bhsp", dt.astype(f32), b.astype(f32), xs.astype(f32))
    state = ssm_state * g[:, :, None, None] + contrib
    y = jnp.einsum("bs,bhsp->bhp", c.astype(f32), state).astype(x.dtype)
    y = y + p["d_skip"].astype(x.dtype)[None, :, None] * xs
    y = y.reshape(B, 1, s.d_inner) * jax.nn.silu(z)
    out = L.dense(p["out_proj"], L.rmsnorm(p["out_norm"], y))
    return x + out, state, window[:, 1:]


def _lane_scan_oracle(p, s, x, ssm_state, conv_state, lens):
    def body(carry, j):
        st, cv = carry
        h, ns, nc = _lane_block_oracle(p, s, jax.lax.dynamic_slice_in_dim(x, j, 1, axis=1), st, cv)
        ok = j < lens
        ns = jnp.where(ok[:, None, None, None], ns, st)
        return (ns, jnp.where(ok[:, None, None], nc, cv)), h[:, 0]

    (st, cv), hs = jax.lax.scan(body, (ssm_state, conv_state), jnp.arange(x.shape[1]))
    return jnp.moveaxis(hs, 0, 1), st, cv


def _mamba_case(weights: str, dtype, B: int, C: int):
    """Small Mamba2 block (w4a4-packed or float projections), an input
    chunk and random non-zero recurrent states."""
    s = MambaSpec(d_model=32, d_state=16, head_dim=8)
    p = mamba_init(jax.random.PRNGKey(0), s)
    if weights == "w4a4":
        for k in ("in_z", "in_xbc", "out_proj"):
            p[k] = L.quantize_dense_for_packed_serving(p[k], w_bits=4, a_bits=4)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (B, C, s.d_model)).astype(dtype)
    st = jax.random.normal(ks[1], (B, s.n_heads, s.d_state, s.head_dim))
    cv = jax.random.normal(ks[2], (B, s.conv_width - 1, s.d_inner + 2 * s.d_state)).astype(dtype)
    return p, s, x, st, cv


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("weights", ["w4a4", "float"])
def test_mamba_decode_chunk_bitwise_equals_lane_scan(weights, dtype):
    """Projections once over all B*C rows, conv and SSM lane by lane: the
    valid lanes' outputs and both new states equal the per-lane block's
    scan bit for bit, for ragged lens (C, 1, partial, 0); one-lane
    ``mamba_decode`` equals the per-lane block outright."""
    C = 8
    p, s, x, st, cv = _mamba_case(weights, dtype, B=4, C=C)
    lens = jnp.asarray([C, 1, 3, 0], jnp.int32)
    got = jax.jit(lambda x, st, cv, n: mamba_decode_chunk(p, s, x, st, cv, lens=n))(x, st, cv, lens)
    want = jax.jit(lambda x, st, cv, n: _lane_scan_oracle(p, s, x, st, cv, n))(x, st, cv, lens)
    valid = np.arange(C)[None, :] < np.asarray(lens)[:, None]
    np.testing.assert_array_equal(np.asarray(got[0], np.float32)[valid], np.asarray(want[0], np.float32)[valid])
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))
    got1 = jax.jit(lambda *a: mamba_decode(p, s, *a))(x[:, :1], st, cv)
    want1 = jax.jit(lambda *a: _lane_block_oracle(p, s, *a))(x[:, :1], st, cv)
    for g, w in zip(got1, want1):
        np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))


def _pallas_calls(jaxpr, in_scan: bool = False):
    """(inside a scan body, eqn) of every ``pallas_call`` in ``jaxpr``,
    through scan and jit sub-jaxprs but not into the kernels."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield in_scan, eqn
            continue
        inner = in_scan or eqn.primitive.name == "scan"
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, Jaxpr):
                    yield from _pallas_calls(sub, inner)


def test_mamba_decode_chunk_projects_all_rows_outside_the_lane_scan():
    """The packed projections run once per block on all S*C rows: three
    kernels outside the lane scan, none inside it."""
    S, C = 4, 8
    p, s, x, st, cv = _mamba_case("w4a4", jnp.bfloat16, B=S, C=C)
    lens = jnp.asarray([C, 1, 3, 0], jnp.int32)
    jaxpr = jax.make_jaxpr(lambda *a: mamba_decode_chunk(p, s, *a, lens=lens))(x, st, cv)
    calls = list(_pallas_calls(jaxpr.jaxpr))
    assert [in_scan for in_scan, _ in calls] == [False] * 3
    assert [eqn.invars[0].aval.shape[0] for _, eqn in calls] == [S * C] * 3


def test_moe_matches_reference_when_uncapped():
    s = MoESpec(d_model=16, d_ff=32, n_experts=8, top_k=2, capacity_factor=8.0)
    p = moe_init(jax.random.PRNGKey(0), s)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 16)) * 0.5
    np.testing.assert_allclose(
        np.asarray(moe_reference(p, s, x)),
        np.asarray(moe_apply(p, s, x, axis_name=None)),
        rtol=1e-3, atol=1e-4,
    )


def test_moe_capacity_drops_fall_back_to_residual():
    s = MoESpec(d_model=16, d_ff=32, n_experts=8, top_k=2, capacity_factor=0.25)
    p = moe_init(jax.random.PRNGKey(0), s)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16))
    y = moe_apply(p, s, x, axis_name=None)
    assert bool(jnp.all(jnp.isfinite(y)))
    # some tokens must pass through unchanged (residual only)
    diffs = np.linalg.norm(np.asarray(y - x).reshape(-1, 16), axis=1)
    assert (diffs < 1e-6).any()


def test_quantized_dense_matches_fake_quant():
    from repro.core.quant import fake_quant_weight
    w = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
    qc = L.QuantConfig(bits={"proj": (4, 8)})
    params = {"w": w}
    got = L.dense(params, x, name="proj", quant=qc)
    assert got.shape == (4, 8)
    assert bool(jnp.all(jnp.isfinite(got)))


def test_serve_packed_params_exact_vs_kernel_oracle():
    """dense() with prepacked weights == the packed_dense oracle on the
    sigmoid-bounded activations (same quant semantics as the QAT path)."""
    from repro.kernels.packed_matmul.ops import PackedDenseParams, packed_dense_reference

    w = jax.random.normal(jax.random.PRNGKey(0), (48, 24))
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 48))
    pp = L.quantize_dense_for_packed_serving({"w": w}, w_bits=4, a_bits=4)
    assert isinstance(pp["w"], PackedDenseParams)
    got = L.dense(pp, x)
    want = packed_dense_reference(jax.nn.sigmoid(x), w, w_bits=4, a_bits=4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_serve_packed_params_close_to_fp():
    """Packed w4a4 serving stays a usable approximation of the fp layer
    (bounded-activation regime, matching the QAT forward semantics)."""
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64))
    pp = L.quantize_dense_for_packed_serving({"w": w}, w_bits=6, a_bits=8)
    qc = L.QuantConfig(bits={"proj": (6, 8)})
    want = L.dense({"w": w}, x, name="proj", quant=qc)  # QAT fake-quant path
    got = L.dense(pp, x)
    rel = float(jnp.linalg.norm(got - want) / (jnp.linalg.norm(want) + 1e-9))
    assert rel < 0.05, rel


def test_serve_int8_params_close_to_fp():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64))
    p8 = L.quantize_dense_for_serving({"w": w})
    full = L.dense({"w": w}, x)
    q = L.dense(p8, x)
    rel = float(jnp.linalg.norm(q - full) / jnp.linalg.norm(full))
    assert rel < 0.02


def test_int8_kv_cache_decode_close_to_bf16():
    """Beyond-paper: int8 KV cache (per-token scales) stays within ~2% of
    the bf16-cache decode logits and preserves argmax."""
    import dataclasses as dc

    from repro.configs import get_config
    from repro.models.transformer import forward_decode, init_cache, init_params

    cfg = get_config("yi-6b", smoke=True)
    cfg8 = dc.replace(cfg, kv_dtype="int8")
    p = init_params(jax.random.PRNGKey(0), cfg)
    B = 2
    c16, c8 = init_cache(cfg, B, 32), init_cache(cfg8, B, 32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, 8), 0, cfg.vocab)
    for t in range(8):
        l16, c16 = forward_decode(p, cfg, c16, toks[:, t : t + 1], jnp.asarray(t, jnp.int32))
        l8, c8 = forward_decode(p, cfg8, c8, toks[:, t : t + 1], jnp.asarray(t, jnp.int32))
    rel = float(jnp.linalg.norm(l8 - l16) / jnp.linalg.norm(l16))
    assert rel < 0.05, rel
    assert bool(jnp.all(jnp.argmax(l8, -1) == jnp.argmax(l16, -1)))
