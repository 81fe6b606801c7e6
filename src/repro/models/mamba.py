"""Mamba2 (SSD — state-space duality) block, chunked-scan formulation.

Train path: the sequence is split into chunks of ``chunk`` tokens; the
intra-chunk term is the quadratic masked product of the duality paper,
the inter-chunk term is a (cheap) ``lax.scan`` over chunk states
[B, H, P, N].  Decode path: O(1) recurrent state update per token, in
three phases.  The input phase (norm, ``in_z``/``in_xbc``/``in_dt``) and
the output phase (gate, gated norm, ``out_proj``) are row-wise, so they
run once over every token row of a step; only the conv window and the
SSM recurrence walk the token lanes in order.

The block layout follows mamba2: in_proj -> (z | xBC | dt), causal
depthwise conv1d(4) on xBC, SSD core, gated RMSNorm, out_proj.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.layers import NO_QUANT, QuantConfig, dense, dense_init, rmsnorm, rmsnorm_init
from repro.parallel.sharding import shard


@dataclasses.dataclass(frozen=True)
class MambaSpec:
    d_model: int
    d_state: int  # N
    head_dim: int = 64  # P
    expand: int = 2
    conv_width: int = 4
    chunk: int = 256
    # TP-local head count (None: all heads).  A mesh shard runs the block
    # with its contiguous group of heads: in_z / the x-part of in_xbc /
    # conv channels / in_dt / a_log / dt_bias / d_skip sliced per head
    # group, B and C columns replicated (they feed every head's state,
    # MQA-style), out_norm reduced globally via psum, out_proj
    # row-parallel.  d_inner then means the *local* inner width.
    shard_heads: int | None = None

    @property
    def d_inner(self) -> int:
        if self.shard_heads is not None:
            return self.shard_heads * self.head_dim
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.head_dim


def mamba_init(key, s: MambaSpec) -> dict:
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    d_in = s.d_inner
    conv_dim = d_in + 2 * s.d_state
    return {
        "ln": rmsnorm_init(s.d_model),
        # input projection split into TP-shardable (z, xBC) and the tiny,
        # replicated dt head (n_heads rarely divides the TP degree)
        "in_z": dense_init(k1, s.d_model, d_in),
        "in_xbc": dense_init(k4, s.d_model, conv_dim),
        "in_dt": dense_init(k5, s.d_model, s.n_heads),
        "conv_w": jax.random.normal(k2, (s.conv_width, conv_dim)) * 0.2,
        "conv_b": jnp.zeros((conv_dim,)),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, s.n_heads)),  # A = -exp(a_log)
        "dt_bias": jnp.zeros((s.n_heads,)),
        "d_skip": jnp.ones((s.n_heads,)),
        "out_norm": rmsnorm_init(d_in),
        "out_proj": dense_init(k3, d_in, s.d_model),
    }


def _project_in(params: dict, x: jax.Array, quant: QuantConfig):
    """Input phase, row-wise: norm, then (z, xBC, dt) of every row of x
    [..., d_model]; xBC is the conv's input."""
    h = rmsnorm(params["ln"], x)
    # named scopes land in each op's op_name metadata in the compiled HLO
    with jax.named_scope("in_proj"):
        z = dense(params["in_z"], h, name="ssm_in", quant=quant)
        xbc = dense(params["in_xbc"], h, name="ssm_in", quant=quant)
        dt = dense(params["in_dt"], h, name="ssm_dt", quant=quant)
    return z, xbc, dt


def _conv1d_causal(w: jax.Array, bias: jax.Array, x: jax.Array) -> jax.Array:
    """Depthwise causal conv over sequence: x [B, S, C], w [K, C]."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        xp,
        w[:, None, :].astype(x.dtype),  # [K, 1, C] HIO
        window_strides=(1,),
        padding="VALID",
        dimension_numbers=("NHC", "HIO", "NHC"),
        feature_group_count=x.shape[-1],
    )
    return out + bias.astype(x.dtype)


def mamba_train(params: dict, s: MambaSpec, x: jax.Array, *, quant: QuantConfig = NO_QUANT) -> jax.Array:
    """x: [B, S, d_model] -> [B, S, d_model] (residual included)."""
    B, S, _ = x.shape
    H, P, N, Q = s.n_heads, s.head_dim, s.d_state, min(s.chunk, S)
    assert S % Q == 0, "sequence must divide the SSD chunk size"
    z, xbc, dt = _project_in(params, x, quant)
    xbc = jax.nn.silu(_conv1d_causal(params["conv_w"], params["conv_b"], xbc))
    xs = xbc[..., : s.d_inner].reshape(B, S, H, P)
    b = xbc[..., s.d_inner : s.d_inner + N]
    c = xbc[..., s.d_inner + N :]
    dt = jax.nn.softplus(dt + params["dt_bias"])  # [B, S, H]
    a = -jnp.exp(params["a_log"])  # [H], negative
    log_a = (dt * a).astype(jnp.float32)  # [B, S, H] (<= 0)

    nc = S // Q
    xs_c = xs.reshape(B, nc, Q, H, P)
    b_c = b.reshape(B, nc, Q, N)
    c_c = c.reshape(B, nc, Q, N)
    dt_c = dt.reshape(B, nc, Q, H)
    la_c = log_a.reshape(B, nc, Q, H)
    cum = jnp.cumsum(la_c, axis=2)  # [B, nc, Q, H] inclusive

    # intra-chunk (quadratic, masked): y[i] += sum_{j<=i} (C_i.B_j) e^{cum_i-cum_j} dt_j x_j
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # [B,nc,Qi,Qj,H]
    mask = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    # clamp BEFORE exp: upper-triangle seg is positive and overflows fp32,
    # and where(mask, exp(inf), 0) still poisons the backward with 0*inf
    decay = jnp.exp(jnp.where(mask, seg, 0.0)) * mask
    cb = jnp.einsum("bnis,bnjs->bnij", c_c, b_c)  # [B,nc,Qi,Qj]
    scores = cb[:, :, :, :, None] * decay * dt_c[:, :, None, :, :]
    y_intra = jnp.einsum("bnijh,bnjhp->bnihp", scores.astype(x.dtype), xs_c)

    # chunk states: S_n = e^{cum_Q} S_{n-1} + sum_j e^{cum_Q - cum_j} dt_j B_j (x) x_j
    tail = jnp.exp(cum[:, :, -1:, :] - cum)  # [B,nc,Q,H]
    contrib = jnp.einsum(
        "bnqh,bnqs,bnqhp->bnhsp",
        (tail * dt_c).astype(jnp.float32),
        b_c.astype(jnp.float32),
        xs_c.astype(jnp.float32),
    )  # [B,nc,H,N,P]
    gamma = jnp.exp(cum[:, :, -1, :])  # [B,nc,H] total chunk decay

    def scan_body(state, inp):
        g, ctr = inp  # [B,H], [B,H,N,P]
        new = state * g[:, :, None, None] + ctr
        return new, state  # emit the *previous* state for inter-chunk term

    init = jnp.zeros((B, H, N, P), jnp.float32)
    _, prev_states = jax.lax.scan(
        scan_body,
        init,
        (jnp.moveaxis(gamma, 1, 0), jnp.moveaxis(contrib, 1, 0)),
    )
    prev_states = jnp.moveaxis(prev_states, 0, 1)  # [B,nc,H,N,P]

    # inter-chunk: y[i] += e^{cum_i} C_i . S_prev
    y_inter = jnp.einsum(
        "bnqh,bnqs,bnhsp->bnqhp",
        jnp.exp(cum),
        c_c.astype(jnp.float32),
        prev_states,
    ).astype(x.dtype)

    y = (y_intra + y_inter).reshape(B, S, H, P)
    y = y + params["d_skip"].astype(x.dtype)[None, None, :, None] * xs.reshape(B, S, H, P)
    y = y.reshape(B, S, s.d_inner) * jax.nn.silu(z)
    y = rmsnorm(params["out_norm"], y)
    out = dense(params["out_proj"], y, name="ssm_out", quant=quant)
    return x + shard(out, "batch", None, None)


def _out_norm(params: dict, y: jax.Array, axis_name: str | None, eps: float = 1e-6) -> jax.Array:
    """Gated-output RMSNorm; under TP the mean-square reduces over the
    *global* d_inner (psum of local sums of squares)."""
    if axis_name is None:
        return rmsnorm(params, y)
    sq = jnp.sum(jnp.square(y), axis=-1, keepdims=True, dtype=jnp.float32)
    tot = jax.lax.psum(sq, axis_name)
    d = jax.lax.psum(jnp.asarray(y.shape[-1], jnp.float32), axis_name)
    var = tot / d
    return (y * jax.lax.rsqrt(var + eps).astype(y.dtype)) * params["g"].astype(y.dtype)


def _decode_lane(
    params: dict,
    s: MambaSpec,
    xbc: jax.Array,  # [B, conv_dim] this lane's conv input
    dt: jax.Array,  # [B, H] this lane's dt projection
    ssm_state: jax.Array,  # [B, H, N, P] float32
    conv_state: jax.Array,  # [B, conv_width-1, conv_dim]
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Conv window and SSM update of one token lane, the part of the step
    that needs lanes in order; returns (y [B, d_inner], ssm_state,
    conv_state)."""
    B = xbc.shape[0]
    H, P, N = s.n_heads, s.head_dim, s.d_state
    with jax.named_scope("conv"):
        window = jnp.concatenate([conv_state, xbc[:, None]], axis=1)  # [B, K, conv_dim]
        conv_out = jnp.einsum("bkc,kc->bc", window, params["conv_w"].astype(xbc.dtype)) + params[
            "conv_b"
        ].astype(xbc.dtype)
        xbc = jax.nn.silu(conv_out)
        new_conv_state = window[:, 1:, :]
    with jax.named_scope("ssm"):
        xs = xbc[:, : s.d_inner].reshape(B, H, P)
        b = xbc[:, s.d_inner : s.d_inner + N]
        c = xbc[:, s.d_inner + N :]
        dt = jax.nn.softplus(dt + params["dt_bias"])
        a = -jnp.exp(params["a_log"])
        g = jnp.exp((dt * a).astype(jnp.float32))  # [B, H]
        contrib = jnp.einsum("bh,bs,bhp->bhsp", dt.astype(jnp.float32), b.astype(jnp.float32), xs.astype(jnp.float32))
        new_state = ssm_state * g[:, :, None, None] + contrib
        y = jnp.einsum("bs,bhsp->bhp", c.astype(jnp.float32), new_state).astype(xbc.dtype)
        y = y + params["d_skip"].astype(xbc.dtype)[None, :, None] * xs
    return y.reshape(B, s.d_inner), new_state, new_conv_state


def _decode_out(
    params: dict,
    x: jax.Array,  # [..., d_model] the block's input
    y: jax.Array,  # [..., d_inner] SSM output of every row
    z: jax.Array,  # [..., d_inner] gate of every row
    quant: QuantConfig,
    axis_name: str | None,
) -> jax.Array:
    """Output phase, row-wise: gate, gated norm, projection, residual."""
    with jax.named_scope("out_proj"):
        y = y * jax.nn.silu(z)
        y = _out_norm(params["out_norm"], y, axis_name)
        out = dense(params["out_proj"], y, name="ssm_out", quant=quant)
        if axis_name is not None:
            out = jax.lax.psum(out, axis_name)
    return x + out


def mamba_decode(
    params: dict,
    s: MambaSpec,
    x: jax.Array,  # [B, 1, d_model]
    ssm_state: jax.Array,  # [B, H, N, P] float32
    conv_state: jax.Array,  # [B, conv_width-1, conv_dim]
    *,
    quant: QuantConfig = NO_QUANT,
    axis_name: str | None = None,  # mesh model axis: heads sharded over it
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token recurrent step; returns (out, ssm_state, conv_state).

    With ``axis_name`` set (inside a shard_map), ``s`` carries
    ``shard_heads`` and ``params`` hold this shard's head-group slices
    (see :class:`MambaSpec`); per-head recurrence is computed exactly as
    on one device, and the row-parallel out_proj is psum-reduced before
    the replicated residual add.
    """
    z, xbc, dt = _project_in(params, x, quant)
    y, new_state, new_conv_state = _decode_lane(params, s, xbc[:, 0], dt[:, 0], ssm_state, conv_state)
    return _decode_out(params, x, y[:, None], z, quant, axis_name), new_state, new_conv_state


def mamba_decode_chunk(
    params: dict,
    s: MambaSpec,
    x: jax.Array,  # [B, C, d_model] a chunk of C token lanes per sequence
    ssm_state: jax.Array,  # [B, H, N, P] float32
    conv_state: jax.Array,  # [B, conv_width-1, conv_dim]
    *,
    lens: jax.Array | None = None,  # [B] int32 valid lanes (None: all C)
    quant: QuantConfig = NO_QUANT,
    axis_name: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Recurrent step over a C-token chunk (chunked-prefill serving).

    The input and output phases run once over all ``B * C`` rows, so each
    projection is one matmul per layer.  Between them a scan over the
    lane axis carries the conv window and the SSM state, so each lane
    sees the state left by the previous one: token-exact with C separate
    single-token steps.  Lanes ``j >= lens[b]`` leave sequence ``b``'s
    recurrent state untouched, so decode slots (one valid lane) ride in
    the same jitted iteration as slots prefilling full chunks.
    """
    C = x.shape[1]
    xt = jnp.moveaxis(x, 1, 0)  # lane-major, so the scan reads whole lanes
    z, xbc, dt = _project_in(params, xt, quant)

    def body(carry, lane):
        st, cv = carry
        xbc_j, dt_j, j = lane
        y, ns, nc = _decode_lane(params, s, xbc_j, dt_j, st, cv)
        if lens is not None:
            ok = j < lens  # [B]
            ns = jnp.where(ok[:, None, None, None], ns, st)
            nc = jnp.where(ok[:, None, None], nc, cv)
        return (ns, nc), y

    (ns, nc), ys = jax.lax.scan(body, (ssm_state, conv_state), (xbc, dt, jnp.arange(C)))
    return jnp.moveaxis(_decode_out(params, xt, ys, z, quant, axis_name), 0, 1), ns, nc
