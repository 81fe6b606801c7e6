"""Mamba2 (``cfg.family == "ssm"``): sizes, weights, reference and counts.

Weights.  The SSM leaves follow ``mamba_ssm``'s own initialisation, so
the state carries history: ``dt = softplus(dt_bias)`` log-uniform in
[1e-3, 0.1] and ``A`` uniform in [1, 16], so one token decays the state
by ``exp(-dt * A)`` between 0.2 and nearly 1; conv weights and bias, and
the dense ``dt`` projection, uniform in +-1/sqrt(fan-in) (PyTorch's
default).  Only ``a_log`` and ``dt_bias``, shaped by ``exp`` and ``log``,
may differ by an ulp between the program's build and the reference's.
The LM head is tied to the float embedding.

Reference.  One layer runs the Mamba2 block over whole sequences:
norm, the packed ``in_z`` and ``in_xbc`` projections, the dense ``dt``
projection, the causal depthwise conv, the SSM recurrence in float32,
gated RMSNorm and ``out_proj``.  The conv and the recurrence never look
right, so right padding changes no target.  The control rounds the
conv, ``dt`` and the SSM's read-out too; the float32 state is not
rounded.

Counts.  The engine's chunk step runs the norm, the three packed
projections, the gate and ``out_proj`` once per layer over all
``n_slots * chunk`` rows; only conv and SSM run lane by lane.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import _fan_in, _uniform
from bench.reference import HI, qdense, rmsnorm


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    vocab: int
    d_inner: int
    d_state: int
    ssm_heads: int
    head_dim: int
    conv_width: int = 4


def dims(cfg) -> Dims:
    d_inner = 2 * cfg.d_model  # Mamba2 expand = 2
    return Dims(cfg.n_layers, cfg.d_model, cfg.vocab, d_inner=d_inner,
                d_state=cfg.ssm_state, ssm_heads=d_inner // cfg.ssm_head_dim,
                head_dim=cfg.ssm_head_dim)


def check(name: str, doc: dict, cfg) -> None:
    """The configuration file's numbers, as the preset holds them, and
    the Mamba2 layer defaults the file assumes."""
    want = {"n_layer": cfg.n_layers, "d_model": cfg.d_model, "vocab_size": cfg.vocab}
    for key, have in want.items():
        if doc[key] != have:
            raise ValueError(f"{name}: preset {doc['preset']} has {key}={have}, "
                             f"the configuration file {doc[key]}")
    if (cfg.ssm_state, cfg.ssm_head_dim) != (128, 64):
        raise ValueError(f"{name}: preset SSM sizes differ from Mamba2 defaults")


# -- weights ----------------------------------------------------------------


def layer_spec(dm: Dims) -> dict[str, tuple]:
    d, conv = dm.d_model, dm.d_inner + 2 * dm.d_state
    return {
        "ln/g": ((d,), "ones"),
        "in_z/w": ((d, dm.d_inner), "proj"), "in_xbc/w": ((d, conv), "proj"),
        "in_dt/w": ((d, dm.ssm_heads), "dense"),
        "conv_w": ((dm.conv_width, conv), "conv"), "conv_b": ((conv,), "conv"),
        "a_log": ((dm.ssm_heads,), "a_log"), "dt_bias": ((dm.ssm_heads,), "dt_bias"),
        "d_skip": ((dm.ssm_heads,), "ones"), "out_norm/g": ((dm.d_inner,), "ones"),
        "out_proj/w": ((dm.d_inner, d), "proj"),
    }


def layer_float(seed32, dm: Dims, i) -> dict[str, jax.Array]:
    key = jax.random.fold_in(jax.random.key(seed32), i)
    out = {}
    for j, (path, (shape, kind)) in enumerate(layer_spec(dm).items()):
        kj = jax.random.fold_in(key, j)
        if kind == "proj":
            out[path] = _fan_in(kj, *shape)
        elif kind == "dense":
            out[path] = _uniform(kj, shape, 1.0 / np.sqrt(shape[0]))
        elif kind == "conv":  # depthwise, fan-in conv_width
            out[path] = _uniform(kj, shape, 1.0 / np.sqrt(dm.conv_width))
        elif kind == "ones":
            out[path] = jnp.ones(shape, jnp.float32)
        elif kind == "a_log":  # A uniform in [1, 16]
            out[path] = jnp.log(_uniform(kj, shape, 7.5) + np.float32(8.5))
        elif kind == "dt_bias":  # inverse softplus of dt, log-uniform in [1e-3, 0.1]
            lo, hi = np.log(1e-3), np.log(0.1)
            u = _uniform(kj, shape, (hi - lo) / 2) + np.float32((hi + lo) / 2)
            dt = jnp.maximum(jnp.exp(u), np.float32(1e-4))
            out[path] = dt + jnp.log(-jnp.expm1(-dt))
        else:
            raise ValueError(kind)
    return out


def top_float(seed32, dm: Dims) -> dict[str, jax.Array]:
    key = jax.random.fold_in(jax.random.key(seed32), 1 << 20)
    return {"embed": _uniform(key, (dm.vocab, dm.d_model), 0.01 * np.sqrt(3.0)),
            "final_ln/g": jnp.ones((dm.d_model,), jnp.float32)}


# -- reference ----------------------------------------------------------------


def ref_layer(w, x, dm: Dims, bits, rnd):
    B, T, _ = x.shape
    H, P, N, di = dm.ssm_heads, dm.head_dim, dm.d_state, dm.d_inner
    h = rnd(rmsnorm(x, w["ln/g"]))
    z = rnd(qdense(h, w["in_z/w"], bits, rnd))
    xbc = rnd(qdense(h, w["in_xbc/w"], bits, rnd))
    dt = rnd(jnp.matmul(h, w["in_dt/w"], precision=HI))
    K = w["conv_w"].shape[0]
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = rnd(sum(pad[:, k : k + T] * w["conv_w"][k] for k in range(K)) + w["conv_b"])
    xbc = rnd(jax.nn.silu(conv))
    xs = xbc[..., :di].reshape(B, T, H, P)
    b, c = xbc[..., di : di + N], xbc[..., di + N :]
    dt = rnd(jax.nn.softplus(dt + w["dt_bias"]))  # [B, T, H]
    g = jnp.exp(rnd(dt * -jnp.exp(w["a_log"])))

    def step(state, inp):
        g_t, dt_t, b_t, c_t, x_t = inp
        state = state * g_t[:, :, None, None] + jnp.einsum(
            "bh,bs,bhp->bhsp", dt_t, b_t, x_t, precision=HI)
        return state, jnp.einsum("bs,bhsp->bhp", c_t, state, precision=HI)

    seq = [jnp.moveaxis(a, 1, 0) for a in (g, dt, b, c, xs)]
    _, y = jax.lax.scan(step, jnp.zeros((B, H, N, P), jnp.float32), seq)
    y = rnd(rnd(jnp.moveaxis(y, 0, 1)) + w["d_skip"][None, None, :, None] * xs)
    y = rnd(y.reshape(B, T, di) * rnd(jax.nn.silu(z)))
    y = rnd(rmsnorm(y, w["out_norm/g"]))
    return rnd(x + rnd(qdense(y, w["out_proj/w"], bits, rnd)))


def ref_head(top, x, dm: Dims, rnd):
    """Final norm and the LM head tied to the embedding."""
    xs = rnd(rmsnorm(x, top["final_ln/g"]))
    return jnp.matmul(xs, rnd(top["embed"]).T, precision=HI)


# -- counts -------------------------------------------------------------------


def packed_projections(dm: Dims) -> list[tuple[str, int, int]]:
    """(name, K, N) of each packed projection of one layer."""
    d, conv = dm.d_model, dm.d_inner + 2 * dm.d_state
    return [("in_z", d, dm.d_inner), ("in_xbc", d, conv), ("out_proj", dm.d_inner, d)]


def packed_params(dm: Dims) -> int:
    """Packed-projection parameters of the whole model."""
    return dm.n_layers * sum(k * n for _, k, n in packed_projections(dm))


def kernel_calls(dm: Dims, n_slots: int, chunk: int) -> list[tuple[int, int, int, int]]:
    """One call per packed projection and layer, over all ``n_slots *
    chunk`` rows of the step."""
    return [(n_slots * chunk, k, n, dm.n_layers) for _, k, n in packed_projections(dm)]


def useful_ops(dm: Dims, chunks: list[tuple[int, int]], n_sampled: int) -> tuple[float, float]:
    """Integer ops: the packed projections of every valid token.  Float
    ops: the dt projection, conv and state update of every valid token,
    and the LM head of sampled rows."""
    rows = sum(n for _, n in chunks)
    int_ops = 2.0 * rows * packed_params(dm)
    conv = dm.d_inner + 2 * dm.d_state
    per_tok = (2 * dm.d_model * dm.ssm_heads + 2 * dm.conv_width * conv
               + 5 * dm.ssm_heads * dm.d_state * dm.head_dim)
    flt = float(rows * per_tok * dm.n_layers)
    flt += 2.0 * n_sampled * dm.d_model * dm.vocab
    return int_ops, flt
