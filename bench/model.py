"""A configuration file -> the program's ModelConfig, and its weights.

Weights are made on the device from the seed in one jitted call, layer by
layer inside a ``lax.map``, straight into the packed form the engine
reads: no float tree of the whole model ever exists.  The float weights
of one layer come from :func:`layer_float`, which the float32 reference
calls again, layer by layer, to rebuild the same numbers on its own.

Values are drawn as raw PRNG bits and turned into uniform floats by exact
arithmetic, so the program's weights and the reference's agree to the bit
whichever program computes them; only the SSM's ``a_log`` and
``dt_bias``, shaped by ``exp`` and ``log``, may differ by an ulp.

The SSM leaves follow ``mamba_ssm``'s own initialisation, so the state
carries history: ``dt = softplus(dt_bias)`` log-uniform in [1e-3, 0.1]
and ``A`` uniform in [1, 16], so one token decays the state by
``exp(-dt * A)`` between 0.2 and nearly 1; conv weights and bias, and the
dense ``dt`` projection, uniform in +-1/sqrt(fan-in) (PyTorch's default).
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Model:
    name: str
    cfg: object  # repro.models.transformer.ModelConfig
    w_bits: int
    a_bits: int
    engine: dict

    @property
    def dims(self) -> "Dims":
        return dims(self.cfg)


@dataclasses.dataclass(frozen=True)
class Dims:
    """The numbers the benchmark's own code reads: the weight generator,
    the reference and the counters never call into the program."""

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


def _expect(cfg) -> dict:
    """The configuration file's numbers, as the preset holds them."""
    return {"n_layer": cfg.n_layers, "d_model": cfg.d_model, "vocab_size": cfg.vocab}


def load_model(name: str, *, smoke: bool = False) -> Model:
    """Read ``configs/<name>.json`` and build the program's preset from it.

    The preset must hold the file's numbers: a program change that moved
    a width fails here, before any run.  ``smoke=True`` takes the
    preset's tiny CPU variant instead (tests only)."""
    from repro.configs import get_config

    doc = json.loads((ROOT / "configs" / f"{name}.json").read_text())
    cfg = get_config(doc["preset"], smoke=smoke)
    if cfg.family != "ssm":
        raise ValueError(f"{name}: the benchmark serves Mamba2 presets, not {cfg.family!r}")
    if not smoke:
        for key, have in _expect(cfg).items():
            if doc[key] != have:
                raise ValueError(f"{name}: preset {doc['preset']} has {key}={have}, "
                                 f"the configuration file {doc[key]}")
        if (cfg.ssm_state, cfg.ssm_head_dim) != (128, 64):
            raise ValueError(f"{name}: preset SSM sizes differ from Mamba2 defaults")
    return Model(name, cfg, doc["bits"]["w_bits"], doc["bits"]["a_bits"], doc["engine"])


# -- float weights, one layer at a time ----------------------------------


def _uniform(key, shape, scale):
    """Uniform on [-scale, scale) from raw bits, by exact float steps."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    unit = (bits >> 8).astype(jnp.float32) * np.float32(2.0 ** -23) - np.float32(1.0)
    return unit * np.float32(scale)


def _fan_in(key, k, n):
    # same variance as N(0, 1/k), the program's own init
    return _uniform(key, (k, n), np.sqrt(3.0 / k))


def layer_spec(dm: Dims) -> dict[str, tuple]:
    """Leaf path -> (shape, kind) of one layer, in the program's layout.
    ``proj`` leaves are the packed projections."""
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
    """Float32 weights of layer ``i`` (traceable in ``seed32`` and ``i``),
    as a flat dict keyed like :func:`layer_spec`."""
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


def embed_float(seed32, dm: Dims) -> jax.Array:
    key = jax.random.fold_in(jax.random.key(seed32), 1 << 20)
    return _uniform(key, (dm.vocab, dm.d_model), 0.01 * np.sqrt(3.0))


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return out


def make_packed_params(model: Model, seed: int):
    """The engine's serving params, made on the default device from the
    seed in one jitted call: packed projections (the program's own
    ``prepack_dense``), float norms, SSM leaves and tied embedding."""
    from repro.kernels.packed_matmul.ops import prepack_dense
    from bench.traffic import seed32 as to32

    dm, wb, ab = model.dims, model.w_bits, model.a_bits
    spec = layer_spec(dm)

    def one(s32, i):
        flat = layer_float(s32, dm, i)
        packed = {
            p: prepack_dense(w, w_bits=wb, a_bits=ab) if spec[p][1] == "proj" else w
            for p, w in flat.items()
        }
        return _nest(packed)

    @jax.jit
    def build(s32):
        layers = jax.lax.map(lambda i: one(s32, i), jnp.arange(dm.n_layers))
        return {
            "embed": embed_float(s32, dm),
            "final_ln": {"g": jnp.ones((dm.d_model,), jnp.float32)},
            "layers": layers,
        }

    return build(jnp.uint32(to32(seed)))


def check_layout(model: Model, params) -> None:
    """The made tree must match what ``build_engine`` would make from the
    program's own ``init_params`` (same structure, shapes and dtypes)."""
    from repro.models import transformer as T
    from repro.serving.api import quantize_params_packed

    want = jax.eval_shape(
        lambda: quantize_params_packed(
            T.init_params(jax.random.PRNGKey(0), model.cfg),
            w_bits=model.w_bits, a_bits=model.a_bits, verbose=False,
        )
    )
    have = jax.eval_shape(lambda: params)
    if jax.tree.structure(want) != jax.tree.structure(have):
        raise ValueError(f"{model.name}: weight tree differs from the program's layout")
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have)):
        if (a.shape, a.dtype) != (b.shape, b.dtype):
            raise ValueError(f"{model.name}: leaf {a.shape}/{a.dtype} made as {b.shape}/{b.dtype}")
