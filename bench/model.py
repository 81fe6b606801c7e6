"""A configuration file -> the program's ModelConfig, its family, its weights.

Everything that depends on the model's block comes from the family
module ``families/<cfg.family>.py`` (the interface is in
``families/__init__.py``); this module holds what every family shares.

Weights are made on the device from the seed in one jitted call, layer by
layer inside a ``lax.map``, straight into the packed form the engine
reads: no float tree of the whole model ever exists.  The float weights
of one layer come from the family's ``layer_float``, which the float32
reference calls again, layer by layer, to rebuild the same numbers on
its own.

Values are drawn as raw PRNG bits and turned into uniform floats by exact
arithmetic (:func:`_uniform`), so the program's weights and the
reference's agree to the bit whichever program computes them.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
FAMILIES = ROOT / "families"


@dataclasses.dataclass(frozen=True)
class Model:
    name: str
    cfg: object  # repro.models.transformer.ModelConfig
    family: object  # the module families/<cfg.family>.py
    w_bits: int
    a_bits: int
    engine: dict

    @property
    def dims(self):
        return self.family.dims(self.cfg)


@functools.cache
def _load_family(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(f"bench_family_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up while it runs
    spec.loader.exec_module(mod)
    return mod


def family(name: str):
    """The module ``families/<name>.py``, loaded by path."""
    path = FAMILIES / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"the benchmark has no module for model family {name!r}: add {path}")
    return _load_family(path)


def load_model(name: str, *, smoke: bool = False) -> Model:
    """Read ``configs/<name>.json`` and build the program's preset from it.

    The preset must hold the file's numbers (the family's ``check``): a
    program change that moved a width fails here, before any run.
    ``smoke=True`` takes the preset's tiny CPU variant instead (tests
    only)."""
    from repro.configs import get_config

    doc = json.loads((ROOT / "configs" / f"{name}.json").read_text())
    cfg = get_config(doc["preset"], smoke=smoke)
    fam = family(cfg.family)
    if not smoke:
        fam.check(name, doc, cfg)
    return Model(name, cfg, fam, doc["bits"]["w_bits"], doc["bits"]["a_bits"], doc["engine"])


# -- what the family modules share -----------------------------------------


def _uniform(key, shape, scale):
    """Uniform on [-scale, scale) from raw bits, by exact float steps."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    unit = (bits >> 8).astype(jnp.float32) * np.float32(2.0 ** -23) - np.float32(1.0)
    return unit * np.float32(scale)


def _fan_in(key, k, n):
    # same variance as N(0, 1/k), the program's own init
    return _uniform(key, (k, n), np.sqrt(3.0 / k))


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
    seed in one jitted call: the family's ``"proj"`` leaves packed by the
    program's own ``prepack_dense``, every other leaf as the family makes
    it."""
    from repro.kernels.packed_matmul.ops import prepack_dense
    from bench.traffic import seed32 as to32

    fam, dm, wb, ab = model.family, model.dims, model.w_bits, model.a_bits
    spec = fam.layer_spec(dm)

    def one(s32, i):
        flat = fam.layer_float(s32, dm, i)
        packed = {
            p: prepack_dense(w, w_bits=wb, a_bits=ab) if spec[p][1] == "proj" else w
            for p, w in flat.items()
        }
        return _nest(packed)

    @jax.jit
    def build(s32):
        layers = jax.lax.map(lambda i: one(s32, i), jnp.arange(dm.n_layers))
        return {**_nest(fam.top_float(s32, dm)), "layers": layers}

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
