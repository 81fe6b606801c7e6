"""Compile every main-path kernel for a TPU v5e chip, without the chip.

The TPU compiler is installed even where no chip is attached: it
compiles for a described ``v5e:2x2`` topology.  This catches what
interpret mode cannot — block shapes off the (8, 128) tiling, primitives
Mosaic cannot lower, scoped-VMEM overflows — at llama3.2-3b widths
(every (K, N) of its projections, M = 512 rows as in one 8-slot x
64-token serving step).  Each test asserts the kernel is still a
``tpu_custom_call`` in the compiled program.  ``interpret=False`` is
passed explicitly because the default backend here is the CPU.

The topology is described inside a fixture (never at import): only one
process may load the TPU library, and every pytest worker imports every
test file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.packed_matmul.kernel import packed_dense_fused_raw, packed_matmul_raw
from repro.kernels.packed_matmul.ops import choose_config
from repro.kernels.paged_gather.kernel import paged_gather_raw
from repro.kernels.quant_matmul.kernel import quant_matmul_raw, quant_packed_matmul_raw
from repro.kernels.quant_matmul.ops import choose_mxu_config

CFG = get_config("llama3.2-3b", smoke=False)
M = 512  # rows of one serving step: n_slots=8 x chunk_tokens=64
_Q, _KV = CFG.n_heads * CFG.hd, CFG.kv_heads * CFG.hd
SHAPES = sorted({
    (CFG.d_model, _Q), (CFG.d_model, _KV), (_Q, CFG.d_model),
    (CFG.d_model, CFG.d_ff), (CFG.d_ff, CFG.d_model),
})
SHAPE_IDS = [f"K{k}-N{n}" for k, n in SHAPES]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _assert_kernel(fn, *shapes):
    compiled = _compile(fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("bits", [(4, 4), (2, 2)], ids=["w4a4", "w2a2"])
@pytest.mark.parametrize("kernel", ["blocked", "fused"])
@pytest.mark.parametrize("kn", SHAPES, ids=SHAPE_IDS)
def test_packed_matmul_compiles_for_v5e(one_chip, kernel, bits, kn):
    w_bits, a_bits = bits
    k, n = kn
    cfg = choose_config(w_bits, a_bits)
    np_ = -(-n // cfg.n_seg)
    opts = dict(n_seg=cfg.n_seg, stride=cfg.stride, acc_chunk=cfg.acc_chunk,
                overlap=cfg.overlap, interpret=False)
    w = jax.ShapeDtypeStruct((k, np_), jnp.int32, sharding=one_chip)
    if kernel == "blocked":
        a = jax.ShapeDtypeStruct((M, k), jnp.int32, sharding=one_chip)
        _assert_kernel(lambda a, w: packed_matmul_raw(a, w, block_k=256, **opts), a, w)
    else:  # whole-K tile resident: the VMEM-heaviest configuration
        x = jax.ShapeDtypeStruct((M, k), jnp.float32, sharding=one_chip)
        _assert_kernel(
            lambda x, w: packed_dense_fused_raw(x, w, a_bits=a_bits, **opts), x, w
        )


@pytest.mark.parametrize("kn", SHAPES, ids=SHAPE_IDS)
def test_mxu_packed_w2a2_compiles_for_v5e(one_chip, kn):
    k, n = kn
    cfg = choose_mxu_config(2, 2)
    a = jax.ShapeDtypeStruct((M, k), jnp.int8, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, -(-n // cfg.n_seg)), jnp.int8, sharding=one_chip)
    _assert_kernel(
        lambda a, w: quant_packed_matmul_raw(
            a, w, n_seg=cfg.n_seg, stride=cfg.stride, acc_chunk=cfg.acc_chunk,
            overlap=cfg.overlap, interpret=False,
        ),
        a, w,
    )


@pytest.mark.parametrize("kn", SHAPES, ids=SHAPE_IDS)
def test_quant_matmul_compiles_for_v5e(one_chip, kn):
    k, n = kn
    a = jax.ShapeDtypeStruct((M, k), jnp.int8, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, n), jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((1, n), jnp.float32, sharding=one_chip)
    _assert_kernel(lambda a, w, s: quant_matmul_raw(a, w, s, interpret=False), a, w, s)


@pytest.mark.parametrize("chunk", [1, 64], ids=["decode", "prefill"])
@pytest.mark.parametrize("int8", [False, True], ids=["fp", "int8"])
def test_paged_gather_compiles_for_v5e(one_chip, int8, chunk):
    # 8 slots x 1024 tokens in 16-token pages, full-residency pool
    n_slots, n_blocks, page, width = 8, 64, 16, CFG.kv_heads * CFG.hd
    n_pages = n_slots * n_blocks + 1

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = spec((n_pages, page, width), jnp.int8 if int8 else jnp.bfloat16)
    args = [spec((n_slots, n_blocks), jnp.int32), spec((n_slots,), jnp.int32),
            spec((1,), jnp.int32), pool, pool]
    if int8:
        args += [spec((n_pages, page, 1), jnp.float32)] * 2
    _assert_kernel(
        lambda *a: paged_gather_raw(*a, chunk=chunk, out_dtype=jnp.bfloat16,
                                    interpret=False),
        *args,
    )
