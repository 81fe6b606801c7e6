"""Pallas TPU kernel: Kernel-Packing matmul on int32 VPU lanes.

TPU adaptation of the paper's Kernel Packing (Eq. 1): the DSP48E2 wide
multiplier becomes the VPU's int32 multiply lane, modeled as a 15x15
unsigned multiplier so every packed partial product stays < 2**30.
``n_seg`` weight levels from adjacent output channels are packed at
``stride``-bit segments into one int32; one integer multiply by an
activation level then computes ``n_seg`` products simultaneously, and a
segment sum stays decodable for ``acc_chunk`` accumulations (Eq. 4's
exact guard-bit bound), after which segments are peeled into int32
accumulators.

## Overpacking (overlap == 1, §IV-B-1)

With ``overlap=1`` the placement steals one guard bit per segment:
adjacent segments share a bit, buying either one extra segment per lane
(denser packing, e.g. w2a3 fits 3 channels instead of 2) or — at equal
density — one extra decoded bit, doubling ``acc_chunk`` and halving the
peel rounds (w4a4: 18 vs 9).  The stolen MSB of each segment is
recovered in-kernel by the paper's Fig. 3 chain: the true LSB of the
*next* segment is recomputed from operand LSBs (AND per product, XOR
over the accumulation chunk), which collapses into one extra integer dot
of the activation LSBs against the weight-LSB planes plus a bottom-up
subtract-and-shift peel — see :mod:`repro.kernels.peel` for the
derivation and ``core.packing.bitpack`` for the Python-int oracle it is
tested against.  The LSB planes cost no storage or extra DMA: because
``stride >= w_bits``, bit ``d*stride`` of the packed word already *is*
segment d's LSB, so one AND against a compile-time mask materializes
them from the weight tile that is resident anyway, and decode-time
recovery costs one XOR per segment.

## Performance

The reduction runs on a 3-D ``(m, n, k)`` grid with the K axis
innermost, so one ``[bm, bk] x [bk, bnp]`` tile pair is resident in VMEM
per step instead of the whole K dimension, and the grid-level pipeline
overlaps the next tile's DMA with the current tile's compute.  ``bnp``
counts packed words (lanes), so every block stays on the TPU's (8, 128)
tiling.  A VMEM scratch accumulator of shape ``[n_seg, bm, bnp]``
carries the peeled per-segment sums across K steps: it is zeroed when
``k == 0`` (the first visit to an output tile — output revisiting is
only legal because the K grid axis is sequential) and copied to the
output tile on the last K step.  When the whole K reduction fits one
step (``grid_k == 1``, the common serve case) a scratch-free kernel body
writes the output tile directly.  Kernels emit ``[n_seg, M, Np]``
segment planes; the wrapper interleaves them back to channel order in
XLA (``peel.interleave``).

Within a K step the reduction runs in lane-aligned groups under a
``fori_loop`` and the packed->peel cadence is preserved inside each
group (see :mod:`repro.kernels.peel`): int32 operands multiply on the
VPU, int8 operands on the MXU, and every formulation is bit-identical;
the property tests and ``tests/diffcheck.py`` cover every placement.

``block_k=None`` is backend-adaptive: 256 when compiling for TPU (the
VMEM-residency bound the blocking exists for), whole-K in interpret
mode, where "VMEM" is host memory and extra grid steps are pure
overhead (~1.6x at M=8, K=1024 shapes).  The wrapper zero-pads all
three dimensions up to block multiples (a K block above
:data:`~repro.kernels.peel.GROUP` rounds up to a multiple of it), which
is exact because zero levels contribute nothing to any segment
(including the LSB-parity planes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.peel import GROUP, interleave, packed_reduce


# whole-K tiles outgrow the default 16 MiB scoped VMEM (a v5e compile at
# bm=128, K=3072 asked for 19.3 MiB); v5e has 128 MiB
FUSED_VMEM_BYTES = 64 * 2**20


def _store(o_ref, segs):
    for d, seg in enumerate(segs):
        o_ref[d] = seg


def _kernel_single_k(a_ref, wp_ref, o_ref, **opts):
    segs = packed_reduce(a_ref, wp_ref, **opts)
    _store(o_ref, segs)


def _kernel_blocked(a_ref, wp_ref, o_ref, acc_ref, **opts):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    segs = packed_reduce(a_ref, wp_ref, **opts)
    for d, seg in enumerate(segs):
        acc_ref[d] += seg

    @pl.when(k_idx == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def _kernel_fused(x_ref, wp_ref, o_ref, asum_ref, a_ref, *, a_bits, **opts):
    # quantize the activation tile once into VMEM (clip -> round -> levels)
    n_lvl = (1 << a_bits) - 1
    a_ref[...] = jnp.round(jnp.clip(x_ref[...], 0.0, 1.0) * n_lvl).astype(jnp.int32)
    asum_ref[...] = jnp.sum(a_ref[...], axis=1, keepdims=True)
    segs = packed_reduce(a_ref, wp_ref, **opts)
    _store(o_ref, segs)


def _blocks(m, k, np_, block_m, block_n, block_k):
    """Clamp the block sizes to the operand and align the K block to the
    reduction's lane groups; returns ``(bm, bnp, bk, grid)``."""
    bm = min(block_m, m)
    bnp = min(block_n, np_)
    bk = min(block_k, k)
    if bk > GROUP:
        bk = -(-bk // GROUP) * GROUP
    grid = (-(-m // bm), -(-np_ // bnp), -(-k // bk))
    return bm, bnp, bk, grid


def packed_dense_fused_raw(
    x: jax.Array,  # [M, K] float activations in [0, 1]
    w_packed: jax.Array,  # [K, N // n_seg] int32 packed weight levels
    *,
    a_bits: int,
    n_seg: int,
    stride: int,
    acc_chunk: int,
    overlap: int = 0,
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Single fused kernel for the prepacked serve path: quantizes the
    activation tile in-kernel (clip -> round -> levels), runs the packed
    reduction over the whole K (no K grid — the serving fast path keeps
    the K tile resident), and also emits the per-row level sums needed by
    the zero-point fold.  Returns ``(acc [M, N] int32, a_sum [M] int32)``.

    ``block_n`` counts packed words per block.  One kernel launch
    replaces quantize + a_sum + matmul; the activation quantization
    recomputes per N block.
    """
    from repro.kernels.common import pad_to, resolve_interpret

    interpret = resolve_interpret(interpret)
    m, k = x.shape
    _, np_ = w_packed.shape
    bm, bnp, bk, grid = _blocks(m, k, np_, block_m, block_n, k)
    x = pad_to(x, grid[0] * bm, bk)
    w_packed = pad_to(w_packed, bk, grid[1] * bnp)
    kernel = functools.partial(
        _kernel_fused, a_bits=a_bits, n_seg=n_seg, stride=stride,
        acc_chunk=acc_chunk, overlap=overlap,
    )
    acc, a_sum = pl.pallas_call(
        kernel,
        grid=grid[:2],
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, bnp), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((n_seg, bm, bnp), lambda i, j: (0, i, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_seg, grid[0] * bm, grid[1] * bnp), jnp.int32),
            jax.ShapeDtypeStruct((grid[0] * bm, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.int32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=FUSED_VMEM_BYTES),
        interpret=interpret,
    )(x, w_packed)
    return interleave(acc[:, :m, :np_]), a_sum[:m, 0]


def packed_matmul_raw(
    a_lvl: jax.Array,  # [M, K] activation levels (unsigned, < 2**a_bits)
    w_packed: jax.Array,  # [K, N // n_seg] packed weight levels
    *,
    n_seg: int,
    stride: int,
    acc_chunk: int,
    overlap: int = 0,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Integer matmul of levels; returns [M, N] int32 accumulator.

    Operands may be int32 (the VPU lane path) or int8 (the MXU lane path
    — ``kernels.quant_matmul.quant_packed_matmul_raw``); the product
    always accumulates int32.  ``block_n`` counts packed words per block.
    ``overlap=1`` runs the overpacked decode (the weight-LSB planes it
    needs are a masked view of ``w_packed`` — see the module docstring).
    """
    from repro.kernels.common import pad_to, resolve_block_k, resolve_interpret

    interpret = resolve_interpret(interpret)
    m, k = a_lvl.shape
    _, np_ = w_packed.shape
    block_k = resolve_block_k(block_k, k, interpret)  # see Performance note
    bm, bnp, bk, grid = _blocks(m, k, np_, block_m, block_n, block_k)
    a_lvl = pad_to(a_lvl, grid[0] * bm, grid[2] * bk)
    w_packed = pad_to(w_packed, grid[2] * bk, grid[1] * bnp)
    opts = dict(n_seg=n_seg, stride=stride, acc_chunk=acc_chunk, overlap=overlap)
    if grid[2] == 1:
        kernel = functools.partial(_kernel_single_k, **opts)
        scratch = []
    else:
        kernel = functools.partial(_kernel_blocked, **opts)
        scratch = [pltpu.VMEM((n_seg, bm, bnp), jnp.int32)]
    acc = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bnp), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((n_seg, bm, bnp), lambda i, j, kk: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((n_seg, grid[0] * bm, grid[1] * bnp), jnp.int32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(a_lvl, w_packed)
    return interleave(acc[:, :m, :np_])
