"""Shared in-kernel segment peel for the packed matmul kernels.

One chunked packed-dot + segment-peel implementation serves both the
int32 VPU kernel (``kernels/packed_matmul``) and the int8 MXU-lane
packed path (``kernels/quant_matmul``): the arithmetic is identical,
only the operand storage dtype differs (the dot always accumulates
int32 via ``preferred_element_type``).

## Chunk product

int8 operands take the MXU's int8 x int8 -> int32 dot.  int32 operands
(the VPU lane path) cannot: the v5e MXU has no int32 mode, and Mosaic
refuses an int32 ``dot_general``.  Their chunk product is a static
unroll of rank-1 updates ``a[:, k:k+1] * w[k:k+1, :]`` on the VPU —
integer multiply-adds modulo 2**32, so the result equals the int32
accumulating dot bit for bit.

The reduction walks K in lane-aligned groups of :data:`GROUP` rows
under a ``fori_loop`` (one aligned dynamic slice of the activation and
weight refs per iteration), and within a group in static
``acc_chunk``-row chunks.  Any chunk no longer than ``acc_chunk``
decodes exactly, so chunks restarting at each group boundary change
nothing.  Per-segment sums stay separate ``[bm, bnp]`` arrays (no
scatter-add, which the TPU lowering lacks); the kernels write them as a
``[n_seg, M, Np]`` block and the wrapper restores channel order.

## No-overpack peel (overlap == 0)

Every segment sum fits ``stride`` bits, so segments are independent
bit-slices of the chunk product: ``(part >> d*stride) & mask``.

## Overpacked peel (overlap == 1, paper §IV-B-1 / Fig. 3)

Overpacking steals one guard bit: each segment sum may need
``stride + 1`` bits, its MSB colliding with the next segment's LSB.  The
stolen bit is recovered from the operands, not the product: the true LSB
of a *sum* of products is the XOR of the per-product LSBs, and the LSB
of one product is the AND of its operand LSBs
(``bitpack.lsb_of_segment_products`` is the Python-int oracle).  In
kernel form the whole AND/XOR tree collapses into a second integer dot:

    parity = dot(a & 1, wp & LSB_MASK)       # LSB_MASK = sum_d 2**(d*stride)

The weight-LSB planes need **no separate storage**: every placement has
``stride >= w_bits`` (segments cannot be narrower than the operand they
carry), so bit ``d*stride`` of the packed word *is* segment d's LSB —
one AND against a compile-time mask materializes the planes the paper's
Fig. 3 reads from registers, costing zero extra weight bytes or DMA.

XOR over the chunk == popcount mod 2, and the per-segment popcounts land
segment-aligned in ``parity`` because the chunk bound keeps every count
below ``2**stride`` (see ``core.packing.select.kernel_acc_chunk``).
Segments then peel **bottom-up** — a sequential carry chain, unlike the
independent no-overpack slices:

    low    = p & (2**stride - 1)             # exact: S_0's low bits
    bit_p  = (p >> stride) & 1               # = msb(S_0) XOR lsb(S_1)
    msb    = bit_p XOR parity(S_1)           # Fig. 3 correction
    S_0    = low + (msb << stride)
    p      = (p - S_0) >> stride             # recurse on S_1..

The last segment keeps all remaining bits (it owns the container top).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# K rows per reduction-loop iteration: one 128-lane activation slice
GROUP = 128


def packed_dot(a, w):
    """Chunk product ``a @ w`` with int32 accumulation (see module doc):
    the MXU dot for int8 operands, VPU rank-1 updates for int32."""
    if a.dtype == jnp.int8:
        return jax.lax.dot_general(
            a, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
        )
    out = a[:, 0:1] * w[0:1, :]
    for k in range(1, a.shape[1]):
        out = out + a[:, k : k + 1] * w[k : k + 1, :]
    return out


def lsb_mask(n_seg: int, stride: int) -> int:
    """Compile-time mask selecting each segment's LSB from a packed word
    (bit d*stride of ``pack_weights`` output is level d's LSB, because
    every placement has stride >= operand bits)."""
    return sum(1 << (d * stride) for d in range(n_seg))


def peel_segments(a, wp, *, n_seg: int, stride: int, overlap: int) -> list:
    """One chunk's packed product, peeled into ``n_seg`` segment sums."""
    mask = (1 << stride) - 1
    p = packed_dot(a, wp)
    if not overlap:
        return [jax.lax.shift_right_logical(p, d * stride) & mask for d in range(n_seg)]
    # Fig. 3 LSB recovery: per-segment popcount of operand-LSB ANDs; bit 0
    # of each stride-aligned counter is the XOR chain
    parity = packed_dot(a & 1, wp & lsb_mask(n_seg, stride))
    segs = []
    for d in range(n_seg - 1):
        low = p & mask
        bit_p = jax.lax.shift_right_logical(p, stride) & 1
        lsb_next = jax.lax.shift_right_logical(parity, (d + 1) * stride) & 1
        val = low + ((bit_p ^ lsb_next) << stride)
        p = jax.lax.shift_right_logical(p - val, stride)
        segs.append(val)
    segs.append(p)  # top segment keeps all remaining bits
    return segs


def packed_reduce(a_ref, wp_ref, *, n_seg: int, stride: int, acc_chunk: int,
                  overlap: int) -> tuple:
    """Packed reduction of one ``[bm, bk] x [bk, bnp]`` tile pair of
    activation levels (int32 or int8) and packed weights.

    Returns ``n_seg`` int32 ``[bm, bnp]`` segment sums: segment d holds
    output channels ``j*n_seg + d``.  ``bk`` must be at most
    :data:`GROUP` or a multiple of it.
    """
    bm, bk = a_ref.shape
    bnp = wp_ref.shape[1]
    group = min(GROUP, bk)
    assert bk % group == 0, (bk, group)

    def body(g, segs):
        k0 = pl.multiple_of(g * group, group)
        a = a_ref[:, pl.ds(k0, group)]
        w = wp_ref[pl.ds(k0, group), :]
        for c0 in range(0, group, acc_chunk):
            c1 = min(c0 + acc_chunk, group)
            part = peel_segments(a[:, c0:c1], w[c0:c1, :], n_seg=n_seg,
                                 stride=stride, overlap=overlap)
            segs = tuple(s + v for s, v in zip(segs, part))
        return segs

    init = tuple(jnp.zeros((bm, bnp), jnp.int32) for _ in range(n_seg))
    return jax.lax.fori_loop(0, bk // group, body, init)


def interleave(acc):
    """Restore channel order: out[:, j*n_seg + d] = acc[d, :, j]."""
    n_seg, m, np_ = acc.shape
    return jnp.moveaxis(acc, 0, -1).reshape(m, np_ * n_seg)
