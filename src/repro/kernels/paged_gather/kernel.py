"""Pallas paged-attention gather: the block table drives the index map.

Derivation.  The continuous-batching decode step reads its KV history
through per-slot block tables: ``pool[block_table]`` materializes a
``[S, T, D]`` gathered view (T = n_blocks * page_size) in HBM on every
fused step — the one hot-path tensor the packed compute kernels never
touch, and pure memory movement in exactly the memory-bound regime the
paper's DSP-packing wins target.  This kernel moves the indirection into
the memory system instead: the grid is ``(n_slots, n_blocks)`` and the
block table rides as a **scalar-prefetched** operand, so the K/V pool
BlockSpec's index map (:func:`repro.kernels.common.table_page_spec`)
resolves grid step ``(s, b)`` to physical page ``block_table[s, b]`` and
streams exactly that page from the pool into a VMEM tile.  Pages no
table row references are never loaded.

Fused into the same pass:

* **int8-KV dequantization** — an int8 pool stores levels plus one
  float32 scale per page row; the tile is dequantized in-register
  (``levels.astype(out) * scale.astype(out)``, the exact op order of the
  XLA reference, so fp pools stay bit-exact and int8 pools match the
  reference bit-for-bit) instead of materializing a dequantized pool;
* **null-page suppression** — page 0 is the reserved null page
  (inactive slots, unallocated tail blocks); its rows hold garbage from
  inactive-slot scatters.  Tiles whose table entry is 0 are forced to
  exact zeros, so the gathered view carries no garbage.  This is inert
  w.r.t. attention output: every null-page key position is outside the
  causal mask by construction (positions only advance into allocated
  pages), and masked lanes underflow to exactly zero probability;
* **per-lane causal / sliding-window masks** — the ``[S, C, T]`` lane
  mask (query lane ``c`` at position ``pos[s] + c`` sees key position
  ``kpos`` iff ``kpos <= pos+c`` and, for ``window > 0``,
  ``pos+c - kpos < window``; ``window <= 0`` is full causal) is emitted
  from the same grid pass via 2-D iota, replacing the separate XLA mask
  computation bit-for-bit.

Both feed shapes of the engine ride through unchanged: C == 1 is plain
decode, C > 1 is chunked prefill (invalid lanes need no masking here —
their scores are garbage the head never reads, exactly as on the XLA
path).  ``interpret=None`` asks the backend (compiled Mosaic on TPU,
interpreter mode on CPU CI), the same convention as every other kernel
wrapper in this package.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import grid_spec, resolve_interpret, table_page_spec


def _gather_body(refs, *, chunk, page_size, out_dtype, quantized):
    """Split the flat pallas ref list and run one (slot, block) step."""
    if quantized:
        bt_ref, pos_ref, win_ref, pk_ref, pv_ref, ks_ref, vs_ref, k_out, v_out, m_out = refs
    else:
        bt_ref, pos_ref, win_ref, pk_ref, pv_ref, k_out, v_out, m_out = refs
        ks_ref = vs_ref = None
    s = pl.program_id(0)
    b = pl.program_id(1)
    live = bt_ref[s, b] != 0

    def tile(pool_ref, scale_ref):
        val = pool_ref[...].astype(out_dtype)
        if scale_ref is not None:
            val = val * scale_ref[...].astype(out_dtype)
        # null-page suppression: the where keeps the tile load itself
        # unconditional (one shape, no control flow), only the value dies
        return jnp.where(live, val, jnp.zeros_like(val))

    k_out[0] = tile(pk_ref, ks_ref)
    v_out[0] = tile(pv_ref, vs_ref)

    # per-lane causal/window mask for this block's page_size key positions
    # (2-D iota per the TPU lowering rules; axes: [C, page_size]), stored
    # as int32 in a block-major [1, 1, C, page_size] tile that sits on the
    # (8, 128) tiling; the wrapper transposes it to lane-major bool
    shape = (chunk, page_size)
    kpos = b * page_size + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    posc = pos_ref[s] + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    win = win_ref[0]
    causal = kpos <= posc
    in_win = (win <= 0) | ((posc - kpos) < win)  # no i1 select on TPU
    m_out[0, 0] = (causal & in_win).astype(jnp.int32)


@functools.lru_cache(maxsize=64)
def _gather_fn(n_slots, n_blocks, page_size, width, chunk, out_dtype, quantized, interpret):
    """Build (and cache) the pallas_call for one static gather geometry."""
    out_dtype = jnp.dtype(out_dtype)
    pool_spec = table_page_spec(page_size, width)
    in_specs = [pool_spec, pool_spec]
    if quantized:
        scale_spec = table_page_spec(page_size, 1)
        in_specs += [scale_spec, scale_spec]
    view_spec = grid_spec((1, 1, page_size, width), lambda s, b: (s, b, 0, 0))
    mask_spec = grid_spec((1, 1, chunk, page_size), lambda s, b: (s, b, 0, 0))
    body = functools.partial(
        _gather_body, chunk=chunk, page_size=page_size,
        out_dtype=out_dtype, quantized=quantized,
    )
    return pl.pallas_call(
        lambda *refs: body(refs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # block table, positions, window
            grid=(n_slots, n_blocks),
            in_specs=in_specs,
            out_specs=[view_spec, view_spec, mask_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_slots, n_blocks, page_size, width), out_dtype),
            jax.ShapeDtypeStruct((n_slots, n_blocks, page_size, width), out_dtype),
            jax.ShapeDtypeStruct((n_slots, n_blocks, chunk, page_size), jnp.int32),
        ],
        interpret=interpret,
    )


def paged_gather_raw(
    block_table: jax.Array,  # [S, n_blocks] int32 physical page ids (0 = null)
    pos: jax.Array,  # [S] int32 first query position per slot
    window: jax.Array,  # [] or [1] int32 (<= 0: full causal; > 0: sliding)
    pool_k: jax.Array,  # [n_pages, page_size, D] fp or int8 levels
    pool_v: jax.Array,
    k_scale: jax.Array | None = None,  # [n_pages, page_size, 1] f32 (int8 pools)
    v_scale: jax.Array | None = None,
    *,
    chunk: int,
    out_dtype,
    interpret: bool | None = None,
):
    """Gather + dequantize + mask in one Pallas pass.

    Returns ``(k_view, v_view, mask)``: the gathered/dequantized
    ``[S, n_blocks, page_size, D]`` K and V tiles (null pages zeroed) and
    the ``[S, chunk, n_blocks, page_size]`` boolean lane mask.
    """
    S, n_blocks = block_table.shape
    _, page_size, width = pool_k.shape
    quantized = pool_k.dtype == jnp.int8
    if quantized and (k_scale is None or v_scale is None):
        raise ValueError("int8 pools require k_scale/v_scale page pools")
    fn = _gather_fn(
        S, n_blocks, page_size, width, chunk, jnp.dtype(out_dtype),
        quantized, resolve_interpret(interpret),
    )
    scalars = (
        block_table.astype(jnp.int32),
        pos.astype(jnp.int32),
        jnp.asarray(window, jnp.int32).reshape(1),
    )
    pools = (pool_k, pool_v, k_scale, v_scale) if quantized else (pool_k, pool_v)
    k_view, v_view, mask = fn(*scalars, *pools)
    return k_view, v_view, jnp.transpose(mask, (0, 2, 1, 3)) != 0
