"""Mixture-of-Experts layer: token-choice top-k with capacity, scatter
dispatch, and expert parallelism via explicit all_to_all (shard_map).

Two execution paths share one parameter layout:

  * ``moe_reference`` — dense per-expert masking; O(E/k) redundant FLOPs
    but trivially correct.  Used as the numeric oracle in tests and for
    tiny smoke configs.
  * ``moe_apply`` — production path: tokens are locally sorted by
    destination expert rank, exchanged with ``jax.lax.all_to_all`` over
    the ``model`` mesh axis (expert parallelism), scattered into
    per-expert capacity buckets, processed as one batched matmul pair,
    and combined back through the inverse route.  Dropped tokens (over
    capacity) fall back to the residual stream, as in Switch/GShard.

Outside a mesh (unit tests), ``moe_apply`` runs the same code with a
1-way expert group, so the collective degenerates to an identity.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.layers import NO_QUANT, QuantConfig, dense, rmsnorm, rmsnorm_init
from repro.parallel.sharding import shard




@dataclasses.dataclass(frozen=True)
class MoESpec:
    d_model: int
    d_ff: int  # per-expert hidden
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    kind: str = "swiglu"


def moe_init(key, s: MoESpec) -> dict:
    kr, k1, k2, k3 = jax.random.split(key, 4)
    E, d, f = s.n_experts, s.d_model, s.d_ff
    scale_in = 1.0 / jnp.sqrt(d)
    scale_out = 1.0 / jnp.sqrt(f)
    p = {
        "router": {"w": jax.random.normal(kr, (d, E)) * scale_in},
        "w_up": jax.random.normal(k1, (E, d, f)) * scale_in,
        "w_down": jax.random.normal(k2, (E, f, d)) * scale_out,
        "ln": rmsnorm_init(d),
    }
    if s.kind in ("swiglu", "geglu"):
        p["w_gate"] = jax.random.normal(k3, (E, d, f)) * scale_in
    return p


def _weight(w, dtype) -> jax.Array:
    """Dequantize a float / int8-dict expert weight tensor."""
    if isinstance(w, dict):
        return w["levels"].astype(dtype) * w["scale"].astype(dtype)
    return w.astype(dtype)


def _n_local_experts(w) -> int:
    """Leading (expert) dim of a float / int8-dict / packed expert weight."""
    from repro.kernels.packed_matmul.ops import PackedDenseParams

    if isinstance(w, PackedDenseParams):
        data = w.w_packed if w.w_packed is not None else w.w_lvl
        return data.shape[0]
    if isinstance(w, dict):
        return w["levels"].shape[0]
    return w.shape[0]


def _expert_matmul(x: jax.Array, w, dtype) -> jax.Array:
    """Batched per-expert matmul [E, C, K] x [E, K, N] -> [E, C, N].

    Float and int8-dict weights use one einsum; prepacked sub-8-bit
    weights (:class:`PackedDenseParams` with a leading expert axis) vmap
    the Pallas Kernel-Packing kernel over experts — the activations take
    the same bounded sigmoid proxy as ``layers.dense``'s packed path.
    """
    import dataclasses as _dc

    from repro.kernels.packed_matmul.ops import PackedDenseParams, packed_dense

    if not isinstance(w, PackedDenseParams):
        return jnp.einsum("ecd,edf->ecf", x, _weight(w, dtype))
    xq = jax.nn.sigmoid(x).astype(jnp.float32)
    packed = w.w_packed is not None

    def one(xe, data):
        pe = _dc.replace(
            w, w_packed=data if packed else None, w_lvl=None if packed else data
        )
        return packed_dense(xe, pe)

    data = w.w_packed if packed else w.w_lvl
    return jax.vmap(one)(xq, data).astype(dtype)


def _expert_ffn(p: dict, s: MoESpec, x: jax.Array) -> jax.Array:
    """x: [E, C, d] -> [E, C, d] batched over local experts."""
    up = _expert_matmul(x, p["w_up"], x.dtype)
    if s.kind in ("swiglu", "geglu"):
        gate = _expert_matmul(x, p["w_gate"], x.dtype)
        act = (jax.nn.silu(gate) if s.kind == "swiglu" else jax.nn.gelu(gate)) * up
    elif s.kind == "squared_relu":
        r = jax.nn.relu(up)
        act = r * r
    else:
        act = jax.nn.gelu(up)
    return _expert_matmul(act, p["w_down"], x.dtype)


def moe_reference(params: dict, s: MoESpec, x: jax.Array) -> jax.Array:
    """Dense oracle: every expert sees every token, outputs are masked."""
    B, S, d = x.shape
    h = rmsnorm(params["ln"], x).reshape(B * S, d)
    logits = h @ params["router"]["w"].astype(h.dtype)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(gates, s.top_k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    weights = jnp.zeros_like(gates).at[jnp.arange(h.shape[0])[:, None], topi].set(topv)
    all_out = _expert_ffn(
        params, s, jnp.broadcast_to(h, (s.n_experts,) + h.shape)
    )  # [E, T, d]
    out = jnp.einsum("te,etd->td", weights.astype(h.dtype), all_out)
    return x + out.reshape(B, S, d)


def _local_moe(params: dict, s: MoESpec, x: jax.Array, *, axis_name: str | None,
               quant: QuantConfig) -> jax.Array:
    """Body shared by the shard_map and meshless paths.

    x: [t_loc, d] local tokens.  When ``axis_name`` is set, experts are
    sharded over that axis (params arrive pre-sliced: [E_loc, ...]) and
    tokens are exchanged with all_to_all.
    """
    t_loc, d = x.shape
    M = jax.lax.axis_size(axis_name) if axis_name else 1
    e_loc = _n_local_experts(params["w_up"])
    E = e_loc * M  # global expert count
    k = s.top_k

    h = rmsnorm(params["ln"], x)
    logits = h @ params["router"]["w"].astype(h.dtype)  # [t_loc, E]
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(gates, k)  # [t_loc, k]
    topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-9)

    # flatten token copies: copy c of token t goes to expert topi[t, c]
    n_copy = t_loc * k
    expert_of_copy = topi.reshape(n_copy)  # [n_copy]
    gate_of_copy = topv.reshape(n_copy)
    token_of_copy = jnp.repeat(jnp.arange(t_loc), k)

    dest_rank = expert_of_copy // e_loc  # owning model-rank
    # send capacity per destination rank
    c_send = int(max(1, round(n_copy / M * s.capacity_factor)))
    order = jnp.argsort(dest_rank)  # stable: groups copies by rank
    rank_sorted = dest_rank[order]
    # position within the destination-rank group
    pos_in_rank = jnp.arange(n_copy) - jnp.searchsorted(rank_sorted, rank_sorted)
    keep = pos_in_rank < c_send
    slot = jnp.clip(rank_sorted * c_send + pos_in_rank, 0, M * c_send - 1)

    send_x = jnp.zeros((M * c_send, d), h.dtype)
    send_meta = jnp.full((M * c_send, 3), -1.0, jnp.float32)  # (expert, gate, src_copy)
    src_copy = order
    send_x = send_x.at[slot].set(jnp.where(keep[:, None], h[token_of_copy[order]], 0.0))
    meta_rows = jnp.stack(
        [
            expert_of_copy[order].astype(jnp.float32),
            gate_of_copy[order],
            src_copy.astype(jnp.float32),
        ],
        axis=-1,
    )
    send_meta = send_meta.at[slot].set(jnp.where(keep[:, None], meta_rows, -1.0))

    if axis_name:
        recv_x = jax.lax.all_to_all(
            send_x.reshape(M, c_send, d), axis_name, split_axis=0, concat_axis=0, tiled=False
        ).reshape(M * c_send, d)
        recv_meta = jax.lax.all_to_all(
            send_meta.reshape(M, c_send, 3), axis_name, split_axis=0, concat_axis=0, tiled=False
        ).reshape(M * c_send, 3)
        my_rank = jax.lax.axis_index(axis_name)
    else:
        recv_x, recv_meta, my_rank = send_x, send_meta, 0

    # group received copies into per-local-expert capacity buckets
    n_recv = M * c_send
    local_expert = recv_meta[:, 0].astype(jnp.int32) - my_rank * e_loc
    valid = recv_meta[:, 0] >= 0
    local_expert = jnp.where(valid, local_expert, e_loc)  # invalid -> overflow bucket
    c_exp = int(max(1, round(n_recv / e_loc * s.capacity_factor)))
    order2 = jnp.argsort(local_expert)
    le_sorted = local_expert[order2]
    pos_in_exp = jnp.arange(n_recv) - jnp.searchsorted(le_sorted, le_sorted)
    keep2 = (pos_in_exp < c_exp) & (le_sorted < e_loc)
    slot2 = jnp.clip(le_sorted * c_exp + pos_in_exp, 0, e_loc * c_exp - 1)

    buckets = jnp.zeros((e_loc * c_exp, d), h.dtype)
    buckets = buckets.at[slot2].set(jnp.where(keep2[:, None], recv_x[order2], 0.0))
    y = _expert_ffn(params, s, buckets.reshape(e_loc, c_exp, d)).reshape(e_loc * c_exp, d)

    # route results back to their recv rows (inverse of the bucket scatter)
    back = jnp.zeros((n_recv, d), h.dtype)
    back = back.at[order2].set(jnp.where(keep2[:, None], y[slot2], 0.0))

    if axis_name:
        back = jax.lax.all_to_all(
            back.reshape(M, c_send, d), axis_name, split_axis=0, concat_axis=0, tiled=False
        ).reshape(M * c_send, d)

    # combine: send slot -> copy -> token, weighted by gates
    out = jnp.zeros((t_loc, d), h.dtype)
    copy_ids = jnp.where(keep, token_of_copy[order], t_loc)  # dropped -> scratch row
    gate_w = jnp.where(keep, gate_of_copy[order], 0.0).astype(h.dtype)
    contrib = back[slot] * gate_w[:, None]
    out = jnp.zeros((t_loc + 1, d), h.dtype).at[copy_ids].add(contrib)[:t_loc]
    return out


def _local_moe_expert_sharded(params: dict, s: MoESpec, x: jax.Array, *,
                              axis_name: str | None) -> jax.Array:
    """Decode-path MoE: tokens replicated over the expert axis, each rank
    computes only its local experts' contributions, combined with a psum.

    Used when the token count cannot shard over the model axis (one-token
    decode steps).  No all_to_all: tokens are already resident everywhere;
    the wire cost is one psum of [t_loc, d] — cheap at decode sizes.
    """
    t_loc, d = x.shape
    M = jax.lax.axis_size(axis_name) if axis_name else 1
    e_loc = _n_local_experts(params["w_up"])
    E = e_loc * M
    k = s.top_k
    my_base = (jax.lax.axis_index(axis_name) * e_loc) if axis_name else 0

    h = rmsnorm(params["ln"], x)
    logits = h @ params["router"]["w"].astype(h.dtype)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(gates, k)
    topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-9)

    n_copy = t_loc * k
    expert_of_copy = topi.reshape(n_copy)
    gate_of_copy = topv.reshape(n_copy)
    token_of_copy = jnp.repeat(jnp.arange(t_loc), k)

    local_e = expert_of_copy - my_base
    mine = (local_e >= 0) & (local_e < e_loc)
    le = jnp.where(mine, local_e, e_loc)
    cap = int(max(1, round(n_copy / E * s.capacity_factor * M)))  # per local expert
    order = jnp.argsort(le)
    le_s = le[order]
    pos = jnp.arange(n_copy) - jnp.searchsorted(le_s, le_s)
    keep = (pos < cap) & (le_s < e_loc)
    slot = jnp.clip(le_s * cap + pos, 0, e_loc * cap - 1)

    buckets = jnp.zeros((e_loc * cap, d), h.dtype)
    buckets = buckets.at[slot].set(jnp.where(keep[:, None], h[token_of_copy[order]], 0.0))
    y = _expert_ffn(params, s, buckets.reshape(e_loc, cap, d)).reshape(e_loc * cap, d)

    gate_w = jnp.where(keep, gate_of_copy[order], 0.0).astype(h.dtype)
    contrib = y[slot] * gate_w[:, None]
    copy_ids = jnp.where(keep, token_of_copy[order], t_loc)
    out = jnp.zeros((t_loc + 1, d), h.dtype).at[copy_ids].add(contrib)[:t_loc]
    if axis_name:
        out = jax.lax.psum(out, axis_name)
    return out


def moe_apply(
    params: dict,
    s: MoESpec,
    x: jax.Array,  # [B, S, d]
    *,
    axis_name: str | None = None,
    quant: QuantConfig = NO_QUANT,
) -> jax.Array:
    """Production MoE block; call inside shard_map when ``axis_name`` set."""
    B, S, d = x.shape
    out = _local_moe(params, s, x.reshape(B * S, d), axis_name=axis_name, quant=quant)
    return x + out.reshape(B, S, d)
