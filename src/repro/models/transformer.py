"""Unified LM-family model: dense / MoE / SSM / hybrid / enc-dec backbones.

One config + one forward covers the ten assigned architectures:

  * dense GQA transformers (yi, llama3.2, nemotron, qwen2-vl backbone)
  * sliding-window patterns (gemma3: 5 local : 1 global)
  * MoE FFNs (llama4-scout 16e top-1, qwen3-moe 128e top-8) with expert
    parallelism via shard_map all_to_all
  * Mamba2/SSD stacks (mamba2-130m) and hybrid stacks with a shared
    attention block every k SSM layers (zamba2)
  * encoder-decoder with cross attention (whisper backbone; modality
    frontend stubbed as precomputed frame embeddings)

Layers are stacked on a leading axis and scanned (jax.lax.scan) so HLO
size and compile time stay O(1) in depth; jax.checkpoint on the scanned
body implements activation rematerialization.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as X
from repro.parallel.sharding import current_rules, shard
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0  # 0 => d_model // n_heads
    mlp_kind: str = "swiglu"
    rope_theta: float = 10_000.0
    use_mrope: bool = False
    # layer pattern: "attn" | "ssm"; window[i] > 0 => sliding-window attention
    family: str = "attn"  # attn | ssm | hybrid | encdec
    window_pattern: tuple[int, ...] = (0,)
    # MoE
    n_experts: int = 0
    top_k: int = 0
    expert_d_ff: int = 0
    capacity_factor: float = 1.25
    # SSM
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_chunk: int = 256
    hybrid_attn_every: int = 6
    # enc-dec
    enc_layers: int = 0
    # execution
    q_chunk: int = 1024
    remat: bool = True
    # two-level remat: outer scan over groups of this many layers keeps only
    # group-boundary activations live (memory ~ L/remat_block checkpoints);
    # 0/1 disables.  Only used when it divides n_layers.
    remat_block: int = 1
    # ZeRO-3 regather: re-gather each layer's fsdp-sharded weights inside
    # the layer scan (bounds gathered-weight HBM to one layer at a time)
    zero3_regather: bool = False
    dtype: Any = jnp.bfloat16
    quant: L.QuantConfig = L.NO_QUANT
    # sharding choice for decode KV cache: "kv_heads" or "seq_mp"
    cache_shard: str = "kv_heads"
    # decode KV cache storage: "bf16" | "int8" (per-token scales)
    kv_dtype: str = "bf16"
    # tensor-parallel degree the *specs* are local to: a mesh shard runs
    # the decode path with replace(cfg, tp_shards=mp), so attn heads /
    # kv groups / d_ff / SSM heads divide by mp while d_model, vocab and
    # the quant assignment stay global.  1 (default) = whole model.
    tp_shards: int = 1

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    def attn_spec(self) -> L.AttnSpec:
        return L.AttnSpec(
            d_model=self.d_model,
            n_heads=self.n_heads // self.tp_shards,
            kv_heads=self.kv_heads // self.tp_shards,
            head_dim=self.hd,
            rope_theta=self.rope_theta,
            use_mrope=self.use_mrope,
            q_chunk=self.q_chunk,
        )

    def mlp_spec(self) -> L.MLPSpec:
        return L.MLPSpec(
            d_model=self.d_model, d_ff=self.d_ff // self.tp_shards, kind=self.mlp_kind
        )

    def moe_spec(self) -> X.MoESpec:
        return X.MoESpec(
            d_model=self.d_model,
            d_ff=self.expert_d_ff,
            n_experts=self.n_experts,
            top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            kind=self.mlp_kind,
        )

    def ssm_spec(self) -> M.MambaSpec:
        shard_heads = None
        if self.tp_shards > 1:
            n_heads = (2 * self.d_model) // self.ssm_head_dim  # expand=2
            shard_heads = n_heads // self.tp_shards
        return M.MambaSpec(
            d_model=self.d_model,
            d_state=self.ssm_state,
            head_dim=self.ssm_head_dim,
            chunk=self.ssm_chunk,
            shard_heads=shard_heads,
        )

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    def windows(self) -> jnp.ndarray:
        pat = self.window_pattern
        reps = -(-self.n_layers // len(pat))
        return jnp.asarray((pat * reps)[: self.n_layers], jnp.int32)

    def param_count(self) -> int:
        """Approximate parameter count (reported in EXPERIMENTS)."""
        d, hd = self.d_model, self.hd
        per = 0
        if self.family in ("attn", "encdec"):
            attn = d * hd * (self.n_heads + 2 * self.kv_heads) + self.n_heads * hd * d
            ffn = (
                self.n_experts * 3 * d * self.expert_d_ff
                if self.is_moe
                else (3 if self.mlp_kind == "swiglu" else 2) * d * self.d_ff
            )
            per = attn + ffn
            total = self.n_layers * per
            if self.family == "encdec":
                total += self.enc_layers * per + self.n_layers * (attn)  # cross attn
        elif self.family == "ssm":
            spec = self.ssm_spec()
            per = d * (2 * spec.d_inner + 2 * spec.d_state + spec.n_heads) + spec.d_inner * d
            total = self.n_layers * per
        else:  # hybrid
            spec = self.ssm_spec()
            per = d * (2 * spec.d_inner + 2 * spec.d_state + spec.n_heads) + spec.d_inner * d
            attn = d * hd * (self.n_heads + 2 * self.kv_heads) + self.n_heads * hd * d
            ffn = 3 * d * self.d_ff
            total = self.n_layers * per + attn + ffn  # one shared block
        return total + self.vocab * d


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _stack_init(key, n: int, init_fn):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def init_params(key: jax.Array, cfg: ModelConfig) -> dict:
    k_emb, k_layers, k_extra, k_enc = jax.random.split(key, 4)
    params: dict = {
        "embed": jax.random.normal(k_emb, (cfg.vocab, cfg.d_model)) * 0.01,
        "final_ln": L.rmsnorm_init(cfg.d_model),
    }
    aspec, mspec = cfg.attn_spec(), cfg.mlp_spec()

    if cfg.family in ("attn", "encdec"):

        def one(k):
            ka, km = jax.random.split(k)
            block = {"attn": L.attn_init(ka, aspec)}
            if cfg.is_moe:
                block["moe"] = X.moe_init(km, cfg.moe_spec())
            else:
                block["mlp"] = L.mlp_init(km, mspec)
            return block

        params["layers"] = _stack_init(k_layers, cfg.n_layers, one)
        if cfg.family == "encdec":

            def enc_one(k):
                ka, km = jax.random.split(k)
                return {"attn": L.attn_init(ka, aspec), "mlp": L.mlp_init(km, mspec)}

            def xattn_one(k):
                return {"xattn": L.attn_init(k, aspec)}

            params["enc_layers"] = _stack_init(k_enc, cfg.enc_layers, enc_one)
            params["xattn_layers"] = _stack_init(k_extra, cfg.n_layers, xattn_one)
    elif cfg.family == "ssm":
        params["layers"] = _stack_init(k_layers, cfg.n_layers, lambda k: M.mamba_init(k, cfg.ssm_spec()))
    elif cfg.family == "hybrid":
        params["layers"] = _stack_init(k_layers, cfg.n_layers, lambda k: M.mamba_init(k, cfg.ssm_spec()))
        ka, km = jax.random.split(k_extra)
        params["shared_attn"] = {"attn": L.attn_init(ka, aspec), "mlp": L.mlp_init(km, mspec)}
    else:
        raise ValueError(cfg.family)
    return params


# ---------------------------------------------------------------------------
# MoE under shard_map (expert parallelism) or direct (tests)
# ---------------------------------------------------------------------------


def _moe_block(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    rules = current_rules()
    spec = cfg.moe_spec()
    if rules is None or rules.mesh is None:
        return X.moe_apply(params, spec, x, axis_name=None, quant=cfg.quant)
    mesh = rules.mesh
    batch_ax, model_ax = rules.batch, rules.experts
    model_size = mesh.shape[model_ax] if isinstance(model_ax, str) else 1
    seq_shardable = x.shape[1] % max(1, model_size) == 0

    p_specs = {
        "router": P(),
        "ln": P(),
        **{
            k: P(model_ax, None, None)
            for k in ("w_up", "w_down", *(["w_gate"] if "w_gate" in params else []))
        },
    }

    if seq_shardable:
        # train/prefill: tokens shard over the model axis; all_to_all EP
        def body(p, xs):
            b, s_loc, d = xs.shape
            out = X._local_moe(
                p, spec, xs.reshape(b * s_loc, d), axis_name=model_ax, quant=cfg.quant
            )
            return xs + out.reshape(b, s_loc, d)

        x_spec = P(batch_ax, model_ax, None)
    else:
        # decode: tokens replicated over the model axis; psum-combined EP
        def body(p, xs):
            b, s_loc, d = xs.shape
            out = X._local_moe_expert_sharded(
                p, spec, xs.reshape(b * s_loc, d), axis_name=model_ax
            )
            return xs + out.reshape(b, s_loc, d)

        x_spec = P(batch_ax, None, None)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(p_specs, x_spec), out_specs=x_spec,
        check_vma=False,
    )(params, x)


# ---------------------------------------------------------------------------
# train forward (next-token loss)
# ---------------------------------------------------------------------------


def _maybe_ckpt(f, cfg):
    return jax.checkpoint(f) if cfg.remat else f


def _attn_mlp_block(p, cfg: ModelConfig, x, positions, window):
    if cfg.zero3_regather:
        from repro.parallel.sharding import current_rules, regather_layer_params

        p = regather_layer_params(p, current_rules())
    x = L.attention_train(
        p["attn"], cfg.attn_spec(), x, positions, window=window, quant=cfg.quant
    )
    if cfg.is_moe:
        x = _moe_block(p["moe"], cfg, x)
    else:
        x = L.mlp(p["mlp"], cfg.mlp_spec(), x, quant=cfg.quant)
    return x


def _scan_stack(body, cfg: ModelConfig, x, xs):
    """Scan over stacked layers; two-level (grouped) when remat_block set.

    The grouped form checkpoints only group boundaries: backward memory is
    O(L / remat_block) saved activations + O(remat_block) transient.
    """
    rb = cfg.remat_block
    n = jax.tree.leaves(xs)[0].shape[0]
    if cfg.remat and rb > 1 and n % rb == 0:
        grouped = jax.tree.map(lambda a: a.reshape((n // rb, rb) + a.shape[1:]), xs)

        def group_body(carry, group_xs):
            out, _ = jax.lax.scan(body, carry, group_xs)
            return out, None

        x, _ = jax.lax.scan(jax.checkpoint(group_body), x, grouped)
        return x
    x, _ = jax.lax.scan(_maybe_ckpt(body, cfg), x, xs)
    return x


def _run_attn_stack(params_stack, cfg: ModelConfig, x, positions, windows):
    def body(carry, xs):
        p, win = xs
        return _attn_mlp_block(p, cfg, carry, positions, win), None

    return _scan_stack(body, cfg, x, (params_stack, windows))


def _run_ssm_stack(params_stack, cfg: ModelConfig, x):
    def body(carry, p):
        if cfg.zero3_regather:
            from repro.parallel.sharding import current_rules, regather_layer_params

            p = regather_layer_params(p, current_rules())
        return M.mamba_train(p, cfg.ssm_spec(), carry, quant=cfg.quant), None

    return _scan_stack(body, cfg, x, params_stack)


def _hybrid_segments(cfg: ModelConfig) -> list[int]:
    """Segment sizes between shared-attention applications (zamba2)."""
    k, n = cfg.hybrid_attn_every, cfg.n_layers
    segs = [k] * (n // k)
    if n % k:
        segs.append(n % k)
    return segs


def forward_train(params: dict, cfg: ModelConfig, batch: dict) -> jax.Array:
    """batch: tokens [B,S] int32, labels [B,S] int32 (+positions for mrope,
    +enc_embeds for encdec).  Returns mean next-token cross-entropy."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = params["embed"].astype(cfg.dtype)[tokens]
    x = shard(x, "batch", None, None)
    if cfg.use_mrope:
        positions = batch["positions"]  # [B, S, 3]
    else:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    if cfg.family == "attn":
        x = _run_attn_stack(params["layers"], cfg, x, positions, cfg.windows())
    elif cfg.family == "ssm":
        x = _run_ssm_stack(params["layers"], cfg, x)
    elif cfg.family == "hybrid":
        idx = 0
        for seg in _hybrid_segments(cfg):
            sub = jax.tree.map(lambda a: a[idx : idx + seg], params["layers"])
            x = _run_ssm_stack(sub, cfg, x)
            idx += seg
            x = _attn_mlp_block(params["shared_attn"], cfg, x, positions, 0)
    elif cfg.family == "encdec":
        enc = batch["enc_embeds"].astype(cfg.dtype)  # [B, Se, d] stub frontend
        Se = enc.shape[1]
        enc_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32)[None], (B, Se))
        def enc_body(carry, p):
            h = L.attention_train(p["attn"], cfg.attn_spec(), carry, enc_pos, window=-1)
            return L.mlp(p["mlp"], cfg.mlp_spec(), h, quant=cfg.quant), None
        enc, _ = jax.lax.scan(_maybe_ckpt(enc_body, cfg), enc, params["enc_layers"])
        aspec = cfg.attn_spec()
        G, hd = cfg.kv_heads, cfg.hd

        def dec_body(carry, xs):
            p, px = xs
            h = L.attention_train(p["attn"], aspec, carry, positions, window=0, quant=cfg.quant)
            ek = L.dense(px["xattn"]["wk"], enc, name="xattn_k", quant=cfg.quant).reshape(B, Se, G, hd)
            ev = L.dense(px["xattn"]["wv"], enc, name="xattn_v", quant=cfg.quant).reshape(B, Se, G, hd)
            h = L.cross_attention(px["xattn"], aspec, h, (ek, ev), quant=cfg.quant)
            return L.mlp(p["mlp"], cfg.mlp_spec(), h, quant=cfg.quant), None

        x, _ = jax.lax.scan(
            _maybe_ckpt(dec_body, cfg), x, (params["layers"], params["xattn_layers"])
        )
    else:
        raise ValueError(cfg.family)

    x = L.rmsnorm(params["final_ln"], x)
    return ce_loss_chunked(x, params["embed"], batch["labels"])


def ce_loss_chunked(x: jax.Array, embed: jax.Array, labels: jax.Array, chunk: int = 512) -> jax.Array:
    """Tied-head cross-entropy, chunked over sequence to bound the [*,V]
    logit buffer (vocab can be 256k)."""
    B, S, d = x.shape
    V = embed.shape[0]
    n = max(1, S // min(chunk, S))
    cs = S // n
    emb_t = embed.astype(x.dtype)

    def body(acc, i):
        xs = jax.lax.dynamic_slice_in_dim(x, i * cs, cs, axis=1)
        ls = jax.lax.dynamic_slice_in_dim(labels, i * cs, cs, axis=1)
        logits = (xs @ emb_t.T).astype(jnp.float32)  # [B, cs, V]
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ls[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), jnp.arange(n))
    return total / (B * S)


# ---------------------------------------------------------------------------
# decode forward (one new token against a KV / SSM cache)
# ---------------------------------------------------------------------------


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, *, dtype=jnp.bfloat16, enc_len: int | None = None
) -> dict:
    """Allocate the serve-time cache pytree (KV or SSM state)."""
    if cfg.family in ("attn", "encdec"):
        # flat KV layout [L, B, T, G*hd]: the fused dim is divisible by the
        # TP degree even when kv_heads alone is not
        shape = (cfg.n_layers, batch, max_len, cfg.kv_heads * cfg.hd)
        if cfg.kv_dtype == "int8" and cfg.family == "attn":
            cache = {
                "k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros((cfg.n_layers, batch, max_len, 1), jnp.float32),
                "v_scale": jnp.zeros((cfg.n_layers, batch, max_len, 1), jnp.float32),
            }
            return cache
        cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        if cfg.family == "encdec":
            se = enc_len or max(1, max_len // 2)
            cache["enc_k"] = jnp.zeros((cfg.n_layers, batch, se, cfg.kv_heads * cfg.hd), dtype)
            cache["enc_v"] = jnp.zeros_like(cache["enc_k"])
        return cache
    sspec = cfg.ssm_spec()
    ssm = {
        "ssm": jnp.zeros((cfg.n_layers, batch, sspec.n_heads, sspec.d_state, sspec.head_dim), jnp.float32),
        "conv": jnp.zeros((cfg.n_layers, batch, sspec.conv_width - 1, sspec.d_inner + 2 * sspec.d_state), dtype),
    }
    if cfg.family == "hybrid":
        ssm["k"] = jnp.zeros((1, batch, max_len, cfg.kv_heads * cfg.hd), dtype)
        ssm["v"] = jnp.zeros_like(ssm["k"])
    return ssm


def forward_decode(
    params: dict, cfg: ModelConfig, cache: dict, tokens: jax.Array, pos: jax.Array,
    head: Any = None,
) -> tuple[jax.Array, dict]:
    """One decode step: tokens [B, 1] -> logits [B, V], updated cache.

    ``head`` optionally carries prepacked sub-8-bit LM-head weights
    (:func:`repro.models.layers.prepack_lm_head`); default is the tied
    full-precision embedding matmul.

    ``params["layers"]`` may be a list of per-layer pytrees instead of
    the stacked scan layout (deployment plans with per-layer bit pairs;
    attn/ssm families only) — the stack is unrolled with identical math.
    """
    B = tokens.shape[0]
    x = params["embed"].astype(cfg.dtype)[tokens]  # [B, 1, d]
    x = shard(x, "batch", None, None)
    aspec = cfg.attn_spec()
    windows = cfg.windows()
    per_layer = isinstance(params["layers"], (list, tuple))
    if per_layer and cfg.family not in ("attn", "ssm"):
        raise NotImplementedError(
            f"per-layer (list) params support attn/ssm families, not {cfg.family!r}"
        )

    if cfg.family in ("attn", "encdec"):
        kv_int8 = cfg.kv_dtype == "int8" and cfg.family == "attn"

        def body(carry, xs):
            if kv_int8:
                p, ck, cv, cks, cvs, win = xs
                h, nk, nv, nks, nvs = L.attention_decode(
                    p["attn"], aspec, carry, ck, cv, pos,
                    window=win, cache_shard=cfg.cache_shard, quant=cfg.quant,
                    cache_k_scale=cks, cache_v_scale=cvs,
                )
                if cfg.is_moe:
                    h = _moe_block(p["moe"], cfg, h)
                else:
                    h = L.mlp(p["mlp"], cfg.mlp_spec(), h, quant=cfg.quant)
                return h, (nk, nv, nks, nvs)
            p, ck, cv, win, *rest = xs
            h, nk, nv = L.attention_decode(
                p["attn"], aspec, carry, ck, cv, pos,
                window=win, cache_shard=cfg.cache_shard, quant=cfg.quant,
            )
            if cfg.family == "encdec":
                px, ek, ev = rest
                se = ek.shape[1]
                ekv = (
                    ek.reshape(ek.shape[0], se, cfg.kv_heads, cfg.hd),
                    ev.reshape(ev.shape[0], se, cfg.kv_heads, cfg.hd),
                )
                h = L.cross_attention(px["xattn"], aspec, h, ekv, quant=cfg.quant)
            if cfg.is_moe:
                h = _moe_block(p["moe"], cfg, h)
            else:
                h = L.mlp(p["mlp"], cfg.mlp_spec(), h, quant=cfg.quant)
            return h, (nk, nv)

        if per_layer:
            # heterogeneous (deployment-plan) layers: iterate the same body
            # the scan uses, feeding each layer's cache slice by hand
            outs = []
            for i, p in enumerate(params["layers"]):
                if kv_int8:
                    xs_i = (p, cache["k"][i], cache["v"][i],
                            cache["k_scale"][i], cache["v_scale"][i], windows[i])
                else:
                    xs_i = (p, cache["k"][i], cache["v"][i], windows[i])
                x, out = body(x, xs_i)
                outs.append(out)
            stacked = [jnp.stack(parts) for parts in zip(*outs)]
            if kv_int8:
                new_cache = dict(cache, k=stacked[0], v=stacked[1],
                                 k_scale=stacked[2], v_scale=stacked[3])
            else:
                new_cache = dict(cache, k=stacked[0], v=stacked[1])
        elif kv_int8:
            xs = (params["layers"], cache["k"], cache["v"],
                  cache["k_scale"], cache["v_scale"], windows)
            x, (nk, nv, nks, nvs) = jax.lax.scan(body, x, xs)
            new_cache = dict(cache, k=nk, v=nv, k_scale=nks, v_scale=nvs)
        else:
            xs = [params["layers"], cache["k"], cache["v"], windows]
            if cfg.family == "encdec":
                xs += [params["xattn_layers"], cache["enc_k"], cache["enc_v"]]
            x, (nk, nv) = jax.lax.scan(body, x, tuple(xs))
            new_cache = dict(cache, k=nk, v=nv)
    elif cfg.family == "ssm":

        def body(carry, xs):
            p, st, cv = xs
            h, ns, nc = M.mamba_decode(p, cfg.ssm_spec(), carry, st, cv, quant=cfg.quant)
            return h, (ns, nc)

        if per_layer:
            outs = []
            for i, p in enumerate(params["layers"]):
                x, out = body(x, (p, cache["ssm"][i], cache["conv"][i]))
                outs.append(out)
            ns, nc = (jnp.stack(parts) for parts in zip(*outs))
            new_cache = dict(cache, ssm=ns, conv=nc)
        else:
            x, (ns, nc) = jax.lax.scan(
                body, x, (params["layers"], cache["ssm"], cache["conv"])
            )
            new_cache = dict(cache, ssm=ns, conv=nc)
    else:  # hybrid
        new_ssm, new_conv = [], []
        idx = 0
        ck, cv = cache["k"][0], cache["v"][0]
        for seg in _hybrid_segments(cfg):
            sub = jax.tree.map(lambda a: a[idx : idx + seg], params["layers"])

            def body(carry, xs):
                p, st, c2 = xs
                h, ns, nc = M.mamba_decode(p, cfg.ssm_spec(), carry, st, c2, quant=cfg.quant)
                return h, (ns, nc)

            x, (ns, nc) = jax.lax.scan(
                body, x, (sub, cache["ssm"][idx : idx + seg], cache["conv"][idx : idx + seg])
            )
            new_ssm.append(ns)
            new_conv.append(nc)
            idx += seg
            x, ck, cv = L.attention_decode(
                params["shared_attn"]["attn"], aspec, x, ck, cv, pos,
                cache_shard=cfg.cache_shard, quant=cfg.quant,
            )
            x = L.mlp(params["shared_attn"]["mlp"], cfg.mlp_spec(), x, quant=cfg.quant)
        new_cache = dict(
            cache,
            ssm=jnp.concatenate(new_ssm, 0),
            conv=jnp.concatenate(new_conv, 0),
            k=ck[None],
            v=cv[None],
        )

    x = L.rmsnorm(params["final_ln"], x)
    logits = L.lm_head(x[:, 0, :], params["embed"], cfg.dtype, packed=head)
    return logits, new_cache


# ---------------------------------------------------------------------------
# paged decode (continuous-batching serving: repro.serving)
# ---------------------------------------------------------------------------


def init_paged_state(
    cfg: ModelConfig, n_slots: int, n_pages: int, page_size: int, *, dtype=jnp.bfloat16,
    kv_dtype=None,
) -> dict:
    """Allocate the paged serving state.

    For attention families the KV cache is a physical page *pool*
    ``[L, n_pages, page_size, G*hd]`` indexed through per-slot block
    tables (page 0 is reserved as the null page for inactive slots); the
    pool is sized by the page budget, not ``n_slots * max_len``.  SSM
    state is O(1) per sequence, so it stays slot-indexed ("pages" of one
    sequence each) and is zeroed on slot recycling.

    ``kv_dtype`` overrides ``cfg.kv_dtype`` ("int8", ``jnp.int8``, or a
    float dtype).  An int8 pool stores K/V rows as int8 levels plus one
    float32 scale per page row (``k_scale``/``v_scale`` pools), halving
    paged-KV memory; rows are dequantized on gather inside
    :func:`repro.models.layers.attention_decode_paged`.
    """
    kv = cfg.kv_dtype if kv_dtype is None else kv_dtype
    kv_int8 = kv == "int8" or kv == jnp.int8
    if not kv_int8 and kv_dtype is not None and not isinstance(kv, str):
        dtype = kv  # explicit float override (e.g. jnp.float32 pools)
    if cfg.family == "attn":
        # under TP (cfg.tp_shards > 1) this is the *local* pool: each mesh
        # rank owns the pages of its contiguous kv-head group
        g_loc = cfg.kv_heads // cfg.tp_shards
        shape = (cfg.n_layers, n_pages, page_size, g_loc * cfg.hd)
        if kv_int8:
            return {
                "k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:-1] + (1,), jnp.float32),
                "v_scale": jnp.zeros(shape[:-1] + (1,), jnp.float32),
            }
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if cfg.family == "ssm":
        sspec = cfg.ssm_spec()
        return {
            "ssm": jnp.zeros(
                (cfg.n_layers, n_slots, sspec.n_heads, sspec.d_state, sspec.head_dim),
                jnp.float32,
            ),
            "conv": jnp.zeros(
                (cfg.n_layers, n_slots, sspec.conv_width - 1, sspec.d_inner + 2 * sspec.d_state),
                dtype,
            ),
        }
    raise NotImplementedError(
        f"continuous-batching serving supports attn/ssm families, not {cfg.family!r}"
    )


def reset_paged_slot(cfg: ModelConfig, state: dict, slot: jax.Array) -> dict:
    """Zero one slot's recurrent state when the scheduler recycles it.

    Attention state needs no reset — a fresh sequence starts at pos 0, so
    every stale page row is masked until overwritten — but SSM/conv state
    is additive across steps and must be cleared.
    """
    if cfg.family != "ssm":
        return state
    return dict(
        state,
        ssm=state["ssm"].at[:, slot].set(0.0),
        conv=state["conv"].at[:, slot].set(0.0),
    )


def embed_paged(params: dict, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    """Token embedding + batch sharding for the paged decode step — the
    entry segment of :func:`forward_decode_paged`, exposed so the in-situ
    attributor (:mod:`repro.obs.attrib`) re-executes the exact op."""
    x = params["embed"].astype(cfg.dtype)[tokens]  # [S, C, d]
    return shard(x, "batch", None, None)


def decode_paged_layer(
    p,
    cfg: ModelConfig,
    layer_state: dict,
    block_table: jax.Array,
    h: jax.Array,  # [S, C, d] hidden states entering this layer
    pos: jax.Array,
    *,
    window: jax.Array | int = -1,
    lens: jax.Array | None = None,
    gather: str = "xla",
    axis_name: str | None = None,
) -> tuple[jax.Array, dict]:
    """One layer of the paged decode/prefill step.

    ``layer_state`` holds this layer's slice of the paged state
    (``k``/``v`` [+ ``k_scale``/``v_scale`` for int8 pools] for attention
    families; ``ssm``/``conv`` for SSM).  Returns the layer's output
    hidden states and its updated state slice.

    This is the single per-layer body: :func:`forward_decode_paged` scans
    (or unrolls) it over the stack, and the in-situ attributor
    (:mod:`repro.obs.attrib`) times it segment by segment — identical
    math by construction, so segmented re-execution attributes the real
    fused step, not a lookalike.

    With ``axis_name`` set (a tensor-parallel shard inside a shard_map),
    ``cfg`` carries ``tp_shards = mp``, ``p`` and ``layer_state`` hold
    this rank's slices, and each block psums once before its residual;
    MoE routes through the expert-sharded psum path directly (the
    rules-driven :func:`_moe_block` cannot nest another shard_map here).
    """
    if cfg.family == "attn":
        aspec = cfg.attn_spec()
        kv_int8 = layer_state["k"].dtype == jnp.int8
        if kv_int8:
            h, nk, nv, nks, nvs = L.attention_decode_paged(
                p["attn"], aspec, h, layer_state["k"], layer_state["v"],
                block_table, pos, window=window, quant=cfg.quant,
                pool_k_scale=layer_state["k_scale"],
                pool_v_scale=layer_state["v_scale"], lens=lens, gather=gather,
                axis_name=axis_name,
            )
        else:
            h, nk, nv = L.attention_decode_paged(
                p["attn"], aspec, h, layer_state["k"], layer_state["v"],
                block_table, pos, window=window, quant=cfg.quant, lens=lens,
                gather=gather, axis_name=axis_name,
            )
            nks = nvs = None
        if cfg.is_moe:
            if axis_name is not None:
                s_, c_, d_ = h.shape
                out = X._local_moe_expert_sharded(
                    p["moe"], cfg.moe_spec(), h.reshape(s_ * c_, d_), axis_name=axis_name
                )
                h = h + out.reshape(s_, c_, d_)
            else:
                h = _moe_block(p["moe"], cfg, h)
        else:
            h = L.mlp(p["mlp"], cfg.mlp_spec(), h, quant=cfg.quant, axis_name=axis_name)
        new_state = {"k": nk, "v": nv}
        if kv_int8:
            new_state.update(k_scale=nks, v_scale=nvs)
        return h, new_state
    if cfg.family == "ssm":
        sspec = cfg.ssm_spec()
        if h.shape[1] > 1 or lens is not None:
            # recurrent over the lane axis; invalid lanes leave state alone
            h, ns, nc = M.mamba_decode_chunk(
                p, sspec, h, layer_state["ssm"], layer_state["conv"],
                lens=lens, quant=cfg.quant, axis_name=axis_name,
            )
        else:
            h, ns, nc = M.mamba_decode(
                p, sspec, h, layer_state["ssm"], layer_state["conv"],
                quant=cfg.quant, axis_name=axis_name,
            )
        return h, {"ssm": ns, "conv": nc}
    raise NotImplementedError(
        f"continuous-batching serving supports attn/ssm families, not {cfg.family!r}"
    )


def head_paged(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,  # [S, C, d] final hidden states
    lens: jax.Array | None = None,
    head: Any = None,
    axis_name: str | None = None,
) -> jax.Array:
    """Final norm + last-valid-lane gather + LM head — the exit segment
    of :func:`forward_decode_paged`, shared with the in-situ attributor.

    Under tensor parallelism the head is vocab-sharded: the shard tree
    carries the full ``embed`` for the (replicated) token lookup plus a
    ``head_embed`` vocab-row slice (or a per-shard prepacked ``head``),
    and the local logits are all-gathered — an exact concatenation.
    """
    x = L.rmsnorm(params["final_ln"], x)
    if lens is not None:
        # only each slot's last valid lane is ever sampled; gather it before
        # the (wide) LM-head matmul so the logits buffer stays [S, V]
        last = jnp.maximum(lens - 1, 0)[:, None, None]
        x_last = jnp.take_along_axis(x, last, axis=1)[:, 0]
    else:
        # lens=None: every lane valid, so the newest token is the last lane
        # (identical to lane 0 on the legacy C == 1 call sites)
        x_last = x[:, -1, :]
    emb = params.get("head_embed", params["embed"])
    return L.lm_head(x_last, emb, cfg.dtype, packed=head, axis_name=axis_name)


def forward_decode_paged(
    params: dict,
    cfg: ModelConfig,
    state: dict,
    block_table: jax.Array,  # [S, n_blocks] int32 (attn families; ignored for ssm)
    tokens: jax.Array,  # [S, C] int32, a chunk of C tokens per serving slot
    pos: jax.Array,  # [S] int32 per-slot position of each chunk's first token
    head: Any = None,
    lens: jax.Array | None = None,  # [S] int32 valid tokens per chunk (None: all)
    gather: str = "xla",  # KV gather backend (see attention_decode_paged)
    axis_name: str | None = None,  # mesh model axis (tensor-parallel shard)
) -> tuple[jax.Array, dict]:
    """One continuous-batching decode/prefill step over the slot set.

    Same math as :func:`forward_decode` (bit-exact for identical
    sequences), but the KV cache is gathered through per-slot block
    tables and every slot carries its own position, so sequences admitted
    at different times coexist in one jitted step.

    Chunked prefill: ``tokens`` may carry ``C > 1`` lanes per slot with
    ``lens[i]`` of them valid — prefilling slots push a whole prompt
    chunk through in one step while decoding slots ride along with
    ``lens == 1`` (their spare lanes are masked).  The returned logits
    are those of each slot's **last valid** lane, which is the only one
    ever sampled.  With ``C == 1`` and ``lens=None`` this is exactly the
    legacy one-token-per-step path.

    ``params["layers"]`` is either the stacked pytree (homogeneous
    layers, scanned — the fast path) or a *list* of per-layer pytrees.
    The list form exists for deployment plans (``repro.plan``) where
    layers carry different ``(w_bits, a_bits)`` packed weights: their
    static metadata differs per layer, so they cannot ride one scan and
    are unrolled instead — same math, layer by layer.
    """
    x = embed_paged(params, cfg, tokens)
    per_layer = isinstance(params["layers"], (list, tuple))
    if cfg.family == "attn":
        windows = cfg.windows()
        kv_int8 = state["k"].dtype == jnp.int8

        def one_layer(h, p, pk, pv, pks, pvs, win):
            st = {"k": pk, "v": pv}
            if kv_int8:
                st.update(k_scale=pks, v_scale=pvs)
            h, nst = decode_paged_layer(
                p, cfg, st, block_table, h, pos, window=win, lens=lens,
                gather=gather, axis_name=axis_name,
            )
            return h, nst["k"], nst["v"], nst.get("k_scale"), nst.get("v_scale")

        if per_layer:
            nk, nv, nks, nvs = [], [], [], []
            for i, p in enumerate(params["layers"]):
                x, k_i, v_i, ks_i, vs_i = one_layer(
                    x, p, state["k"][i], state["v"][i],
                    state["k_scale"][i] if kv_int8 else None,
                    state["v_scale"][i] if kv_int8 else None,
                    windows[i],
                )
                nk.append(k_i)
                nv.append(v_i)
                nks.append(ks_i)
                nvs.append(vs_i)
            new_state = dict(state, k=jnp.stack(nk), v=jnp.stack(nv))
            if kv_int8:
                new_state.update(k_scale=jnp.stack(nks), v_scale=jnp.stack(nvs))
        elif kv_int8:

            def body(carry, xs):
                p, pk, pv, pks, pvs, win = xs
                h, npk, npv, npks, npvs = one_layer(carry, p, pk, pv, pks, pvs, win)
                return h, (npk, npv, npks, npvs)

            x, (nk, nv, nks, nvs) = jax.lax.scan(
                body, x,
                (params["layers"], state["k"], state["v"],
                 state["k_scale"], state["v_scale"], windows),
            )
            new_state = dict(state, k=nk, v=nv, k_scale=nks, v_scale=nvs)
        else:

            def body(carry, xs):
                p, pk, pv, win = xs
                h, npk, npv, _, _ = one_layer(carry, p, pk, pv, None, None, win)
                return h, (npk, npv)

            x, (nk, nv) = jax.lax.scan(
                body, x, (params["layers"], state["k"], state["v"], windows)
            )
            new_state = dict(state, k=nk, v=nv)
    elif cfg.family == "ssm":

        def ssm_step(h, p, st, cv):
            h, nst = decode_paged_layer(
                p, cfg, {"ssm": st, "conv": cv}, block_table, h, pos, lens=lens,
                axis_name=axis_name,
            )
            return h, nst["ssm"], nst["conv"]

        if per_layer:
            ns_l, nc_l = [], []
            for i, p in enumerate(params["layers"]):
                x, ns_i, nc_i = ssm_step(x, p, state["ssm"][i], state["conv"][i])
                ns_l.append(ns_i)
                nc_l.append(nc_i)
            new_state = dict(state, ssm=jnp.stack(ns_l), conv=jnp.stack(nc_l))
        else:

            def body(carry, xs):
                p, st, cv = xs
                h, ns, nc = ssm_step(carry, p, st, cv)
                return h, (ns, nc)

            x, (ns, nc) = jax.lax.scan(body, x, (params["layers"], state["ssm"], state["conv"]))
            new_state = dict(state, ssm=ns, conv=nc)
    else:
        raise NotImplementedError(
            f"continuous-batching serving supports attn/ssm families, not {cfg.family!r}"
        )

    logits = head_paged(params, cfg, x, lens=lens, head=head, axis_name=axis_name)
    return logits, new_state


def encode_for_decode(params: dict, cfg: ModelConfig, enc_embeds: jax.Array) -> dict:
    """Run the encoder and produce per-layer cross-attention K/V (whisper
    serve path): returns {'enc_k': [L,B,Se,G,hd], 'enc_v': ...}."""
    assert cfg.family == "encdec"
    B, Se, _ = enc_embeds.shape
    enc = enc_embeds.astype(cfg.dtype)
    enc_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32)[None], (B, Se))

    def enc_body(carry, p):
        h = L.attention_train(p["attn"], cfg.attn_spec(), carry, enc_pos, window=-1)
        return L.mlp(p["mlp"], cfg.mlp_spec(), h, quant=cfg.quant), None

    enc, _ = jax.lax.scan(enc_body, enc, params["enc_layers"])
    G, hd = cfg.kv_heads, cfg.hd

    def kv_body(_, px):
        ek = L.dense(px["xattn"]["wk"], enc).reshape(B, Se, G * hd)
        ev = L.dense(px["xattn"]["wv"], enc).reshape(B, Se, G * hd)
        return None, (ek, ev)

    _, (eks, evs) = jax.lax.scan(kv_body, None, params["xattn_layers"])
    return {"enc_k": eks, "enc_v": evs}
