"""In-situ per-layer kernel attribution: segmented re-execution of the
fused serving step.

The engine's fused step is one jitted graph — its trace span knows only
the aggregate ``device_wait``, never which layer (or which ``(w_bits,
a_bits)`` packing choice) the time went to.  This module closes that
gap the only way that measures the *serving configuration* rather than
a standalone kernel: every ``attrib_every`` engine steps, the step is
re-executed **segmented** — embedding, then each layer through
:func:`repro.models.transformer.decode_paged_layer` (the exact per-layer
body the fused step scans/unrolls), then the LM head — on the same
tokens/positions/lens/block-table and a donation-safe copy of the
pre-step paged state.  Each segment is timed with the repo's
``block_until_ready`` discipline, so a sample attributes real device
time to every layer and, through the layer's packed-weight metadata, to
its bit pair.

Outputs per sample:

* per-layer seconds and **shares** (shares sum to 1 by construction —
  the ``check_invariants.py --kind attrib`` gate re-checks anyway);
* accumulation into a shared :class:`~repro.obs.metrics.MetricsRegistry`
  (``repro_attrib_steps_total``, per-layer/per-pair seconds counters) so
  the telemetry endpoint exposes attribution alongside engine counters.

The shares come from a separate re-execution, so they are not put on the
trace of the step they were sampled in.

Sampling cost is paid only on sampled steps (one state copy + one
segmented re-execution); a disabled attributor costs the engine one
``is not None`` predicate per step, exactly like tracing.

:mod:`repro.obs.drift` consumes :attr:`LayerAttributor.samples` for its
``in-situ`` mode, reporting predicted-vs-measured rank inversions from
times measured inside the fused step next to the standalone numbers.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels.packed_matmul.ops import PackedDenseParams
from repro.models import transformer as T
from repro.obs.metrics import MetricsRegistry
from repro.parallel.sharding import ShardingRules, use_rules


def _iter_packed(tree):
    """Yield every PackedDenseParams node in a params subtree.  Packed
    leaves are pytree *nodes* (their arrays are the leaves), so this is
    an isinstance walk over the host structure, not a tree_map."""
    if isinstance(tree, PackedDenseParams):
        yield tree
        return
    if isinstance(tree, dict):
        for v in tree.values():
            yield from _iter_packed(v)
    elif isinstance(tree, (list, tuple)):
        for v in tree:
            yield from _iter_packed(v)


def layer_bit_pair(layer_params) -> tuple[int, int] | None:
    """The ``(w_bits, a_bits)`` pair of a layer's packed projections, or
    None for a float layer.  Plan granularity is one pair per layer; if a
    hand-built tree ever mixes pairs inside one layer, the smallest pair
    is reported (deterministic, and the interesting one for packing)."""
    pairs = sorted({(p.w_bits, p.a_bits) for p in _iter_packed(layer_params)})
    return pairs[0] if pairs else None


def pair_label(pair: tuple[int, int] | None) -> str:
    """Metric-label form of a bit pair: ``w5a4``, or ``fp`` for float."""
    return f"w{pair[0]}a{pair[1]}" if pair is not None else "fp"


class LayerAttributor:
    """Sampled segmented profiler for the paged decode step.

    Built once per engine (same ``cfg``/``params``/``head``/sharding
    rules as the fused step); :meth:`sample` re-executes one step's
    inputs layer by layer and returns the attribution row.  All jitted
    segment functions are donation-free, so re-running a segment for
    min-of-``reps`` timing is safe, and the caller's state copy is never
    invalidated.
    """

    def __init__(
        self,
        cfg,
        params,
        *,
        head=None,
        rules: ShardingRules | None = None,
        reps: int = 1,
        registry: MetricsRegistry | None = None,
        max_samples: int = 1024,
        gather: str = "xla",  # KV gather backend — must match the fused step
    ):
        if cfg.family not in ("attn", "ssm"):
            raise NotImplementedError(
                f"attribution covers the paged attn/ssm step, not {cfg.family!r}"
            )
        if reps < 1:
            raise ValueError("reps must be >= 1")
        self.cfg = cfg
        self.params = params
        self.head = head
        self.rules = rules if rules is not None else ShardingRules(enabled=False)
        self.reps = reps
        self.registry = registry if registry is not None else MetricsRegistry()
        self.max_samples = max_samples
        self.gather = gather
        self.samples: list[dict] = []
        self.n_sample_drops = 0  # samples beyond max_samples (oldest evicted)
        self._warm = False

        layers = params["layers"]
        self._per_layer = isinstance(layers, (list, tuple))
        if self._per_layer:
            if len(layers) != cfg.n_layers:
                raise ValueError(
                    f"params carry {len(layers)} layers, config {cfg.n_layers}"
                )
            self.bit_pairs = [layer_bit_pair(p) for p in layers]
        else:
            self.bit_pairs = [layer_bit_pair(layers)] * cfg.n_layers
        self._windows = cfg.windows() if cfg.family == "attn" else None

        rules_ = self.rules

        def embed_fn(p, tokens):
            with use_rules(rules_):
                return T.embed_paged(p, cfg, tokens)

        def layer_fn(p_i, state, i, table, h, pos, win, lens):
            # slice this layer's state inside the jit (dynamic index —
            # no host-side per-layer state copies)
            st = {k: v[i] for k, v in state.items()}
            with use_rules(rules_):
                return T.decode_paged_layer(
                    p_i, cfg, st, table, h, pos, window=win, lens=lens,
                    gather=gather,
                )

        def stacked_layer_fn(layers_, state, i, table, h, pos, win, lens):
            p_i = jax.tree.map(lambda a: a[i], layers_)
            st = {k: v[i] for k, v in state.items()}
            with use_rules(rules_):
                return T.decode_paged_layer(
                    p_i, cfg, st, table, h, pos, window=win, lens=lens,
                    gather=gather,
                )

        def head_fn(p, h, lens):
            with use_rules(rules_):
                return T.head_paged(p, cfg, h, lens=lens, head=head)

        self._embed = jax.jit(embed_fn)
        # list-params layers differ in static packed metadata, so the jit
        # cache compiles once per distinct structure; stacked params share
        # one compilation across all layer indices
        self._layer = jax.jit(layer_fn) if self._per_layer else jax.jit(stacked_layer_fn)
        self._head = jax.jit(head_fn)

    # -- timing ------------------------------------------------------------

    def _timed(self, fn, *args):
        """min-of-reps block_until_ready seconds, plus the output."""
        best, out = float("inf"), None
        for _ in range(self.reps):
            t0 = time.perf_counter()
            out = fn(*args)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        return best, out

    def _run(self, timed, state, table, tokens, pos, lens):
        cfg = self.cfg
        t_embed, h = timed(self._embed, self.params, tokens)
        layers = self.params["layers"]
        per_layer_s = []
        for i in range(cfg.n_layers):
            win = self._windows[i] if self._windows is not None else -1
            p_or_stack = layers[i] if self._per_layer else layers
            dt, (h, _) = timed(
                self._layer, p_or_stack, state, jnp.asarray(i, jnp.int32),
                table, h, pos, win, lens,
            )
            per_layer_s.append(dt)
        t_head, _ = timed(self._head, self.params, h, lens)
        return t_embed, per_layer_s, t_head

    def sample(
        self,
        state: dict,
        block_table,
        tokens,
        pos,
        lens=None,
        *,
        step: int | None = None,
    ) -> dict:
        """One attribution sample over a step's exact inputs.

        ``state`` must be a donation-safe copy of the **pre-step** paged
        state (the fused step donates the engine's buffer); the segment
        functions never donate, so ``state`` survives this call intact.
        """
        table = jnp.asarray(block_table)
        tokens = jnp.asarray(tokens)
        pos = jnp.asarray(pos)
        lens = None if lens is None else jnp.asarray(lens)
        if not self._warm:
            # compile pass: run every segment once untimed so the first
            # sample measures kernels, not XLA
            def untimed(fn, *args):
                out = fn(*args)
                jax.block_until_ready(out)
                return 0.0, out

            self._run(untimed, state, table, tokens, pos, lens)
            self._warm = True
        t_embed, per_layer_s, t_head = self._run(
            self._timed, state, table, tokens, pos, lens
        )
        total = sum(per_layer_s)
        rows = []
        reg = self.registry
        layer_sec = reg.counter(
            "repro_attrib_layer_seconds_total",
            "segmented in-situ device seconds by layer",
        )
        pair_sec = reg.counter(
            "repro_attrib_pair_seconds_total",
            "segmented in-situ device seconds by (w_bits, a_bits) pair",
        )
        for i, s in enumerate(per_layer_s):
            pair = self.bit_pairs[i]
            label = pair_label(pair)
            rows.append({
                "index": i,
                "w_bits": pair[0] if pair else None,
                "a_bits": pair[1] if pair else None,
                "pair": label,
                "seconds": s,
                "share": s / total if total > 0 else None,
            })
            layer_sec.inc(s, layer=str(i), pair=label)
            pair_sec.inc(s, pair=label)
        reg.counter(
            "repro_attrib_steps_total", "engine steps attributed in situ"
        ).inc()
        out = {
            "step": step,
            "reps": self.reps,
            "n_layers": self.cfg.n_layers,
            "embed_seconds": t_embed,
            "head_seconds": t_head,
            "total_layer_seconds": total,
            "layers": rows,
        }
        self.samples.append(out)
        if len(self.samples) > self.max_samples:
            del self.samples[0]
            self.n_sample_drops += 1
        return out

    # -- aggregation -------------------------------------------------------

    def summary(self) -> dict:
        """Mean attribution across all retained samples: per-layer mean
        seconds/share and per-pair share totals (render_tables + bench
        artifact input; :mod:`repro.obs.drift` re-derives its own)."""
        n = len(self.samples)
        if n == 0:
            return {"n_samples": 0, "layers": [], "pairs": []}
        n_layers = self.cfg.n_layers
        sec = [0.0] * n_layers
        shr = [0.0] * n_layers
        for s in self.samples:
            for row in s["layers"]:
                sec[row["index"]] += row["seconds"]
                shr[row["index"]] += row["share"] or 0.0
        layers = []
        by_pair: dict[str, dict] = {}
        for i in range(n_layers):
            pair = self.bit_pairs[i]
            label = pair_label(pair)
            layers.append({
                "index": i,
                "pair": label,
                "w_bits": pair[0] if pair else None,
                "a_bits": pair[1] if pair else None,
                "mean_seconds": sec[i] / n,
                "mean_share": shr[i] / n,
            })
            agg = by_pair.setdefault(
                label, {"pair": label, "n_layers": 0, "mean_seconds": 0.0,
                        "mean_share": 0.0}
            )
            agg["n_layers"] += 1
            agg["mean_seconds"] += sec[i] / n
            agg["mean_share"] += shr[i] / n
        return {
            "n_samples": n,
            "n_sample_drops": self.n_sample_drops,
            "layers": layers,
            "pairs": [by_pair[k] for k in sorted(by_pair)],
        }
