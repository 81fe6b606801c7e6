"""Continuous-batching decode engine over the paged KV/SSM cache.

One jitted step advances *every* active slot per iteration — a chunk of
up to ``chunk_tokens`` prompt (or replayed) tokens for requests still
prefilling, one freshly sampled token for those decoding — so the batch
stays full as long as the waiting queue has work (iteration-level
scheduling).  Prefill and decode coexist in the same fused step: tokens
ship as a dense ``[S, C]`` block with a per-slot valid-length vector,
the step scatters each slot's valid K/V rows through its block table,
and finishes with the LM head on each slot's last valid lane (optionally
prepacked sub-8-bit, so the last matmul of every step also runs through
the Pallas Kernel-Packing kernel).  Host-side bookkeeping (argmax
sampling, phase transitions, admission, page funding, preemption,
eviction) runs between steps on plain numpy.

With ``admit="on-demand"`` pages are granted just-in-time before each
step instead of worst-case-reserved at admission; on pool exhaustion the
lowest-progress slot is preempted (pages freed, request requeued with
its generated prefix) and replayed chunked later — token-identical under
greedy sampling because paged attention recomputes bit-exact rows.

**Mesh parallelism.**  ``EngineConfig.mesh = MeshConfig(dp, mp)`` shards
the engine across a ``(data, model)`` mesh.  Each of the ``dp`` data
replicas owns its *own* page pool, block table, and scheduler shard
(requests are routed round-robin at admission), and the fused step
advances every replica at once: the batch ships as ``[dp, S, C]``.
``mp > 1`` additionally tensor-parallelizes the model — packed weights
are sliced on N *before* prepacking (against the global tanh normalizer,
so per-shard packed words equal slices of the single-device prepack and
no repacking ever follows a collective), attention/SSM heads and the
vocab shard on the model axis, MoE experts shard by expert, and the step
runs under ``shard_map`` with exactly one psum-style collective per
block plus one tiled all-gather for the logits.  ``dp > 1`` with
``mp == 1`` needs no mesh at all: the *same compiled* single-shard step
dispatches once per replica on its own state, so replica semantics are
testable on a single device and each replica's tokens are bit-identical
to the single-device engine (a ``vmap``-stacked step would compile a
different XLA graph whose ~1e-4 logit deltas can flip greedy argmax on
near-ties).  ``dp == mp == 1`` is byte-identical to the pre-mesh engine.

**Request lifecycle & fault tolerance.**  Every request ends in exactly
one terminal status (``ok | cancelled | shed | failed`` — see
:mod:`repro.serving.lifecycle`).  Between steps the engine polices
cooperative cancellation, TTFT/total deadlines (shedding requests that
expired or provably cannot meet their deadline), and a bounded waiting
queue (``max_waiting`` per replica) that sheds the lowest-deadline-slack
request under backpressure.  A stall watchdog replaces the old hard
``RuntimeError``: after ``watchdog_ticks`` idle loop iterations with
waiting work the head request is shed deterministically, so ``run()``
never crashes and never spins forever; with ``dp > 1`` a replica that
stalls on its own (waiting work, nothing placeable) while siblings make
progress is quarantined *whole* for ``quarantine_ticks`` and its waiting
queue re-routed to the least-loaded live replica.  Faults in the fused
step are retried up to ``max_step_retries`` times (transient faults fire
*before* the step touches state, so the retry is exact); on exhaustion —
or on a non-finite logits row about to be sampled — the victim request
is preempted through the PR-5 token-identical requeue/replay path and
its slot quarantined for ``quarantine_ticks``.  A request accumulating
more than ``max_request_retries`` fault strikes is finalized
``failed``.  Non-injected (hard) step exceptions invalidate the donated
state buffer: the engine restores a ``CheckpointManager`` snapshot of
the paged state (``snapshot_every``) or re-initializes it, then replays
every in-flight request — correctness never depends on snapshot
freshness because replay rebuilds all resident rows.

Per-request latency/throughput is recorded against either the wall
clock (serving benchmarks) or a deterministic virtual step clock
(tests): ``run(realtime=False)`` counts one time unit per engine step.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import Counter
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models import transformer as T
from repro.kernels.paged_gather.ops import check_gather_backend
from repro.models.layers import prepack_lm_head
from repro.obs.attrib import LayerAttributor
from repro.obs.metrics import MetricsRegistry, WindowedSeries, percentile
from repro.obs.trace import TraceRecorder
from repro.parallel.sharding import ShardingRules, use_rules
from repro.serving.chaos import ChaosConfig, ChaosInjector, InjectedFault
from repro.serving.lifecycle import SLO, TERMINAL_STATUSES, Request
from repro.serving.paged_kv import BlockTable, PageAllocator
from repro.serving.scheduler import Scheduler


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Observability knobs, grouped (PR-10 API redesign).

    ``EngineConfig`` used to carry these flat; the flat keywords still
    work as deprecated shims (see ``EngineConfig.__post_init__``).
    """

    # > 0: every N steps, re-execute the step segmented per layer on a
    # donation-safe state copy and attribute device time to each layer /
    # bit pair (repro.obs.attrib).  0 (off) costs one predicate per step.
    attrib_every: int = 0
    # timing repetitions per attribution segment (min-of-reps)
    attrib_reps: int = 1
    # > 0 with run(trace=<path>): rewrite the partial trace to disk every
    # N steps, so a crashed run still leaves a loadable trace behind
    trace_checkpoint_every: int = 0
    # serve /metrics, /livez, /trace on this port while running (the CLI
    # / build_engine front door starts the TelemetryServer; the engine
    # itself never opens sockets).  None = no telemetry server.
    telemetry_port: int | None = None


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Mesh shape for the serving engine: ``dp`` data replicas x ``mp``
    tensor/expert-parallel model shards.  ``(1, 1)`` (default) is the
    single-device engine; ``mp > 1`` requires ``dp * mp`` JAX devices."""

    dp: int = 1
    mp: int = 1

    def __post_init__(self):
        if self.dp < 1 or self.mp < 1:
            raise ValueError(f"mesh axes must be >= 1, got dp={self.dp} mp={self.mp}")

    @property
    def enabled(self) -> bool:
        return self.dp > 1 or self.mp > 1

    @property
    def n_devices(self) -> int:
        return self.dp * self.mp

    @classmethod
    def parse(cls, spec) -> "MeshConfig":
        """``"2x2"`` / ``"2"`` / ``(2, 2)`` / ``None`` -> MeshConfig."""
        if spec is None:
            return cls()
        if isinstance(spec, MeshConfig):
            return spec
        if isinstance(spec, str):
            parts = [int(p) for p in spec.lower().split("x")]
        else:
            parts = [int(p) for p in spec]
        if len(parts) == 1:
            return cls(dp=parts[0])
        if len(parts) == 2:
            return cls(dp=parts[0], mp=parts[1])
        raise ValueError(f"mesh spec must be DP or DPxMP, got {spec!r}")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    n_slots: int = 8
    page_size: int = 16
    max_len: int = 128  # per-sequence cap: prompt + generated tokens
    # page-pool budget; 0 => full residency (every slot can hold max_len)
    n_pages: int = 0
    policy: str = "continuous"  # or "static" (gang admission baseline)
    # prefill chunk budget per slot per step; 1 = legacy one-token prefill
    chunk_tokens: int = 1
    # page admission: "reserve" (worst case at admit) or "on-demand"
    # (grow per step, preempt lowest-progress slot on pool exhaustion)
    admit: str = "reserve"
    packed_head: bool = False
    head_bits: tuple[int, int] = (8, 8)
    # -- lifecycle / fault tolerance ------------------------------------
    # waiting-queue bound per replica; 0 = unbounded.  Overflow sheds the
    # request with the least deadline slack (deadline-aware shedding).
    max_waiting: int = 0
    # idle loop iterations with waiting-but-unplaceable work before the
    # watchdog sheds the queue head (deterministic; replaces the old
    # stall RuntimeError).  With dp > 1 the same budget also trips the
    # whole-replica quarantine when one replica stalls alone.
    watchdog_ticks: int = 64
    # ticks a slot (or, dp > 1, a stalled replica) sits out after hosting
    # a fault before re-entering admission
    quarantine_ticks: int = 8
    # consecutive fused-step retries before escalating to a victim
    # preemption, and per-request fault strikes before status "failed"
    max_step_retries: int = 4
    max_request_retries: int = 3
    # assert page/slot accounting invariants after a drained run()
    check_invariants: bool = True
    # > 0: snapshot the paged device state via CheckpointManager every N
    # steps (restored on hard step faults; mirrors FaultTolerantRunner)
    snapshot_every: int = 0
    snapshot_dir: str | None = None
    # -- observability (DEPRECATED flat shims -> ObsConfig) --------------
    # None = take the nested ``obs`` value; an explicit int overrides it.
    # Prefer ``obs=ObsConfig(...)``; these keywords remain for PR-7/8/9
    # callers and will go away once nothing constructs them flat.
    attrib_every: int | None = None
    attrib_reps: int | None = None
    trace_checkpoint_every: int | None = None
    # KV gather backend inside the fused step: "xla" is the legacy
    # pool[block_table] gather, "kernel" the Pallas paged-gather kernel
    # (bit-exact either way — see models.layers.attention_decode_paged)
    gather_backend: str = "xla"
    # -- nested sub-configs (PR-10 canonical spelling) -------------------
    obs: ObsConfig = ObsConfig()
    # fault injection; disabled default.  (The legacy Engine(chaos=...)
    # keyword still wins when passed — deprecated shim.)
    chaos: ChaosConfig = ChaosConfig()
    mesh: MeshConfig = MeshConfig()

    def __post_init__(self):
        # fold the deprecated flat observability keywords into ``obs``
        # (flat wins when explicitly set), then mirror the resolved
        # values back so legacy readers of the flat fields keep working.
        obs = self.obs
        for name in ("attrib_every", "attrib_reps", "trace_checkpoint_every"):
            v = getattr(self, name)
            if v is not None and v != getattr(obs, name):
                obs = dataclasses.replace(obs, **{name: v})
        object.__setattr__(self, "obs", obs)
        for name in ("attrib_every", "attrib_reps", "trace_checkpoint_every"):
            object.__setattr__(self, name, getattr(obs, name))

    @property
    def blocks_per_slot(self) -> int:
        return -(-self.max_len // self.page_size)

    def pool_pages(self) -> int:
        return self.n_pages or self.n_slots * self.blocks_per_slot + 1

    @classmethod
    def from_cli(cls, args) -> "EngineConfig":
        """Build an EngineConfig from an argparse namespace (the serving
        CLI / benchmark flag set).  Missing attributes take the field
        defaults, so partial namespaces — tests, ad-hoc scripts — work.
        This is the *only* place CLI flags turn into engine knobs; mesh
        options (``--mesh DPxMP``) enter the engine exclusively here or
        via an explicit ``MeshConfig``."""
        g = lambda name, default: getattr(args, name, default)  # noqa: E731
        packed = bool(g("packed", False))
        return cls(
            n_slots=g("batch", 8),
            page_size=g("page_size", 16),
            max_len=g("max_len", 128),
            n_pages=g("pages", 0),
            chunk_tokens=g("chunk_tokens", 1),
            admit=g("admit", "reserve"),
            packed_head=bool(g("packed_head", False)),
            head_bits=(g("wbits", 8), g("abits", 8)) if packed else (8, 8),
            max_waiting=g("max_waiting", 0),
            gather_backend=g("gather_backend", "xla"),
            obs=ObsConfig(
                attrib_every=g("attrib_every", 0),
                attrib_reps=g("attrib_reps", 1),
                trace_checkpoint_every=g("trace_checkpoint_every", 0),
                telemetry_port=g("telemetry_port", None),
            ),
            chaos=ChaosConfig(
                seed=g("chaos_seed", 0),
                step_fault_rate=g("chaos_step_rate", 0.0),
                alloc_fault_rate=g("chaos_alloc_rate", 0.0),
                nan_rate=g("chaos_nan_rate", 0.0),
            ),
            mesh=MeshConfig.parse(g("mesh", None)),
        )


def _place_by_shard(tree, sharding):
    """Place ``[mp, ...]``-stacked host arrays on a mesh so each device
    receives only its own slice.  (With a plain ``device_put`` of the
    host tree, llama3.2-3b at mp=4 peaked at 16.8 GB on the first chip,
    about the size of the whole stacked tree, against 3.6 GB in use per
    chip after placement.)"""

    def one(a):
        host = np.asarray(a)
        return jax.make_array_from_callback(host.shape, sharding, lambda idx: host[idx])

    return jax.tree.map(one, tree)


@dataclasses.dataclass
class _Replica:
    """One data-parallel shard's host-side serving state: its own page
    pool, block table, and scheduler (waiting queue + active slots)."""

    index: int
    allocator: PageAllocator  # possibly chaos-wrapped; injector is shared
    block_table: BlockTable
    scheduler: Scheduler
    idle: int = 0  # consecutive stalled ticks (replica watchdog clock)
    quarantined_until: float | None = None  # tick when the replica re-enters

    @property
    def quarantined(self) -> bool:
        return self.quarantined_until is not None


class Engine:
    """Request-level serving engine: submit() prompts, run() to completion."""

    def __init__(
        self,
        cfg: T.ModelConfig,
        params,
        ecfg: EngineConfig = EngineConfig(),
        rules: ShardingRules | None = None,
        head=None,
        chaos: ChaosConfig | None = None,
        *,
        shard_params=None,
    ):
        """``head`` optionally injects prepacked LM-head weights (e.g. from
        a deployment plan's ``lm_head`` entry via
        :func:`repro.plan.apply.apply_plan`); otherwise ``ecfg.packed_head``
        prepacks the tied embedding at ``ecfg.head_bits`` here.  ``chaos``
        (deprecated — prefer ``ecfg.chaos``) arms the deterministic fault
        injector (:mod:`repro.serving.chaos`) around the fused step and
        every replica's page allocator.

        With ``ecfg.mesh.mp > 1``, ``params`` must be *unpacked* (float
        or int8 serving dicts): the engine slices each rank's
        tensor-parallel shard first, because packed words only equal
        slices of the global prepack when slicing precedes packing.
        Callers with packed/plan weights pass pre-sliced, pre-packed,
        ``[mp, ...]``-stacked shards via ``shard_params`` (and a stacked
        ``head``) — :func:`repro.serving.api.build_engine` does exactly
        that and is the recommended front door.
        """
        if cfg.family not in ("attn", "ssm"):
            raise NotImplementedError(
                f"continuous batching supports attn/ssm families, not {cfg.family!r}"
            )
        if ecfg.chunk_tokens < 1:
            raise ValueError("chunk_tokens must be >= 1")
        if ecfg.max_step_retries < 0 or ecfg.max_request_retries < 0:
            raise ValueError("retry budgets must be >= 0")
        if ecfg.attrib_every < 0 or ecfg.trace_checkpoint_every < 0:
            raise ValueError("attrib_every/trace_checkpoint_every must be >= 0")
        if ecfg.attrib_reps < 1:
            raise ValueError("attrib_reps must be >= 1")
        check_gather_backend(ecfg.gather_backend)
        self.cfg = cfg
        self.ecfg = ecfg
        self.rules = rules if rules is not None else ShardingRules(enabled=False)
        self.dp, self.mp = ecfg.mesh.dp, ecfg.mesh.mp
        if self.mp > 1 and cfg.kv_dtype == "int8" and cfg.family == "attn":
            raise NotImplementedError(
                "int8 KV pools carry one scale per page row over the full "
                "kv-head dim; a model-parallel slice would change every "
                "scale.  Serve int8 KV with mp=1 or switch kv_dtype."
            )
        if ecfg.attrib_every > 0 and self.mp > 1:
            raise ValueError(
                "in-situ attribution re-executes the step single-shard; it "
                "is not supported with model parallelism (mesh.mp > 1) — "
                "set attrib_every=0"
            )
        # legacy chaos keyword wins over the nested config (deprecated shim)
        chaos_cfg = chaos if chaos is not None else ecfg.chaos
        self._chaos = (
            ChaosInjector(chaos_cfg)
            if chaos_cfg is not None and chaos_cfg.enabled
            else None
        )
        n_pages = ecfg.pool_pages()
        self.replicas: list[_Replica] = []
        for r in range(self.dp):
            allocator = PageAllocator(n_pages)
            if self._chaos is not None:
                allocator = self._chaos.wrap_allocator(allocator)
            table = BlockTable(ecfg.n_slots, ecfg.blocks_per_slot)
            sched = Scheduler(
                ecfg.n_slots, allocator, table, ecfg.page_size,
                policy=ecfg.policy, admit=ecfg.admit,
            )
            self.replicas.append(_Replica(r, allocator, table, sched))
        # replica-0 aliases: the single-replica API every pre-mesh caller
        # (tests, benchmarks, telemetry) already holds
        self.allocator = self.replicas[0].allocator
        self.block_table = self.replicas[0].block_table
        self.scheduler = self.replicas[0].scheduler
        self._rr = 0  # round-robin request -> replica routing cursor
        self.replica_quarantines = 0

        # -- params / head (per-shard sliced + packed when mp > 1) ---------
        self._local_cfg = (
            cfg if self.mp == 1 else dataclasses.replace(cfg, tp_shards=self.mp)
        )
        self._mesh = None
        if self.mp > 1:
            from repro.launch.mesh import make_host_mesh

            self._mesh = make_host_mesh((self.dp, self.mp), axes=("data", "model"))
        # replica r of a dp > 1, mp == 1 engine runs on device r when the
        # host has that many devices (else every replica shares device 0)
        devs = jax.devices()
        self._devices = (
            devs[: self.dp] if self.mp == 1 and len(devs) >= self.dp
            else [devs[0]] * self.dp
        )
        if self.mp > 1:
            from repro.parallel.sharding import slice_decode_params, stack_decode_shards

            if shard_params is None:
                shard_params = stack_decode_shards(
                    [slice_decode_params(params, cfg, self.mp, r) for r in range(self.mp)]
                )
            self.params = shard_params
            if head is None and ecfg.packed_head:
                from repro.core.quant import weight_tanh_max

                emb = params["embed"]
                vs = emb.shape[0] // self.mp
                t_max = weight_tanh_max(emb)
                head = stack_decode_shards([
                    prepack_lm_head(
                        emb[r * vs : (r + 1) * vs],
                        w_bits=ecfg.head_bits[0], a_bits=ecfg.head_bits[1],
                        t_max=t_max,
                    )
                    for r in range(self.mp)
                ])
            # each model rank's chip holds only its own shard
            by_rank = NamedSharding(self._mesh, P("model"))
            self.params = _place_by_shard(self.params, by_rank)
            if head is not None:
                head = _place_by_shard(head, by_rank)
            self._rep_params = [self.params] * self.dp
        else:
            if head is None and ecfg.packed_head:
                head = prepack_lm_head(
                    params["embed"], w_bits=ecfg.head_bits[0], a_bits=ecfg.head_bits[1]
                )
            placed = {d: jax.device_put(params, d) for d in set(self._devices)}
            self._rep_params = [placed[d] for d in self._devices]
            self.params = self._rep_params[0]
        self._head = head  # kept for segmented re-execution (attribution)

        self._ckpt = None
        if ecfg.snapshot_every > 0:
            import tempfile

            from repro.checkpoint.manager import CheckpointManager

            snap_dir = ecfg.snapshot_dir or tempfile.mkdtemp(prefix="engine-snap-")
            self._ckpt = CheckpointManager(snap_dir, keep=2)

        # -- device state (leading [dp] / [dp, mp] axes when stacked) ------
        self.state = self._init_state()
        self._build_step(head)
        self._build_reset()

        self._pending: list[Request] = []  # sorted by arrival
        self._next_rid = 0
        self.n_steps = 0
        self.ticks = 0  # run()-loop iterations (quarantine/watchdog clock)
        self.slot_token_steps = 0  # active slots summed over steps (occupancy)
        self.fed_tokens = 0  # valid token lanes summed over steps
        self.finished: list[Request] = []
        self.step_retries = 0  # fused-step attempts burned on injected faults
        self.hard_recoveries = 0  # state restores after non-injected step faults
        self.fault_log: list[str] = []  # one line per recovered hard fault
        # host copy of the latest step's logits, [dp, n_slots, vocab]
        # (row [r, s] is sampled for slot s of replica r when its chunk
        # completes the request's sequence)
        self.last_logits: np.ndarray | None = None
        self._step_time_ewma: float | None = None  # realtime deadline estimator
        # -- observability ------------------------------------------------
        # tracing is a single `is not None` predicate on every hot-path
        # hook; holders stay None until run(trace=...) arms a recorder
        self._trace: TraceRecorder | None = None
        self._trace_path = None
        self._t_wall0: float | None = None  # run() start (monotonic)
        self._t_run_end: float | None = None  # frozen elapsed after run()
        self._vclock = 0.0
        self.registry = MetricsRegistry()
        self._win_tokens = WindowedSeries()
        self._win_steps = WindowedSeries()
        self._win_sheds = WindowedSeries()
        self._win_preempts = WindowedSeries()
        # in-situ attribution: same off-mode discipline as tracing — the
        # hot path pays one `is not None` predicate when disabled.  With
        # dp > 1 (mp == 1: params stay global) replica 0's shard is
        # sampled; mp > 1 was rejected above.
        self._attrib: LayerAttributor | None = None
        if ecfg.attrib_every > 0:
            self._attrib = LayerAttributor(
                cfg, self.params, head=head, rules=self.rules,
                reps=ecfg.attrib_reps, registry=self.registry,
                gather=ecfg.gather_backend,
            )

    # -- construction helpers ----------------------------------------------

    @property
    def _stacked(self) -> bool:
        """True when engine state/batches carry a leading replica axis."""
        return self.dp > 1 or self.mp > 1

    def _init_state(self):
        """Device state: one tree (dp == mp == 1), a *list* of per-replica
        trees (dp > 1, mp == 1 — each replica's buffer lives on its
        replica's device and is dispatched and donated independently), or
        one ``[dp, mp, ...]``-stacked tree sharded over the mesh (mp > 1 —
        the shard_map step owns the whole mesh's state)."""
        ecfg = self.ecfg

        def fresh(device):
            with jax.default_device(device):
                base = T.init_paged_state(
                    self._local_cfg, ecfg.n_slots, ecfg.pool_pages(),
                    ecfg.page_size, dtype=self.cfg.dtype,
                )
            return jax.device_put(base, device)

        if self.mp > 1:
            tiled = jax.tree.map(
                lambda a: jnp.tile(a[None, None], (self.dp, self.mp) + (1,) * a.ndim),
                fresh(self._devices[0]),
            )
            return jax.device_put(tiled, NamedSharding(self._mesh, P("data", "model")))
        if self.dp > 1:
            return [fresh(d) for d in self._devices]
        return fresh(self._devices[0])

    def _build_step(self, head) -> None:
        """Compile-ready fused step for the engine's mesh mode.

        * ``mp == 1`` (any ``dp``): the legacy single-shard jit —
          byte-identical signature and XLA graph to the pre-mesh engine.
          With ``dp > 1`` the step loop dispatches this *same compiled
          executable* once per replica, so per-request tokens are
          bit-identical to the single-device engine by construction.
        * ``mp > 1``: ``shard_map`` over the ``(data, model)`` mesh —
          params/head enter stacked on a leading ``[mp]`` axis with spec
          ``P("model")``, state on ``[dp, mp]`` with
          ``P("data", "model")``, batches on ``[dp]`` with ``P("data")``;
          logits return model-replicated (the head all-gathers).
        """
        cfg, ecfg, rules = self.cfg, self.ecfg, self.rules
        local_cfg = self._local_cfg
        C = ecfg.chunk_tokens
        if self.mp == 1:
            # C == 1 keeps the legacy single-token step signature (and XLA
            # graph) byte-identical; C > 1 threads the valid-length vector
            # through the fused step so prefill chunks and decode lanes
            # share one compilation
            if C > 1:

                def step_fn(p, state, table, tokens, pos, lens):
                    with use_rules(rules):
                        return T.forward_decode_paged(
                            p, cfg, state, table, tokens, pos, head=head, lens=lens,
                            gather=ecfg.gather_backend,
                        )

            else:

                def step_fn(p, state, table, tokens, pos):
                    with use_rules(rules):
                        return T.forward_decode_paged(
                            p, cfg, state, table, tokens, pos, head=head,
                            gather=ecfg.gather_backend,
                        )

            self._step = jax.jit(step_fn, donate_argnums=(1,))
            return
        # mesh (dp, mp): params+head ride one tuple argument so each model
        # rank gets its own slice (a closed-over head would replicate)
        smap = functools.partial(jax.shard_map, check_vma=False)

        def _drop_lead(tree):
            return jax.tree.map(lambda a: jnp.squeeze(a, 0), tree)

        def body(*args):
            if C > 1:
                ph, state, table, tokens, pos, lens = args
            else:
                ph, state, table, tokens, pos = args
                lens = None
            p, hd = ph
            p = _drop_lead(p)  # local [1(model), ...] -> this rank's shard
            hd = None if hd is None else _drop_lead(hd)
            st = jax.tree.map(lambda a: jnp.squeeze(jnp.squeeze(a, 1), 0), state)
            kw = dict(head=hd, gather=ecfg.gather_backend, axis_name="model")
            if lens is not None:
                kw["lens"] = lens[0]
            with use_rules(rules):
                logits, ns = T.forward_decode_paged(
                    p, local_cfg, st, table[0], tokens[0], pos[0], **kw
                )
            return logits[None], jax.tree.map(lambda a: a[None, None], ns)

        n_batch = 4 if C > 1 else 3
        in_specs = (P("model"), P("data", "model")) + (P("data"),) * n_batch
        fn = smap(
            body, mesh=self._mesh, in_specs=in_specs,
            out_specs=(P("data"), P("data", "model")),
        )
        jitted = jax.jit(fn, donate_argnums=(1,))
        mesh = self._mesh

        def mesh_step(*args):
            with jax.set_mesh(mesh):
                return jitted(*args)

        self._step = mesh_step

    def _build_reset(self) -> None:
        cfg, local_cfg, mp = self.cfg, self._local_cfg, self.mp
        if mp == 1:
            # dp > 1 reuses this same jit per replica on its own tree
            self._reset = jax.jit(
                lambda state, slot: T.reset_paged_slot(cfg, state, slot),
                donate_argnums=(0,),
            )
            return

        def reset_fn(state, rep, slot):
            sub = jax.tree.map(lambda a: a[rep], state)
            sub = jax.vmap(lambda s: T.reset_paged_slot(local_cfg, s, slot))(sub)
            return jax.tree.map(lambda full, r_: full.at[rep].set(r_), state, sub)

        self._reset = jax.jit(reset_fn, donate_argnums=(0,))

    def _reset_slot(self, replica: int, slot: int) -> None:
        """Zero one slot's recurrent (SSM) state on (re-)admission: a
        replayed request rebuilds its state from position 0."""
        if self.cfg.family != "ssm":
            return
        slot_ = jnp.asarray(slot, jnp.int32)
        if self.mp > 1:
            self.state = self._reset(self.state, jnp.asarray(replica, jnp.int32), slot_)
        elif self.dp > 1:
            self.state[replica] = self._reset(self.state[replica], slot_)
        else:
            self.state = self._reset(self.state, slot_)

    def _params_arg(self):
        """First fused-step argument: the raw params tree, or — on the
        mesh — the ``(params, head)`` tuple so the head shards too."""
        return (self.params, self._head) if self.mp > 1 else self.params

    def _live_replicas(self) -> list[_Replica]:
        return [r for r in self.replicas if not r.quarantined]

    def _any_active(self) -> bool:
        return any(rep.scheduler.active for rep in self.replicas)

    def _all_done(self) -> bool:
        return all(rep.scheduler.all_done() for rep in self.replicas)

    def _active_items(self):
        """(replica, slot, request) triples over every replica's batch."""
        for rep in self.replicas:
            for slot, req in rep.scheduler.active.items():
                yield rep, slot, req

    # -- request intake ----------------------------------------------------

    def submit(
        self,
        prompt,
        max_new_tokens: int,
        arrival: float = 0.0,
        *,
        deadline: float | None = None,
        ttft_deadline: float | None = None,
        slo: SLO | None = None,
    ) -> Request:
        """Queue a request.  ``deadline``/``ttft_deadline`` are absolute
        engine-clock times; an :class:`SLO` instead carries relative
        budgets resolved against ``arrival`` (explicit deadlines win)."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(prompt) + max_new_tokens > self.ecfg.max_len:
            raise ValueError(
                f"prompt({len(prompt)}) + max_new({max_new_tokens}) exceeds "
                f"max_len {self.ecfg.max_len}"
            )
        slo_name = None
        if slo is not None:
            slo_ttft, slo_total = slo.resolve(arrival)
            ttft_deadline = ttft_deadline if ttft_deadline is not None else slo_ttft
            deadline = deadline if deadline is not None else slo_total
            slo_name = slo.name
        req = Request(
            self._next_rid, prompt, max_new_tokens, arrival=arrival,
            deadline=deadline, ttft_deadline=ttft_deadline, slo=slo_name,
        )
        self._next_rid += 1
        self._pending.append(req)
        self._pending.sort(key=lambda r: r.arrival)
        if self._trace is not None:
            self._trace_attach(req)
        return req

    def cancel(self, req: Request) -> bool:
        """Request cooperative cancellation.  Returns False if the request
        already carries a terminal status; otherwise it will be finalized
        ``cancelled`` (pages/slot reclaimed, partial output kept) at the
        next between-steps policing pass."""
        if req.status is not None:
            return False
        req.cancel()
        return True

    # -- tracing -----------------------------------------------------------

    def _trace_attach(self, req: Request) -> None:
        """Open the request's envelope + ``queued`` phase span (idempotent,
        so arming a recorder after submissions double-begins nothing)."""
        self._trace.req_begin(
            req.rid, prompt_tokens=len(req.prompt),
            max_new_tokens=req.max_new_tokens, arrival=req.arrival,
            slo=req.slo,
        )
        if self._trace.phase(req.rid) is None:
            self._trace.req_phase(req.rid, "queued")

    def _arm_trace(self, trace) -> None:
        """``trace`` is a TraceRecorder, or a path to save a fresh one to
        at the end of ``run()``.  Already-submitted requests (pending,
        waiting, or resident from an earlier run) are re-attached."""
        if isinstance(trace, TraceRecorder):
            self._trace, self._trace_path = trace, None
        else:
            self._trace, self._trace_path = TraceRecorder(), trace
        for req in self._pending:
            self._trace_attach(req)
        for rep in self.replicas:
            for req in rep.scheduler.waiting:
                self._trace_attach(req)
            for req in rep.scheduler.active.values():
                self._trace_attach(req)
                self._trace.req_phase(req.rid, "prefill", slot=req.slot)
        if self._chaos is not None:
            self._chaos.trace = self._trace

    def _seal_trace(self) -> None:
        """Stamp run metadata into the recorder (the block the trace gates
        cross-check against) and save it when run() owns the file."""
        tr = self._trace
        m = self.metrics()
        tr.metadata.update(
            arch=self.cfg.name, family=self.cfg.family,
            policy=self.ecfg.policy, admit=self.ecfg.admit,
            chunk_tokens=self.ecfg.chunk_tokens, realtime=self._realtime,
            steps=self.n_steps, n_requests=len(self.finished),
            statuses=m["statuses"], injected=m["injected"],
            preemptions=m["preemptions"], step_retries=self.step_retries,
            chaos_seed=self._chaos.cfg.seed if self._chaos is not None else None,
            dp=self.dp, mp=self.mp,
        )
        if self._trace_path is not None:
            tr.save(self._trace_path)

    # -- step loop ---------------------------------------------------------

    def warmup(self) -> None:
        """Compile the fused step before timing (all-slots-inactive shapes
        are identical to live ones; the garbage rows land on null page 0)."""
        S, C = self.ecfg.n_slots, self.ecfg.chunk_tokens
        if self.mp > 1:
            table = np.stack([rep.block_table.as_array() for rep in self.replicas])
            args = [
                self._params_arg(),
                self.state,
                jnp.asarray(table),
                jnp.zeros((self.dp, S, C), jnp.int32),
                jnp.zeros((self.dp, S), jnp.int32),
            ]
            if C > 1:
                args.append(jnp.zeros((self.dp, S), jnp.int32))
            logits, self.state = self._step(*args)
            jax.block_until_ready(logits)
            return
        for rep in self.replicas:
            args = [
                self._rep_params[rep.index],
                self.state[rep.index] if self.dp > 1 else self.state,
                jnp.asarray(rep.block_table.as_array()),
                jnp.zeros((S, C), jnp.int32),
                jnp.zeros((S,), jnp.int32),
            ]
            if C > 1:
                args.append(jnp.zeros((S,), jnp.int32))
            logits, ns = self._step(*args)
            if self.dp > 1:
                self.state[rep.index] = ns
            else:
                self.state = ns
            jax.block_until_ready(logits)

    def _route_replica(self) -> _Replica:
        """Round-robin over live (non-quarantined) replicas — the
        deterministic request -> replica-shard assignment."""
        pool = self._live_replicas() or self.replicas
        rep = pool[self._rr % len(pool)]
        self._rr += 1
        return rep

    def _admit(self, now: float) -> None:
        while self._pending and self._pending[0].arrival <= now:
            req = self._pending.pop(0)
            rep = self._route_replica()
            req.replica = rep.index
            rep.scheduler.submit(req)
        for rep in self.replicas:
            if rep.quarantined:
                continue
            for req in rep.scheduler.admit(now):
                # zero recurrent state on every (re-)admission: a replayed
                # SSM request rebuilds its state from position 0
                self._reset_slot(rep.index, req.slot)
                if self._trace is not None:
                    self._trace.req_phase(req.rid, "prefill", slot=req.slot,
                                          replayed=req.n_preempted > 0)

    # -- lifecycle policing ------------------------------------------------

    def _finalize(self, req: Request, status: str, now: float, reason: str | None = None) -> None:
        """Move a request to its terminal status exactly once, reclaiming
        its pages/slot through its replica's scheduler if it is resident."""
        assert req.status is None, f"rid {req.rid} already terminal ({req.status})"
        assert status in TERMINAL_STATUSES, status
        if req.slot != -1:
            self.replicas[req.replica].scheduler.finish(req, now)
        else:
            req.t_finish = now
        req.status = status
        if reason is not None:
            req.shed_reason = reason
        self.finished.append(req)
        self.registry.counter(
            "repro_requests_total", "requests by terminal status"
        ).inc(status=status)
        if status == "shed":
            self._win_sheds.add(now)
        if self._trace is not None:
            self._trace.req_end(req.rid, status, reason=reason,
                                out_tokens=len(req.out_tokens))

    def _est_service_time(self, req: Request) -> float | None:
        """Optimistic remaining-service estimate on the engine clock, or
        None when no per-step time estimate exists yet (realtime warmup)."""
        per_step = 1.0 if not self._realtime else self._step_time_ewma
        if per_step is None:
            return None
        return req.min_steps_left(self.ecfg.chunk_tokens) * per_step

    def _expired_reason(self, req: Request, now: float) -> str | None:
        if req.deadline is not None and now >= req.deadline and not req.done:
            return "deadline"
        if (
            req.ttft_deadline is not None
            and req.t_first_token is None
            and now >= req.ttft_deadline
        ):
            return "ttft"
        return None

    def _slack(self, req: Request, now: float) -> float:
        """Deadline slack (time to spare under an optimistic service
        estimate); +inf for requests without a deadline."""
        if req.deadline is None:
            return float("inf")
        est = self._est_service_time(req)
        return req.deadline - now - (est if est is not None else 0.0)

    def _police(self, now: float) -> None:
        """Between-steps lifecycle pass: cooperative cancellation, deadline
        expiry/infeasibility shedding, and bounded-queue backpressure —
        applied to every replica shard."""
        for req in [r for r in self._pending if r.cancel_requested]:
            self._pending.remove(req)
            self._finalize(req, "cancelled", now)
        for rep in self.replicas:
            sched = rep.scheduler
            # cancellation: cooperative, honoured wherever the request sits
            for req in [r for r in list(sched.waiting) if r.cancel_requested]:
                sched.remove_waiting(req)
                self._finalize(req, "cancelled", now)
            for req in [r for r in list(sched.active.values()) if r.cancel_requested]:
                self._finalize(req, "cancelled", now)
            # deadline expiry (active requests are dropped mid-decode: their
            # pages fund work that can still meet its SLO)
            for req in list(sched.active.values()):
                reason = self._expired_reason(req, now)
                if reason is not None:
                    self._finalize(req, "shed", now, reason=reason)
            for req in list(sched.waiting):
                reason = self._expired_reason(req, now)
                if reason is None and req.deadline is not None:
                    est = self._est_service_time(req)
                    if est is not None and now + est > req.deadline:
                        reason = "infeasible"
                if reason is not None:
                    sched.remove_waiting(req)
                    self._finalize(req, "shed", now, reason=reason)
            # backpressure: bounded waiting queue sheds the least-slack request
            if self.ecfg.max_waiting:
                while len(sched.waiting) > self.ecfg.max_waiting:
                    victim = min(
                        sched.waiting,
                        key=lambda r: (self._slack(r, now), -r.arrival, -r.rid),
                    )
                    sched.remove_waiting(victim)
                    self._finalize(victim, "shed", now, reason="queue-overflow")

    # -- fault handling ----------------------------------------------------

    def _strike(self, req: Request, now: float) -> None:
        """One fault strike against a resident request: preempt it through
        the token-identical requeue/replay path and quarantine its slot;
        over-budget requests are finalized ``failed`` instead of replayed."""
        sched = self.replicas[req.replica].scheduler
        slot = req.slot
        req.n_faults += 1
        sched.preempt(req, now)
        sched.quarantine_slot(slot, self.ticks + self.ecfg.quarantine_ticks)
        self._win_preempts.add(now)
        if self._trace is not None:
            self._trace.req_event(req.rid, "fault_strike", n_faults=req.n_faults)
            self._trace.req_event(req.rid, "quarantine", slot=slot,
                                  until_tick=self.ticks + self.ecfg.quarantine_ticks)
            self._trace.req_phase(req.rid, "queued", reason="fault")
        if req.n_faults > self.ecfg.max_request_retries:
            sched.remove_waiting(req)
            self._finalize(req, "failed", now)

    def _pick_victim(self) -> Request:
        """Lowest-progress active request across every replica (ties:
        youngest rid) — the global twin of ``Scheduler.pick_victim``."""
        return min(
            (req for _, _, req in self._active_items()),
            key=lambda r: (r.n_fed, -r.rid),
        )

    def _recover_hard_fault(self, exc: Exception, now: float) -> None:
        """A non-injected exception escaped the fused step: the donated
        state buffer can no longer be trusted.  Restore the latest
        snapshot (or re-initialize) and replay every in-flight request —
        replay rewrites all resident rows, so correctness is independent
        of snapshot freshness."""
        self.hard_recoveries += 1
        self.fault_log.append(f"step {self.n_steps}: {type(exc).__name__}: {exc}")
        for _, _, req in list(self._active_items()):
            self._strike(req, now)
        self.state = self._restore_state()

    def _restore_state(self):
        template = self._init_state()
        if self._ckpt is not None:
            self._ckpt.wait()
            if self._ckpt.latest_step() is not None:
                _, state = self._ckpt.restore(template)
                return state
        return template

    def _fund_pages(self, now: float) -> None:
        """On-demand mode: before the step, grow every active slot's page
        list to cover its chunk (each replica funds from its own pool).
        Slots are funded in descending-progress order; on pool exhaustion
        the replica's lowest-progress slot is preempted (freeing its pages
        for the rest) — possibly the requester itself, in which case it
        leaves the batch and replays later.  The highest-progress slot can
        always be funded (its total demand is bounded by the submit-time
        worst-case feasibility check), so every step advances at least one
        request per replica — no livelock.  (A chaos-flaky allocator can
        still starve a whole pass transiently; the requests requeue and
        the next tick retries.)"""
        C = self.ecfg.chunk_tokens
        for rep in self.replicas:
            sched = rep.scheduler
            for req in sorted(sched.active.values(), key=lambda r: (-r.n_fed, r.rid)):
                if req.slot == -1:
                    continue  # already preempted as someone else's victim
                last_pos = req.n_fed + req.n_feed(C) - 1
                while not sched.ensure_pages(req, last_pos):
                    victim = sched.pick_victim()
                    sched.preempt(victim)
                    self._win_preempts.add(now)
                    if self._trace is not None:
                        self._trace.req_event(victim.rid, "preempt", reason="pages")
                        self._trace.req_phase(victim.rid, "queued", reason="preempt")
                    if victim is req:
                        break

    def _emit_attrib_spans(self, sample: dict, t0: float, t1: float) -> None:
        """Perfetto child spans under ``device_wait``: subdivide the fused
        step's actual device interval proportionally to the measured
        per-layer shares, on the dedicated attribution thread track."""
        from repro.obs.trace import ATTRIB_TID

        tr = self._trace
        span = max(t1 - t0, 0.0)
        acc = t0
        for row in sample["layers"]:
            frac = row["share"] or 0.0
            dt = span * frac
            tr.complete(
                f"layer{row['index']:02d} {row['pair']}", acc, acc + dt,
                tid=ATTRIB_TID, step=sample["step"], share=frac,
                seconds=row["seconds"],
            )
            acc += dt

    def _emit_counter_tracks(self, tr: TraceRecorder) -> None:
        """Per-step Perfetto counter-track samples: pool pressure, slot
        occupancy, windowed throughput, and the monotone fault counters
        (summed over replica shards)."""
        window = 5.0 if self._realtime else 32.0
        tps = self._win_tokens.rate(self._elapsed(), window)
        tr.counter("pages", free=sum(r.allocator.n_free for r in self.replicas))
        tr.counter(
            "slots",
            active=sum(len(r.scheduler.active) for r in self.replicas),
            waiting=sum(len(r.scheduler.waiting) for r in self.replicas)
            + len(self._pending),
        )
        tr.counter("tokens_per_s_window", tokens_per_s=tps or 0.0)
        tr.counter("preemptions_total", preemptions=self.preemptions)
        tr.counter("shed_total", shed=self.registry.counter(
            "repro_requests_total").value(status="shed"))

    def _step_once(self, now_fn: Callable[[], float]) -> None:
        R, S, C = self.dp, self.ecfg.n_slots, self.ecfg.chunk_tokens
        if self.ecfg.admit == "on-demand":
            self._fund_pages(now_fn())
            if not self._any_active():
                return  # everything preempted; admission retries next loop
        tokens = np.zeros((R, S, C), np.int32)
        pos = np.zeros((R, S), np.int32)
        lens = np.zeros((R, S), np.int32)
        for rep, slot, req in self._active_items():
            chunk, start = req.next_chunk(C)
            tokens[rep.index, slot, : len(chunk)] = chunk
            pos[rep.index, slot] = start
            lens[rep.index, slot] = len(chunk)
        args = None  # single-shard batch args (also fed to the attributor)
        if not self._stacked:
            args = [
                self.params,
                self.state,
                jnp.asarray(self.block_table.as_array()),
                jnp.asarray(tokens[0]),
                jnp.asarray(pos[0]),
            ]
            if C > 1:
                args.append(jnp.asarray(lens[0]))

        def dispatch():
            """Run the fused step in this engine's mesh mode; returns the
            logits (``[S, V]`` single-shard, ``[R, S, V]`` on the mesh, a
            list of ``R`` per-replica ``[S, V]`` arrays for dp > 1 with
            mp == 1) and swaps the donated state buffer(s) in place."""
            if self.mp > 1:
                table = np.stack([rep.block_table.as_array() for rep in self.replicas])
                margs = [
                    self._params_arg(), self.state, jnp.asarray(table),
                    jnp.asarray(tokens), jnp.asarray(pos),
                ]
                if C > 1:
                    margs.append(jnp.asarray(lens))
                out, self.state = self._step(*margs)
                return out
            if self.dp > 1:
                # one dispatch of the same compiled executable per replica:
                # bit-identical per-request math to the single-device engine
                rows = []
                for rep in self.replicas:
                    rargs = [
                        self._rep_params[rep.index], self.state[rep.index],
                        jnp.asarray(rep.block_table.as_array()),
                        jnp.asarray(tokens[rep.index]), jnp.asarray(pos[rep.index]),
                    ]
                    if C > 1:
                        rargs.append(jnp.asarray(lens[rep.index]))
                    row, self.state[rep.index] = self._step(*rargs)
                    rows.append(row)
                return rows  # one per replica device; stacked on the host
            out, self.state = self._step(*args)
            return out
        tr = self._trace
        if tr is not None:
            for rep, slot, req in self._active_items():
                if lens[rep.index, slot] and tr.phase(req.rid) == "prefill":
                    tr.req_event(req.rid, "prefill_chunk",
                                 start=int(pos[rep.index, slot]),
                                 n=int(lens[rep.index, slot]))
        attrib_state = None
        if self._attrib is not None and (self.n_steps + 1) % self.ecfg.attrib_every == 0:
            # the fused step donates self.state — copy BEFORE dispatch so the
            # segmented re-execution sees the same pre-step state.  Injected
            # faults raise before state is touched, so the copy stays valid
            # across retries; hard-fault paths return early and drop it.
            # With dp > 1 replica 0's shard is attributed (params are global).
            if self.dp > 1:
                attrib_state = jax.tree.map(jnp.copy, self.state[0])
            else:
                attrib_state = jax.tree.map(jnp.copy, self.state)
        t_span = [0.0, 0.0]  # dispatch start / return (tracing only)
        for attempt in range(self.ecfg.max_step_retries + 1):
            try:
                if self._chaos is not None:
                    self._chaos.before_step()  # raises BEFORE state is touched
                if tr is not None:
                    t_span[0] = tr.now()
                logits = dispatch()
                if tr is not None:
                    t_span[1] = tr.now()
                break
            except InjectedFault:
                self.step_retries += 1
                if tr is not None:
                    tr.instant("step_retry", attempt=attempt)
                if attempt == self.ecfg.max_step_retries:
                    # transient fault outlasted the retry budget: treat it
                    # like an attributable slot fault — replay the lowest-
                    # progress victim, quarantine its slot, step next tick
                    self._strike(self._pick_victim(), now_fn())
                    return
            except Exception as exc:  # hard fault: donated state invalidated
                if tr is not None:
                    tr.instant("hard_fault", exc=type(exc).__name__)
                self._recover_hard_fault(exc, now_fn())
                return
        self.n_steps += 1
        n_active = sum(len(r.scheduler.active) for r in self.replicas)
        self.slot_token_steps += n_active
        self.fed_tokens += int(lens.sum())
        t_wait = None
        if tr is not None:
            # split host dispatch from device wait: block explicitly, then
            # the np.asarray below is a post-sync host copy
            jax.block_until_ready(logits)
            t_wait = tr.now()
            tr.complete("dispatch", t_span[0], t_span[1], step=self.n_steps)
            tr.complete("device_wait", t_span[1], t_wait, step=self.n_steps)
            tr.complete("step", t_span[0], t_wait, step=self.n_steps,
                        active=n_active, fed=int(lens.sum()))
        if attrib_state is not None:
            if self.dp > 1:
                sample = self._attrib.sample(
                    attrib_state, jnp.asarray(self.block_table.as_array()),
                    jnp.asarray(tokens[0]), jnp.asarray(pos[0]),
                    jnp.asarray(lens[0]) if C > 1 else None, step=self.n_steps,
                )
            else:
                sample = self._attrib.sample(
                    attrib_state, args[2], args[3], args[4],
                    args[5] if C > 1 else None, step=self.n_steps,
                )
            if tr is not None:
                self._emit_attrib_spans(sample, t_span[1], t_wait)
        if tr is not None:
            self._emit_counter_tracks(tr)
            if (
                self._trace_path is not None
                and self.ecfg.trace_checkpoint_every > 0
                and self.n_steps % self.ecfg.trace_checkpoint_every == 0
            ):
                # crash-durable partial trace; the final seal overwrites it
                tr.save(self._trace_path)
        logits_np = np.asarray(logits)  # device sync; [S, V] or [R, S, V]
        if logits_np.ndim == 2:
            logits_np = logits_np[None]
        self.last_logits = logits_np
        if self._chaos is not None:
            logits_np = np.array(logits_np)  # writable host copy
            for rep in self.replicas:
                sampling = [
                    s for s, r in rep.scheduler.active.items()
                    if r.n_fed + int(lens[rep.index, s]) >= len(r.seq)
                ]
                self._chaos.poison_logits(logits_np[rep.index], sampling)
        t = now_fn()
        if self._ckpt is not None and self.n_steps % self.ecfg.snapshot_every == 0:
            self._ckpt.save_async(self.n_steps, self.state)
        n_new = 0
        for rep, slot, req in list(self._active_items()):
            req.n_fed += int(lens[rep.index, slot])
            if req.n_fed < len(req.seq):
                continue  # mid-prompt / mid-replay: logits not sampled
            if tr is not None:
                tr.req_phase(req.rid, "decode", slot=slot)
            row = logits_np[rep.index, slot]
            if not np.isfinite(row).all():
                # poisoned (or genuinely non-finite) logits about to be
                # sampled: never emit garbage — quarantine the slot and
                # replay the request token-identically
                self._strike(req, t)
                continue
            nxt = int(np.argmax(row))
            if not req.out_tokens:
                req.t_first_token = t
            req.out_tokens.append(nxt)
            n_new += 1
            if req.done:
                self._finalize(req, "ok", t)
        self._win_steps.add(t)
        if n_new:
            self._win_tokens.add(t, n_new)
        reg = self.registry
        reg.counter("repro_steps_total", "fused engine steps").inc()
        reg.counter("repro_generated_tokens_total", "sampled tokens").inc(n_new)
        reg.counter("repro_fed_tokens_total", "valid token lanes fed").inc(
            float(lens.sum()))

    def _replica_watchdog(self, now: float) -> None:
        """dp > 1 only: a replica with waiting work and an empty batch
        while at least one sibling is live gets quarantined *whole* after
        ``watchdog_ticks`` stalled ticks — its waiting queue re-routes to
        the least-loaded live replica, so a wedged pool shard (flaky
        allocator, poisoned device) degrades capacity instead of wedging
        every request routed to it."""
        if self.dp == 1:
            return
        for rep in self.replicas:
            sched = rep.scheduler
            stalled = bool(sched.waiting) and not sched.active and not rep.quarantined
            rep.idle = rep.idle + 1 if stalled else 0
            if rep.idle <= self.ecfg.watchdog_ticks:
                continue
            others = [o for o in self.replicas if o is not rep and not o.quarantined]
            if not others:
                continue  # nowhere to re-route; the global watchdog sheds
            rep.idle = 0
            rep.quarantined_until = self.ticks + self.ecfg.quarantine_ticks
            self.replica_quarantines += 1
            target = min(
                others,
                key=lambda o: (
                    len(o.scheduler.active) + len(o.scheduler.waiting),
                    o.index,
                ),
            )
            moved = 0
            while sched.waiting:
                req = sched.waiting.popleft()
                req.replica = target.index
                target.scheduler.submit(req)
                moved += 1
            if self._trace is not None:
                self._trace.instant(
                    "replica_quarantine", replica=rep.index,
                    until_tick=rep.quarantined_until, rerouted=moved,
                    target=target.index,
                )

    def run(
        self,
        *,
        realtime: bool = True,
        max_steps: int | None = None,
        trace=None,
    ) -> dict:
        """Drive the engine until every submitted request reaches a
        terminal status.

        ``realtime=False`` uses a deterministic virtual clock (1.0 per
        step — idle ticks also advance it; idle gaps jump straight to the
        next arrival) so tests and A/B comparisons are noise-free.

        ``trace`` arms request/step span recording: pass a
        :class:`~repro.obs.trace.TraceRecorder` to inspect events in
        process, or a path to have the engine write Perfetto-loadable
        Chrome trace JSON there when the run ends.  ``None`` (default)
        keeps every tracing hook a single predicate check.
        """
        self._realtime = realtime
        if trace is not None:
            self._arm_trace(trace)
        t_wall0 = self._t_wall0 = time.monotonic()
        self._t_run_end = None

        idle = 0

        def now() -> float:
            return (time.monotonic() - t_wall0) if realtime else self._vclock

        while self._pending or not self._all_done():
            if max_steps is not None and self.n_steps >= max_steps:
                break
            self.ticks += 1
            for rep in self.replicas:
                rep.scheduler.release_quarantined(self.ticks)
                if rep.quarantined and self.ticks >= rep.quarantined_until:
                    rep.quarantined_until = None
            self._police(now())
            self._admit(now())
            self._replica_watchdog(now())
            if not self._any_active():
                if self._pending:
                    # nothing running: wait for (or jump to) the next arrival
                    nxt = self._pending[0].arrival
                    if realtime:
                        time.sleep(min(max(nxt - now(), 0.0), 0.01))
                    else:
                        self._vclock = max(self._vclock, nxt)
                    idle = 0
                    continue
                if self._all_done():
                    continue  # loop condition exits
                # waiting work but nothing placeable (quarantine drain,
                # flaky allocator, or a genuine stall): idle ticks release
                # quarantines; the watchdog sheds the head deterministically
                # instead of crashing or spinning forever
                idle += 1
                if realtime:
                    time.sleep(0.001)
                else:
                    self._vclock += 1.0
                if idle > self.ecfg.watchdog_ticks:
                    for rep in self.replicas:
                        if rep.scheduler.waiting:
                            victim = rep.scheduler.waiting[0]
                            rep.scheduler.remove_waiting(victim)
                            self._finalize(victim, "shed", now(), reason="watchdog")
                            break
                    idle = 0
                continue
            idle = 0
            t_step0 = time.monotonic()
            self._step_once(now)
            if realtime:
                dt = time.monotonic() - t_step0
                self.registry.histogram(
                    "repro_step_seconds", "fused step wall time"
                ).observe(dt)
                self._step_time_ewma = (
                    dt if self._step_time_ewma is None
                    else 0.8 * self._step_time_ewma + 0.2 * dt
                )
            else:
                self._vclock += 1.0
        drained = not self._pending and self._all_done()
        if drained:
            for rep in self.replicas:
                rep.scheduler.release_quarantined(None)
                rep.quarantined_until = None
            if self._ckpt is not None:
                self._ckpt.wait()
            if self.ecfg.check_invariants:
                self.assert_no_leaks()
        self._t_run_end = time.monotonic() - t_wall0
        out = self.metrics()
        if self._trace is not None:
            self._seal_trace()
        return out

    _realtime = True  # set by run(); _est_service_time default

    # -- reporting ---------------------------------------------------------

    @property
    def preemptions(self) -> int:
        return sum(rep.scheduler.n_preemptions for rep in self.replicas)

    def assert_no_leaks(self) -> None:
        """Page + slot accounting invariant on **every replica shard**:
        each replica's pages are all back on its free list and each slot
        is free (or quarantined) with a cleared block table.  Raises
        AssertionError naming the leaking replica."""
        for rep in self.replicas:
            try:
                rep.allocator.assert_no_leaks()
                rep.scheduler.assert_all_reclaimed()
            except AssertionError as exc:
                raise AssertionError(f"replica {rep.index}: {exc}") from exc

    def _elapsed(self) -> float:
        """Engine-clock time since run() started: the virtual clock, or
        wall time (frozen once the run returns).  0.0 before any run."""
        if not self._realtime:
            return self._vclock
        if self._t_run_end is not None:
            return self._t_run_end
        if self._t_wall0 is None:
            return 0.0
        return time.monotonic() - self._t_wall0

    def metrics(self, wall: float | None = None) -> dict:
        """End-of-run (or so-far) summary.  ``wall`` defaults to the
        engine's own clock, so this is callable mid-run and after
        ``run()`` without the caller supplying elapsed time; passing an
        explicit ``wall`` (the pre-PR-7 signature) still wins."""
        if wall is None:
            wall = self._elapsed()
        done = self.finished
        ok = [r for r in done if r.status == "ok"]
        statuses = Counter(r.status for r in done)
        lat = [r.t_finish - r.arrival for r in ok if r.t_finish is not None]
        ttft = [r.t_first_token - r.arrival for r in done if r.t_first_token is not None]
        gen = sum(len(r.out_tokens) for r in done)
        pct = percentile  # one shared None-never-NaN implementation
        return {
            "engine": self.ecfg.policy,
            "admit": self.ecfg.admit,
            "chunk_tokens": self.ecfg.chunk_tokens,
            "dp": self.dp,
            "mp": self.mp,
            "n_requests": len(done),
            "n_ok": len(ok),
            "statuses": dict(statuses),
            "generated_tokens": gen,
            "generated_tokens_ok": sum(len(r.out_tokens) for r in ok),
            "prompt_tokens": sum(len(r.prompt) for r in done),
            "fed_tokens": self.fed_tokens,
            "preemptions": self.preemptions,
            "quarantines": sum(r.scheduler.n_quarantines for r in self.replicas),
            "replica_quarantines": self.replica_quarantines,
            "step_retries": self.step_retries,
            "hard_recoveries": self.hard_recoveries,
            "injected": self._chaos.counters() if self._chaos is not None
            else {"step": 0, "alloc": 0, "nan": 0},
            "steps": self.n_steps,
            "wall": wall,
            "tokens_per_s": gen / wall if wall > 0 else None,
            "latency_p50": pct(lat, 50),
            "latency_p99": pct(lat, 99),
            "ttft_p50": pct(ttft, 50),
            "ttft_p99": pct(ttft, 99),
            "slot_occupancy": (
                self.slot_token_steps / (self.n_steps * self.ecfg.n_slots * self.dp)
                if self.n_steps
                else 0.0
            ),
        }

    def live_metrics(self, window: float | None = None) -> dict:
        """Trailing-window snapshot, callable mid-run (e.g. between
        ``run(max_steps=k)`` resumptions) — unlike :meth:`metrics`, the
        rates here cover only the *last* ``window`` engine-clock units
        (default 5 s wall / 32 virtual steps)."""
        if window is None:
            window = 5.0 if self._realtime else 32.0
        now = self._elapsed()
        statuses = Counter(r.status for r in self.finished)
        n_active = sum(len(r.scheduler.active) for r in self.replicas)
        n_waiting = sum(len(r.scheduler.waiting) for r in self.replicas)
        return {
            "now": now,
            "window": window,
            "tokens_per_s_window": self._win_tokens.rate(now, window),
            "steps_per_s_window": self._win_steps.rate(now, window),
            "shed_rate_window": self._win_sheds.rate(now, window),
            "preemption_rate_window": self._win_preempts.rate(now, window),
            "queue_depth": len(self._pending) + n_waiting,
            "active_slots": n_active,
            "slot_occupancy": n_active / (self.ecfg.n_slots * self.dp),
            "free_pages": sum(r.allocator.n_free for r in self.replicas),
            "steps": self.n_steps,
            "statuses": dict(statuses),
        }

    def prometheus_text(self) -> str:
        """Prometheus text exposition of the engine registry, with the
        point-in-time gauges refreshed at scrape time."""
        reg = self.registry
        reg.gauge("repro_queue_depth", "pending + waiting requests").set(
            len(self._pending)
            + sum(len(r.scheduler.waiting) for r in self.replicas))
        reg.gauge("repro_active_slots", "slots decoding/prefilling").set(
            sum(len(r.scheduler.active) for r in self.replicas))
        reg.gauge("repro_free_pages", "page-pool headroom").set(
            sum(r.allocator.n_free for r in self.replicas))
        reg.gauge("repro_preemptions", "scheduler preemptions so far").set(
            self.preemptions)
        return reg.prometheus_text()
