"""The continuous-batching scheduler: robustness under load as the
design center.

One scheduler owns a set of resident decode GROUPS — one per (prompt
bucket, lane) — each a fixed-capacity batch driven through
`DecodeEngine`'s serve hooks (models/generate.py).  The loop advances in
SEGMENTS (`segment_steps` decode steps per compiled call) and makes every
robustness decision at the segment boundary, the natural synchronization
point the PR-3 engine already exposes:

  * JOIN — queued requests prefill as a cohort (padded to a power of two,
    so join batches reuse a handful of compiled shape classes) and their
    cache rows splice into free slots of the running batch
    (`merge_cache_rows`).  A short request that finishes frees its slot
    for the next arrival while long rows keep decoding: occupancy
    tracks offered load instead of draining to one.
  * CANCEL — a resident row whose deadline has passed is frozen (its
    `done` mask bit) and its request finished as `timeout`; the engine
    never spends another decode step on work nobody can use.
  * COMPLETE — rows that hit their token budget or stop token are
    harvested and their slots freed.

Overload never reaches this loop: admission (serve/admission.py) sheds at
the front door on queue depth, deadline feasibility, and the
deadline-miss breaker — and when the breaker is open with a quantized
fallback bundle configured, new traffic runs DEGRADED on the int8
weights (quant/) instead of being refused: reduced fidelity beats an
error page.

Every request carries a `serve.request` span; segments and prefills are
span-timed and feed the admission controller's per-bucket EWMAs, so the
feasibility math always reflects the engine as measured, not as hoped.
Deadline math runs on the injectable resilience clock — the whole
scheduler is testable with a `VirtualClock` and zero sleeps by calling
`_tick()` directly (the loop thread, spawned by serve/lifecycle.py, is
just `_tick` + a condition wait).

Groups take TURNS inside a pass: harvest the group's segment in flight,
cancel, join, dispatch its next segment — and the fetch of that segment
waits for the group's next turn, so with two live groups the device
always has the other group's program queued or running while the host
works on this one (`_Flight`).  A whole-prompt join dispatches its
prefill, merge and the group's segment back to back and fetches its first
tokens after them (`_join`), so the device never waits for the splice's
host work.  `_tick()` still ends with every segment it dispatched
harvested; only the loop thread carries them from one pass into the next.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Optional

import jax
import numpy as np

from mmlspark_tpu import config
from mmlspark_tpu.models.generate import (DEFAULT_CACHE_CHUNK, FIXED, WINDOW,
                                          DecodeEngine)
from mmlspark_tpu.observe import compiles
from mmlspark_tpu.observe.logging import get_logger
from mmlspark_tpu.observe.metrics import inc_counter
from mmlspark_tpu.observe.spans import monotonic
from mmlspark_tpu.observe.telemetry import active_run
from mmlspark_tpu.observe.trace import (mint_context, span_on_tracer,
                                        tail_promote, trace_event)
from mmlspark_tpu.resilience.clock import Clock, get_clock
from mmlspark_tpu.serve.admission import (AdmissionController,
                                          InvalidRequest, MissRateBreaker,
                                          Overloaded, StepTimeEstimator)
from mmlspark_tpu.serve.prefix_cache import PrefixCache
from mmlspark_tpu.serve.request import (CANCELLED, HANDOFF, INTERACTIVE,
                                        OK, PRIORITIES, TIMEOUT, Request)

SERVE_QUEUE_CAPACITY = config.register(
    "MMLSPARK_TPU_SERVE_QUEUE_CAPACITY", 64,
    "serving: bounded admission-queue depth; arrivals beyond it shed "
    "with Overloaded (429)", ptype=int)
SERVE_MAX_BATCH = config.register(
    "MMLSPARK_TPU_SERVE_MAX_BATCH", 8,
    "serving: resident decode slots per prompt-bucket group (the "
    "continuous batch width)", ptype=int)
SERVE_SEGMENT_STEPS = config.register(
    "MMLSPARK_TPU_SERVE_SEGMENT_STEPS", 8,
    "serving: decode steps per compiled segment — the join/cancel/"
    "complete boundary cadence", ptype=int)
SERVE_DEFAULT_DEADLINE_S = config.register(
    "MMLSPARK_TPU_SERVE_DEFAULT_DEADLINE_S", 30.0,
    "serving: deadline for requests that do not set one", ptype=float)
SERVE_DRAIN_TIMEOUT_S = config.register(
    "MMLSPARK_TPU_SERVE_DRAIN_TIMEOUT_S", 10.0,
    "serving: graceful-drain budget after SIGTERM/stop — in-flight "
    "requests finish or cancel by min(their deadline, this), then the "
    "loop exits", ptype=float)
SERVE_WARMUP_JOINS = config.register(
    "MMLSPARK_TPU_SERVE_WARMUP_JOINS", False,
    "serving: warmup also pre-compiles every late-join shape class "
    "(cohort merges and terminal segments at each grown cache width) — "
    "slower startup, but a ready engine then NEVER pays XLA against a "
    "deadline; recommended for production fleets", ptype=bool)
SERVE_PREFILL_CHUNK = config.register(
    "MMLSPARK_TPU_SERVE_PREFILL_CHUNK", 0,
    "serving: chunked prefill — join cohorts prefill in chunks of this "
    "many prompt tokens, ONE chunk per scheduler tick, so a long "
    "prompt's forward interleaves with resident decode segments instead "
    "of stalling them (0 = whole-prompt prefill; power of two "
    "recommended — buckets a non-divisor chunk doesn't divide fall back "
    "to whole-prompt)", ptype=int)
SERVE_SPEC_TOKENS = config.register(
    "MMLSPARK_TPU_SERVE_SPEC_TOKENS", 0,
    "serving: speculative decoding — draft-model tokens proposed per "
    "verify round (0 = off; needs a draft_bundle on the ServingEngine). "
    "Greedy outputs stay byte-identical to plain decoding; a round "
    "advances a row by up to this+1 tokens for one target forward",
    ptype=int)
SERVE_ROLE = config.register(
    "MMLSPARK_TPU_SERVE_ROLE", "colocated",
    "serving: this engine's tier in a disaggregated fleet — 'colocated' "
    "(prefill + decode on the same replica, the default), 'prefill' "
    "(runs chunked prefill only, ships finished KV cache rows to a "
    "decode replica over the handoff bus), or 'decode' (receives "
    "handed-off rows and decodes them to completion)", ptype=str)
SERVE_CACHE_DTYPE = config.register(
    "MMLSPARK_TPU_SERVE_CACHE_DTYPE", "model",
    "serving: resident KV-cache dtype — 'model' or 'int8' (per-head "
    "symmetric quantize-on-write; on a disaggregated fleet int8 pages "
    "also halve the handoff wire bytes)", ptype=str)

SERVE_PREFIX_CACHE = config.register(
    "MMLSPARK_TPU_SERVE_PREFIX_CACHE", False,
    "serving: cross-request radix prefix KV cache — finished prefill "
    "rows stay resident at cache_chunk granularity and later requests "
    "sharing a chunk-aligned prompt prefix splice them in, prefilling "
    "only the novel suffix (decode/colocated roles only; greedy outputs "
    "stay byte-identical at model dtype)", ptype=bool)
SERVE_PREFIX_MAX_ROWS = config.register(
    "MMLSPARK_TPU_SERVE_PREFIX_MAX_ROWS", 64,
    "serving: prefix-pool LRU budget in resident CHUNK rows (one row = "
    "one cache_chunk of KV slots); leased rows never evict", ptype=int)
SERVE_PREFIX_MAX_MB = config.register(
    "MMLSPARK_TPU_SERVE_PREFIX_MAX_MB", 256.0,
    "serving: prefix-pool LRU budget in resident megabytes (int8 KV "
    "rows fit ~4x more prefixes per MB than model-dtype)", ptype=float)
SERVE_LANE_BATCH_SHARE = config.register(
    "MMLSPARK_TPU_SERVE_LANE_BATCH_SHARE", 0.5,
    "serving: greatest fraction of the admission queue the BATCH "
    "priority lane may hold; beyond it batch arrivals shed queue_full "
    "while interactive traffic still seats (and a full queue displaces "
    "its newest batch request for an interactive arrival) — overload "
    "costs the batch tier first", ptype=float)

_ROLES = ("colocated", "prefill", "decode")


@dataclasses.dataclass
class ServeConfig:
    """Knobs for one ServingEngine (docs/serving.md 'Knobs').

    None fields fall back to their MMLSPARK_TPU_SERVE_* config vars at
    construction, the TrainerConfig convention."""

    max_new_tokens: int = 32          # engine-wide generation cap
    max_batch: Optional[int] = None   # resident slots per bucket group
    queue_capacity: Optional[int] = None
    segment_steps: Optional[int] = None
    default_deadline_s: Optional[float] = None
    drain_timeout_s: Optional[float] = None
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    stop_tokens: tuple = ()
    cache_chunk: int = DEFAULT_CACHE_CHUNK
    seed: int = 0
    # deadline-miss breaker (serve/admission.py MissRateBreaker)
    miss_window: int = 32
    miss_min_samples: int = 8
    shed_miss_rate: float = 0.5
    breaker_reset_s: float = 5.0
    warmup_buckets: tuple = ()        # () = the engine's smallest bucket
    warmup_joins: Optional[bool] = None  # pre-compile late-join shapes too
    prefill_chunk: Optional[int] = None  # chunked prefill (0 = off)
    spec_tokens: Optional[int] = None    # speculative draft depth (0 = off)
    role: Optional[str] = None           # colocated | prefill | decode
    cache_dtype: Optional[str] = None    # model | int8 resident KV cache
    prefix_cache: Optional[bool] = None  # cross-request prefix KV reuse
    prefix_max_rows: Optional[int] = None   # pool LRU budget, chunk rows
    prefix_max_mb: Optional[float] = None   # pool LRU budget, megabytes
    lane_batch_share: Optional[float] = None  # batch lane's queue share

    def __post_init__(self):
        read = lambda explicit, var, cast: cast(
            var.current() if explicit is None else explicit)
        self.max_batch = read(self.max_batch, SERVE_MAX_BATCH, int)
        self.role = read(self.role, SERVE_ROLE, str)
        self.cache_dtype = read(self.cache_dtype, SERVE_CACHE_DTYPE, str)
        self.queue_capacity = read(self.queue_capacity,
                                   SERVE_QUEUE_CAPACITY, int)
        self.segment_steps = read(self.segment_steps,
                                  SERVE_SEGMENT_STEPS, int)
        self.default_deadline_s = read(self.default_deadline_s,
                                       SERVE_DEFAULT_DEADLINE_S, float)
        self.drain_timeout_s = read(self.drain_timeout_s,
                                    SERVE_DRAIN_TIMEOUT_S, float)
        self.warmup_joins = read(self.warmup_joins,
                                 SERVE_WARMUP_JOINS, bool)
        self.prefill_chunk = read(self.prefill_chunk,
                                  SERVE_PREFILL_CHUNK, int)
        self.spec_tokens = read(self.spec_tokens, SERVE_SPEC_TOKENS, int)
        self.prefix_cache = read(self.prefix_cache,
                                 SERVE_PREFIX_CACHE, bool)
        self.prefix_max_rows = read(self.prefix_max_rows,
                                    SERVE_PREFIX_MAX_ROWS, int)
        self.prefix_max_mb = read(self.prefix_max_mb,
                                  SERVE_PREFIX_MAX_MB, float)
        self.lane_batch_share = read(self.lane_batch_share,
                                     SERVE_LANE_BATCH_SHARE, float)
        if self.prefix_max_rows < 1:
            raise ValueError("prefix_max_rows must be >= 1")
        if self.prefix_max_mb <= 0:
            raise ValueError("prefix_max_mb must be > 0")
        if not 0.0 < self.lane_batch_share <= 1.0:
            raise ValueError(
                f"lane_batch_share must be in (0, 1], "
                f"got {self.lane_batch_share}")
        if self.prefill_chunk < 0:
            raise ValueError("prefill_chunk must be >= 0")
        if self.spec_tokens < 0:
            raise ValueError("spec_tokens must be >= 0")
        if self.role not in _ROLES:
            raise ValueError(f"role must be one of {_ROLES}, "
                             f"got {self.role!r}")
        if self.cache_dtype not in ("model", "int8"):
            raise ValueError(
                f"cache_dtype must be 'model' or 'int8', "
                f"got {self.cache_dtype!r}")
        if self.role != "colocated" and self.spec_tokens:
            # the handoff carries target caches only; speculative lanes
            # would need the draft cache shipped too — out of scope
            raise ValueError(
                "speculative decoding is colocated-only: a "
                f"role={self.role!r} tier cannot run spec_tokens > 0")
        if self.role == "prefill" and self.prefix_cache:
            # disaggregated tiers must not double-cache: the pool lives
            # where decode does (build_fleet keeps it off the prefill
            # tier; its finished rows ship over the handoff bus and the
            # DECODE replica pools them)
            raise ValueError(
                "prefix_cache is decode/colocated-only: a role='prefill' "
                "replica ships finished KV rows over the handoff bus and "
                "must not keep a second resident copy — enable the pool "
                "on the decode tier instead")
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if self.segment_steps < 1:
            raise ValueError("segment_steps must be >= 1")
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")


class _Group:
    """One (bucket, lane)'s resident batch: fixed `capacity` rows, numpy
    row state on the host, caches on the device.  A row is free when
    `rows[i] is None` (its `done` bit stays True so the compiled segment
    freezes it)."""

    def __init__(self, bucket: int, capacity: int):
        self.bucket = bucket
        self.capacity = capacity
        self.rows: list[Optional[Request]] = [None] * capacity
        self.caches = None
        self.draft_caches = None       # speculative lanes only
        self.spec_rounds = 0           # per-group RNG round counter
        self.reserved: set = set()     # slots held by in-flight chunked
        # prefills (their rows stay None until the cohort splices in)
        self.tok = np.zeros(capacity, np.int32)
        self.done = np.ones(capacity, bool)
        self.true_len = np.ones(capacity, np.int32)
        self.budget = np.zeros(capacity, np.int32)
        self.t_row = np.zeros(capacity, np.int32)
        self.row_ids = np.zeros(capacity, np.int32)
        # per-row sampling keys, cached until the row composition changes
        # (recomputing the fold every segment would retrace a vmap per
        # tick for nothing)
        self.keys = None
        self.keys_ids: Optional[tuple] = None

    def free_slots(self) -> list:
        return [i for i, r in enumerate(self.rows)
                if r is None and i not in self.reserved]

    def live_slots(self) -> list:
        return [i for i, r in enumerate(self.rows) if r is not None]

    def release(self, slot: int) -> None:
        self.rows[slot] = None
        self.done[slot] = True
        self.t_row[slot] = 0
        self.budget[slot] = 0
        self.true_len[slot] = 1


@dataclasses.dataclass
class _Flight:
    """A dispatched segment whose results are still on the device: what
    its harvest needs.  The group's host row state (`tok`, `done`,
    `t_row`, ...) is the dispatch's own until then — nothing seats,
    releases or cancels a row of a group in flight."""

    group: _Group
    lane: str
    toks: object                   # device handles of the program
    tok: object
    done: object
    counts: list                   # its device counts (`_take_counts`)
    live: list                     # slots resident at the dispatch
    read: int                      # cache width the segment reads
    dispatched: float              # `monotonic()` before the call
    own_s: Optional[float] = None  # `_ended`, once its end was seen


# engine lifecycle states
CREATED, READY, DRAINING, STOPPED = "created", "ready", "draining", "stopped"

# finished requests whose latency and time to first token feed the
# percentiles of `stats()` (the newest; the counters keep the totals)
_PERCENTILE_SAMPLES = 4096


def _device_bytes(tree) -> int:
    """Bytes of a tree's leaves that live on a device (a host leaf
    counts 0: it would be uploaded by every call that takes it)."""
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree)
               if isinstance(x, jax.Array))


def _assemble_prefix_row(chunks: list) -> list:
    """Concatenate a prefix hit's per-chunk pool payloads back into one
    cache row (slot axis 1), layer by layer — both cache layouts ride
    through (2-tuple model-dtype, 4-tuple int8 with its scale arrays)."""
    import jax.numpy as jnp
    row = []
    for layer_parts in zip(*chunks):
        row.append(tuple(jnp.concatenate(ts, axis=1)
                         for ts in zip(*layer_parts)))
    return row


class ServingEngine:
    """In-process serving over a model bundle (module docstring).

    Inline (tests, benches): construct, `warmup()`, then call `submit` +
    `_tick()` yourself — with an injected `VirtualClock` nothing sleeps.
    Production: `serve/lifecycle.start_engine(engine)` spawns the loop
    thread and wires SIGTERM -> `begin_drain`; `serve/lifecycle.
    start_http` puts the stdlib front end in front of `submit`.
    """

    def __init__(self, bundle, cfg: Optional[ServeConfig] = None, *,
                 degraded_bundle=None, draft_bundle=None,
                 clock: Optional[Clock] = None, mesh=None):
        self.cfg = cfg or ServeConfig()
        self._clock = clock
        self._bundle = bundle
        self._module = bundle.module()
        # weights are placed once (_place_variables): off-mesh on the
        # default device; over a device mesh replicated at mp=1,
        # partition-rule sharded when the mesh has a model axis, and every
        # DecodeEngine program traces its KV hints against it
        if mesh is not None and int(mesh.shape.get("seq", 1)) > 1:
            raise ValueError(
                "ServingEngine does not support a seq-sharded mesh "
                "(seq>1): continuous batching splices and pages "
                "whole-window cache rows, which a seq-partitioned "
                "window breaks up; use DecodeEngine.generate / "
                "TextGenerator for seq-parallel long-context decode")
        self._mesh = mesh
        # speculative lanes: one shared draft (zoo/speculative.py) drafts
        # for every lane — greedy exactness is per-lane by construction,
        # so the quantized degraded lane pairs with the same draft
        if self.cfg.spec_tokens and draft_bundle is None:
            raise ValueError(
                "spec_tokens > 0 needs a draft_bundle "
                "(zoo.truncated_draft_bundle builds one)")
        self._draft_module = (draft_bundle.module()
                              if self.cfg.spec_tokens else None)
        # telemetry handles captured ONCE, on the constructing thread
        # (the loop thread never sees the caller's contextvars)
        self._run = active_run()
        self._tracer = self._run.tracer if self._run is not None else None
        self._engines = {"primary": self._decode_engine(self._module)}
        if FIXED in self._engines["primary"].state_kinds:
            # a fixed per-row state cannot be cut at a prompt prefix or
            # paged by window chunk: what rests on either refuses here
            name = type(self._module).__name__
            if self.cfg.prefix_cache:
                raise ValueError(
                    f"the prefix cache (and resume) is not supported for "
                    f"{name}: its rows hold a fixed state beside the K/V "
                    "window, and the pool keeps window chunks only")
            if self.cfg.role != "colocated":
                raise ValueError(
                    f"tiered roles and KV handoff are not supported for "
                    f"{name} (role={self.cfg.role!r}): the handoff pages "
                    "window chunks only")
        self._cast_bytes = 0           # the gauge `weights_cast_bytes`
        # set-up's gauges (`stats()`): seconds of `setup.place_weights`
        # and `setup.warmup`, the `warmup_program` events, and the host's
        # clock at `ready` (a row of the compile ledger that ends later
        # is a program compiled in flight: `compiles_after_ready`)
        self._place_s = 0.0
        self._warmup_s = 0.0
        self._warmup_programs = 0
        self._ready_at: Optional[float] = None
        self._draft_vars = (self._place_replicated(draft_bundle)
                            if self.cfg.spec_tokens else None)
        self._variables = {"primary": self._place_variables(bundle)}
        if degraded_bundle is not None:
            deg = degraded_bundle.module()
            if deg.vocab_size != self._module.vocab_size:
                raise ValueError(
                    "degraded bundle must share the primary vocabulary")
            self._engines["degraded"] = self._decode_engine(deg)
            self._variables["degraded"] = self._place_variables(
                degraded_bundle, "degraded")
        self.estimator = StepTimeEstimator()
        self.breaker = MissRateBreaker(
            "serve", window=self.cfg.miss_window,
            min_samples=self.cfg.miss_min_samples,
            miss_rate=self.cfg.shed_miss_rate,
            reset_s=self.cfg.breaker_reset_s, clock=clock)
        self.admission = AdmissionController(
            self.cfg.queue_capacity, self.estimator, self.breaker,
            max_batch=self.cfg.max_batch,
            degraded_available=degraded_bundle is not None,
            batch_share=self.cfg.lane_batch_share, clock=clock)
        # cross-request prefix pool: primary-lane rows only (degraded
        # lanes decode different weights — their caches never mix)
        self._prefix = (PrefixCache(
            self.cfg.cache_chunk, max_rows=self.cfg.prefix_max_rows,
            max_bytes=int(self.cfg.prefix_max_mb * 2 ** 20))
            if self.cfg.prefix_cache else None)
        self._groups: dict[tuple, _Group] = {}
        # in-flight chunked prefills: one advances a single chunk per
        # tick, in its group's turn, between the joins and the segment
        self._pending: list[dict] = []
        # segments dispatched and not yet harvested, in dispatch order
        # (the device's own order), and when the last program seen to
        # end did: a program's own time starts there (`_ended`)
        self._flights: list[_Flight] = []
        self._device_free = 0.0
        self.role = self.cfg.role
        # prefill tier: the handoff bus (serve/handoff.py) wires this to
        # receive each finished cohort's (reqs, first tokens, caches)
        # instead of seating them locally; the engine finishes the
        # exported requests with status `handoff`
        self.handoff_export = None
        self._state = CREATED
        self._state_lock = threading.Lock()
        self._wake = threading.Condition()
        self._next_id = 0
        self._id_lock = threading.Lock()
        # the newest samples only: `stats()` re-reads them on every call
        self._latencies: collections.deque = collections.deque(
            maxlen=_PERCENTILE_SAMPLES)
        self._ttfts: collections.deque = collections.deque(
            maxlen=_PERCENTILE_SAMPLES)
        self._counts: dict[str, float] = {}
        self._counts_lock = threading.Lock()
        self._drain_deadline: Optional[float] = None
        self._thread = None            # set by lifecycle.start_engine
        self._guard = None             # PreemptionGuard, set by lifecycle
        self._base_key = jax.random.key(self.cfg.seed)
        # jitted so repeated folds (every join) don't re-trace the vmap;
        # compiled once per cohort size
        self._fold_keys = jax.jit(jax.vmap(
            lambda i: jax.random.fold_in(self._base_key, i)))
        self._stops = np.asarray(self.cfg.stop_tokens or (), np.int32)

    def _decode_engine(self, module) -> DecodeEngine:
        return DecodeEngine(
            module, self.cfg.max_new_tokens,
            temperature=self.cfg.temperature, top_k=self.cfg.top_k,
            top_p=self.cfg.top_p, stop_tokens=self.cfg.stop_tokens,
            chunk=self.cfg.cache_chunk, mesh=self._mesh,
            cache_dtype=self.cfg.cache_dtype,
            prefill_chunk=self.cfg.prefill_chunk or None,
            draft_module=self._draft_module,
            spec_tokens=self.cfg.spec_tokens)

    def _place_variables(self, bundle, lane: str = "primary", *,
                         replicate_only: bool = False):
        """A lane's weights are placed ONCE, here (`bridge.place_weights`):
        off-mesh on the default device, under a mesh replicated (dp-only)
        or partition-rule sharded (mp >= 2; the bundle's own rules, else
        DEFAULT_RULES).  What is placed is the lane's RESIDENT tree
        (`DecodeEngine.resident_variables`): the leaves its programs read
        only through a cast to the compute dtype are held in that dtype,
        so no call casts them again.  Every jitted call is handed the
        placed tree, so none uploads it again; it stays resident until
        the engine stops."""
        from mmlspark_tpu.parallel.bridge import place_weights
        eng = self._engines["primary" if lane == "draft" else lane]
        with compiles.setup_phase("place_weights", lane=lane) as phase:
            placed = jax.block_until_ready(place_weights(
                eng.resident_variables(bundle.variables,
                                       draft=lane == "draft"),
                self._mesh, bundle.partition_rules(),
                replicate_only=replicate_only))
            nbytes = _device_bytes(placed)
            if phase.span is not None:
                phase.span.attrs["bytes"] = nbytes
        cast = sum(
            int(got.nbytes) for got, src in zip(
                jax.tree_util.tree_leaves(placed),
                jax.tree_util.tree_leaves(bundle.variables))
            if got.dtype != src.dtype)
        self._cast_bytes += cast
        self._place_s += phase.seconds
        self._record_serve({"event": "weights_placed", "lane": lane,
                            "bytes": nbytes,
                            "cast_bytes": cast,
                            "seconds": round(phase.seconds, 3)})
        return placed

    def _place_replicated(self, bundle):
        """Draft weights, placed once: on the default device off-mesh,
        whole on every device of any mesh (the draft is small; its cache
        rides the data axis only — parallel/partition.py
        DRAFT_KV_CACHE_SPEC)."""
        return self._place_variables(bundle, "draft", replicate_only=True)

    # -- lifecycle ---------------------------------------------------------
    def now(self) -> float:
        return (self._clock or get_clock()).monotonic()

    @property
    def state(self) -> str:
        return self._state

    @property
    def ready(self) -> bool:
        return self._state == READY

    @property
    def alive(self) -> bool:
        return self._state in (READY, DRAINING)

    def warmup(self) -> "ServingEngine":
        """Pre-compile the serving shape classes BEFORE readiness flips:
        cohort prefills (each power-of-two join width up to capacity) and
        one resident segment per warmup bucket.  A first real request
        must never pay an XLA compile against its deadline."""
        if self._state != CREATED:
            return self
        engine = self._engines["primary"]
        buckets = tuple(self.cfg.warmup_buckets) or (engine.bucket_for(1),)
        buckets = list(map(int, buckets))
        with compiles.setup_phase("warmup", buckets=buckets) as phase:
            for lane, eng in self._engines.items():
                variables = self._variables[lane]
                for bucket in buckets:
                    self._warm_bucket(eng, variables, bucket)
        self._warmup_s = phase.seconds
        self._record_serve({"event": "warmup_done", "buckets": buckets,
                            "seconds": round(phase.seconds, 3),
                            "programs": int(phase.programs),
                            "cache_hits": int(phase.cache_hits),
                            "cache_misses": int(phase.cache_misses)})
        self._ready_at = time.perf_counter()
        self._state = READY
        self._record_serve({"event": "ready"})
        get_logger("serve").info(
            "serving engine ready: buckets %s warmed in %.2fs (%d programs, "
            "%d from the compile cache)", buckets, phase.seconds,
            phase.programs, phase.cache_hits)
        return self

    @contextlib.contextmanager
    def _warm_program(self, kind: str, bucket: int, width: int = 0,
                      window: int = 0):
        """One program class of the warm-up: the phase
        `setup.warm_program` round its call (the trace, the lowering and
        the compile or the load happen inside it), closed with a
        `warmup_program` event on the serve timeline.  `width` is the
        join width (a merge's `k`), `window` the cache width."""
        with compiles.setup_phase("warm_program", kind=kind, bucket=bucket,
                                  width=width, window=window) as phase:
            yield
        self._warmup_programs += 1
        self._record_serve({"event": "warmup_program", "kind": kind,
                            "bucket": bucket, "width": width,
                            "window": window,
                            "seconds": round(phase.seconds, 3),
                            "compiled": int(phase.programs),
                            "cache_hits": int(phase.cache_hits)})

    def _warm_bucket(self, eng: DecodeEngine, variables, bucket: int) -> None:
        """Compile every shape class a full-budget batch in this bucket
        can touch: cohort prefills at each power-of-two join width, then
        a dummy capacity batch driven through the whole segment/window
        ladder — so a ready engine never pays XLA against a deadline.

        With `warmup_joins` the sweep also covers what the ladder alone
        cannot: the cohort-merge program at EVERY grown cache width the
        batch passes through (a late join splices a fresh base-width
        cohort into an old, wide batch) and the terminal segment class
        where the cache has already reached its final width — the
        shapes an engine otherwise compiles mid-flight, against a live
        request's deadline, the first time a join lands late."""
        cap = self.cfg.max_batch
        seg = self.cfg.segment_steps
        cohorts, firsts = {}, {}
        chunks = eng.serve_prefill_chunks(bucket)
        n = 1
        while True:
            m = min(n, cap)
            prompts = np.zeros((m, bucket), np.int32)
            live = np.ones(m, bool)
            tl = np.ones(m, np.int32)
            keys = self._row_keys(np.arange(m))
            if chunks:
                # the chunked programs are what this bucket runs live
                with self._warm_program("prefill_chunk", bucket, m):
                    state = None
                    for ci in range(chunks):
                        state = eng.serve_prefill_chunk(
                            variables, prompts, tl, ci, state)
                    tok, done, caches = eng.serve_prefill_finish(
                        state, live, keys)
            else:
                with self._warm_program("prefill", bucket, m):
                    tok, done, caches = eng.serve_prefill(
                        variables, prompts, tl, live, keys)
            if eng.spec_tokens:
                with self._warm_program("draft_prefill", bucket, m):
                    dcaches = eng.serve_draft_prefill(self._draft_vars,
                                                      prompts)
            cohorts[m] = caches
            firsts[m] = tok, done
            if n >= cap:
                break
            n *= 2
        if self.role == "prefill":
            # a prefill-tier engine never decodes or merges: the cohort
            # prefill programs above are its whole compiled surface
            return
        if not eng.spec_tokens and not chunks:
            # a queued join's segment starts from its cohort's first
            # tokens on the device (`_join`): one program a cohort width
            for m, (first, first_done) in firsts.items():
                with self._warm_program("seat", bucket, m):
                    eng.serve_seat(np.zeros(cap, np.int32),
                                   np.ones(cap, bool), first, first_done,
                                   list(range(m)))
        warmed_widths: set = set()

        def warm_joins(resident) -> None:
            # one merge program per (resident width, cohort width, join
            # count): splice k rows from the power-of-two cohort that a
            # k-wide join would prefill (engine._join pads the same way)
            width = eng.state_window(resident)
            if not self.cfg.warmup_joins or width in warmed_widths:
                return
            warmed_widths.add(width)
            for k in range(1, cap + 1):
                m = 1
                while m < k:
                    m *= 2
                with self._warm_program("merge", bucket, k, width):
                    DecodeEngine.merge_cache_rows(
                        resident, cohorts[min(m, cap)],
                        list(range(k)), list(range(k)), mesh=eng.mesh,
                        kinds=eng.state_kinds)

        budget = np.full(cap, self.cfg.max_new_tokens, np.int32)
        t_row = np.zeros(cap, np.int32)
        t = 0
        warm_joins(caches)
        if eng.spec_tokens:
            # speculative lanes replace segments with draft-verify
            # rounds: sweep the same window ladder at full-acceptance
            # stride, then pin the steady (window -> window) class that
            # partial acceptance revisits
            k1 = eng.spec_tokens + 1
            rounds = 0
            while t < self.cfg.max_new_tokens + k1:
                tr = np.minimum(t_row + t, self.cfg.max_new_tokens - 1)
                window = eng.serve_window(bucket, int(tr.max()), k1)
                with self._warm_program("spec_round", bucket, k1, window):
                    (caches, dcaches, _, _, tok, done,
                     _) = eng.serve_spec_round(
                        variables, self._draft_vars, caches, dcaches, tok,
                        done, tl, budget, bucket, tr, rounds, keys, window)
                t += k1
                rounds += 1
            return
        # as a live group's state comes to be: a segment consumes the
        # state it steps on, and the cohorts are spliced again below
        with self._warm_program("merge", bucket, cap,
                                eng.state_window(caches)):
            caches = DecodeEngine.merge_cache_rows(
                eng.empty_state(cap, bucket), caches, list(range(cap)),
                list(range(cap)), mesh=eng.mesh, kinds=eng.state_kinds)
        while t < self.cfg.max_new_tokens:
            window = eng.serve_window(bucket, t, seg)
            with self._warm_program("segment", bucket, seg, window):
                caches, _, tok, done = eng.serve_step(
                    variables, caches, tok, done, tl, budget, bucket,
                    t_row, keys, seg, window)
            t += seg
            t_row = t_row + seg
            warm_joins(caches)
        if self.cfg.warmup_joins:
            # terminal class: the widest window a live row can demand
            # (t_row = max_new - 1), entered with the cache already at
            # that width — the ladder stops one segment short of it
            final = eng.serve_window(bucket, self.cfg.max_new_tokens - 1,
                                     seg)
            for _ in range(2):  # (last-ladder-width -> final), then the
                # steady state (final -> final); re-runs are cache hits
                with self._warm_program("segment", bucket, seg, final):
                    caches, _, tok, done = eng.serve_step(
                        variables, caches, tok, done, tl, budget, bucket,
                        t_row, keys, seg, final)
                warm_joins(caches)

    def begin_drain(self, reason: str = "stop") -> None:
        """Stop admitting; in-flight requests finish or cancel by
        min(their deadline, now + drain_timeout); then the loop exits.
        Idempotent; safe from any thread (SIGTERM handler included)."""
        with self._state_lock:
            if self._state not in (CREATED, READY):
                return
            self._state = DRAINING
            self._drain_deadline = self.now() + self.cfg.drain_timeout_s
        self.admission.close(self.cfg.drain_timeout_s)
        inc_counter("serve.drains")
        trace_event("serve.drain_start", cat="serve", reason=reason)
        self._record_serve({"event": "drain_start", "reason": reason,
                            "in_flight": self.in_flight(),
                            "queued": self.admission.pending()})
        get_logger("serve").warning(
            "serving engine draining (%s): %d in flight, %d queued",
            reason, self.in_flight(), self.admission.pending())
        with self._wake:
            self._wake.notify_all()

    def _finish_drain(self) -> None:
        self._state = STOPPED
        # the placed weights die with the engine, not with whoever still
        # holds the stopped object (a thread, a closure, the HTTP server)
        self._variables = {}
        self._draft_vars = None
        self._cast_bytes = 0
        trace_event("serve.drain_end", cat="serve")
        self._record_serve({"event": "drain_end",
                            "counts": dict(self._counts)})
        self._gauge_stats()
        with self._wake:
            self._wake.notify_all()

    def stop(self, timeout: Optional[float] = None) -> None:
        """Graceful stop: drain, then join the loop thread (if any)."""
        self.begin_drain("stop")
        if self._thread is not None:
            self._thread.join(timeout if timeout is not None
                              else self.cfg.drain_timeout_s + 5.0)
        else:
            # inline engines drain synchronously (each tick makes
            # progress: joins, decode, or the drain-deadline cancel)
            while self._state == DRAINING:
                if self._drained():
                    self._finish_drain()
                    break
                self._tick()

    # -- submission --------------------------------------------------------
    def _new_id(self) -> int:
        with self._id_lock:
            self._next_id += 1
            return self._next_id

    def _validate(self, prompt, max_new_tokens: int) -> np.ndarray:
        try:
            arr = np.asarray(prompt, np.int32)
        except (TypeError, ValueError) as e:
            raise InvalidRequest(f"prompt is not a token array: {e}") from e
        if arr.ndim != 1 or arr.size < 1:
            raise InvalidRequest(
                f"prompt must be a non-empty 1-D token array, got shape "
                f"{arr.shape}")
        if arr.min() < 0 or arr.max() >= self._module.vocab_size:
            raise InvalidRequest(
                f"prompt tokens outside the vocabulary "
                f"[0, {self._module.vocab_size})")
        if not 1 <= int(max_new_tokens) <= self.cfg.max_new_tokens:
            raise InvalidRequest(
                f"max_new_tokens must be in [1, {self.cfg.max_new_tokens}],"
                f" got {max_new_tokens}")
        return arr

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               deadline_s: Optional[float] = None,
               priority: Optional[str] = None, trace=None) -> Request:
        """Admit one request or raise (`InvalidRequest` for poison,
        `Overloaded` when shed).  `priority` picks the admission lane
        ('interactive', the default, or 'batch' — weighted shedding
        costs the batch lane first under overload).  `trace` is an
        upstream TraceContext (the router's per-attempt child); a bare
        engine mints its own root and records the waterfall's `admit`
        event itself.  Returns the live `Request`; callers block on
        `request.wait()` or poll `request.finished`."""
        if not self.alive:
            self._count("shed_draining")
            self._count("shed")
            self._record_serve({"event": "shed", "reason": "draining"})
            raise Overloaded("draining", self.retry_after_s(),
                             f"engine is {self._state}")
        pri = str(priority) if priority is not None else INTERACTIVE
        if pri not in PRIORITIES:
            raise InvalidRequest(
                f"priority must be one of {PRIORITIES}, got {priority!r}")
        n_new = int(max_new_tokens if max_new_tokens is not None
                    else self.cfg.max_new_tokens)
        arr = self._validate(prompt, n_new)
        try:
            bucket = self._engines["primary"].bucket_for(arr.size)
        except ValueError as e:
            inc_counter("serve.poison")
            raise InvalidRequest(str(e)) from e
        now = self.now()
        deadline = now + (float(deadline_s) if deadline_s is not None
                          else self.cfg.default_deadline_s)
        req = Request(self._new_id(), arr, bucket, n_new, now, deadline,
                      priority=pri)
        try:
            self.admission.try_admit(req, self.in_flight_tokens())
        except Overloaded as e:
            self._count(f"shed_{e.reason}")
            self._count("shed")
            self._record_serve({"event": "shed", "reason": e.reason,
                               "request": req.id, "priority": pri})
            raise
        finally:
            # a full queue seats an interactive arrival by displacing
            # its newest queued BATCH request: finish the displaced ones
            # here, WITHOUT feeding the miss breaker (displacement is
            # weighted-shedding policy, not a deadline pathology)
            for d in self.admission.drain_displaced():
                d.finish(CANCELLED, now,
                         "displaced by interactive arrival")
                self._count("displaced")
                self._count("shed")
                self._record_serve({
                    "event": "shed", "reason": "displaced",
                    "request": d.id,
                    "priority": getattr(d, "priority", INTERACTIVE)})
        self._count("admitted")
        if req.degraded:
            self._count("degraded")
            self._record_serve({"event": "degraded", "request": req.id})
        if trace is not None:
            req.trace = trace
        else:
            # no router tier above: this engine IS the front door, so it
            # mints the root context and records the waterfall's `admit`
            req.trace = mint_context()
            if req.trace is not None:
                self._record_serve({"event": "admit", "request": req.id,
                                    "priority": pri, "bucket": bucket,
                                    "trace": req.trace.trace_id,
                                    "sampled": req.trace.sampled})
        if self._tracer is not None:
            req.span = self._tracer.span(
                "serve.request", cat="serve", request=req.id,
                bucket=bucket, prompt_len=arr.size, new_tokens=n_new,
                deadline_in_s=round(deadline - now, 4),
                **self._trace_fields(req))
        with self._wake:
            self._wake.notify_all()
        return req

    # -- accounting --------------------------------------------------------
    def _count(self, name: str, n: float = 1) -> None:
        self._count_all({name: n})

    def _count_all(self, deltas: dict) -> None:
        # front-end threads (submit) and the loop thread both count;
        # the lock keeps read-modify-write updates from losing increments
        with self._counts_lock:
            for name, n in deltas.items():
                self._counts[name] = self._counts.get(name, 0) + n

    @staticmethod
    def _take_counts(eng: DecodeEngine) -> list:
        """The device counts of the program `eng` dispatched last, taken
        off its side channel: the next program of that engine overwrites
        `counts_out`, so whoever will fetch a program owns its counts
        from the dispatch on."""
        counts, eng.counts_out = eng.counts_out, []
        return counts

    def _ended(self, dispatched: float) -> float:
        """A program's results are ready: its own seconds, from the later
        of its dispatch and the end of the program before it (the device
        runs them in dispatch order, and so must the calls here)."""
        now = monotonic()
        own = now - max(dispatched, self._device_free)
        self._device_free = now
        return own

    def _fetch(self, eng: DecodeEngine, *arrays, counts: list,
               dispatched: float, flight: Optional[_Flight] = None,
               after: Optional[_Flight] = None) -> tuple:
        """Bring a program's results to the host: where the scheduler
        thread waits for the device.  The enclosing prefill span's self
        time is then dispatch and argument upload, and this span
        (`fetch_wait_s`) the wait.  What the program counted on the
        device (`counts`, its own from `_take_counts`, under
        `eng.count_names`; nothing for a model that counts nothing) comes
        in the same fetch and goes to the engine's counters.

        Returns (host arrays, the program's own seconds).  A segment
        still in flight that was dispatched before this program ends
        before it: its end is stamped first, so each program's time is
        its own and not the queue's before it.  Those are the segments
        in flight ahead of `flight` (the segment being harvested) or of
        `after` (the first one dispatched after this program: a queued
        join's, which the fetch does not wait for); with neither, every
        segment in flight."""
        t0 = monotonic()
        with span_on_tracer(self._tracer, "serve.fetch", cat="serve"):
            for f in self._flights:
                if f is flight or f is after:
                    break
                if f.own_s is None:
                    jax.block_until_ready(f.done)
                    f.own_s = self._ended(f.dispatched)
            out = [np.asarray(a) for a in arrays]
            counted = [np.asarray(a) for a in counts]
        if flight is None or flight.own_s is None:
            own = self._ended(dispatched)
        else:
            own = flight.own_s
        self._count("fetch_wait_s", monotonic() - t0)
        for values in counted:
            self._count_all(dict(zip(eng.count_names, values.tolist())))
        return out, own

    def _record_serve(self, event: dict) -> None:
        if self._run is not None:
            self._run.record_serve(event)

    @staticmethod
    def _trace_fields(req: Request) -> dict:
        """The trace join fields a serve event/span carries (empty for an
        untraced request) — observe/assemble.py groups on `trace`."""
        t = getattr(req, "trace", None)
        return {"trace": t.trace_id, "sampled": t.sampled,
                "attempt": t.attempt} if t is not None else {}

    def _record_prefix(self, event: dict) -> None:
        if self._run is not None:
            self._run.record_prefix(event)

    def _gauge_prefix(self) -> None:
        # mmlspark_tpu_prefix_{hit_rate,resident_rows,resident_bytes,
        # evictions} on the Prometheus surface (observe/export.py)
        if self._run is None or self._prefix is None:
            return
        s = self._prefix.stats()
        self._run.gauge("prefix.hit_rate", round(s["hit_rate"], 4))
        self._run.gauge("prefix.resident_rows", s["resident_rows"])
        self._run.gauge("prefix.resident_bytes", s["resident_bytes"])
        self._run.gauge("prefix.evictions", s["evictions"])

    def retry_after_s(self) -> float:
        """The live backoff hint for refused/cancelled traffic: remaining
        drain time while draining (a replacement process is that far
        away), the configured drain budget once stopped, and the
        breaker's own cooldown otherwise — never a bare constant."""
        now = self.now()
        if self._state == DRAINING and self._drain_deadline is not None:
            return max(0.1, self._drain_deadline - now)
        if self._state == STOPPED:
            return max(0.1, self.cfg.drain_timeout_s)
        return max(0.1, self.breaker.retry_in_s())

    def cancel_request(self, req: Request, detail: str = "cancelled") -> bool:
        """Withdraw one unfinished request — resident row or still queued
        — WITHOUT feeding the miss breaker (the router cancelling a
        losing hedge attempt is scheduling, not engine failure).  True
        when the request was found and cancelled."""
        if req.finished:
            return False
        for g in list(self._groups.values()):
            for i in g.live_slots():
                if g.rows[i] is req:
                    req.finish(CANCELLED, self.now(), detail)
                    if not self._in_flight(g):
                        g.release(i)
                    # else the row state is the segment's in flight: its
                    # harvest frees the slot of a finished request
                    self._count("cancelled_external")
                    return True
        for job in list(self._pending):
            if req in job["reqs"]:
                # its cohort row keeps prefilling (static shapes) but the
                # finish-time expiry filter drops it before the splice
                req.finish(CANCELLED, self.now(), detail)
                self._count("cancelled_external")
                return True
        if self.admission.remove(req):
            req.finish(CANCELLED, self.now(), detail)
            self._count("cancelled_external")
            return True
        return False

    def in_flight(self) -> int:
        # list() the dict: submit threads read while the loop thread
        # adds/drops groups (iterating the live dict would race);
        # chunked-prefill cohorts count too — they hold reserved slots
        return (sum(len(g.live_slots())
                    for g in list(self._groups.values()))
                + sum(len(job["reqs"]) for job in list(self._pending)))

    def in_flight_tokens(self) -> int:
        total = 0
        for g in list(self._groups.values()):
            for i in g.live_slots():
                req = g.rows[i]
                if req is not None:
                    total += max(0, req.max_new_tokens - len(req.tokens))
        for job in list(self._pending):
            for req in job["reqs"]:
                total += req.max_new_tokens
        return total

    def _row_keys(self, ids) -> jax.Array:
        return self._fold_keys(np.asarray(ids, np.int32))

    def _group_keys(self, g: _Group) -> jax.Array:
        ids = tuple(int(x) for x in g.row_ids)
        if g.keys_ids != ids:
            g.keys = self._row_keys(g.row_ids)
            g.keys_ids = ids
        return g.keys

    def _complete(self, req: Request, status: str, detail: str = "") -> None:
        now = self.now()
        req.finish(status, now, detail)
        missed = status != OK or now > req.deadline
        self.breaker.record(missed)
        # the per-request terminal record the strict-priority drill
        # asserts lane outcomes against (zero interactive misses while
        # batch sheds); gated so the no-telemetry hot path never builds
        # the dict
        if self._run is not None:
            rec = {
                "event": "finish", "request": req.id, "status": status,
                "priority": getattr(req, "priority", INTERACTIVE),
                "deadline_miss": bool(missed),
                "latency_s": round(now - req.arrival, 6),
                **self._trace_fields(req)}
            # tail-based sampling: a head-unsampled attempt that finished
            # badly or slow is promoted to full waterfall detail
            tail = tail_promote(getattr(req, "trace", None), status=status,
                                latency_s=now - req.arrival)
            if tail:
                rec["tail"] = tail
            self._record_serve(rec)
        self._count("finished")
        self._count(status)
        if status == OK:
            self._latencies.append(now - req.arrival)
            self._count("tokens_served", len(req.tokens))
            if now > req.deadline:
                self._count("deadline_miss")
                inc_counter("serve.deadline_miss")
                trace_event("serve.deadline_miss", cat="serve",
                            request=req.id,
                            late_s=round(now - req.deadline, 4))
            else:
                self._count("met_deadline")
                self._count("goodput_tokens", len(req.tokens))
        elif status == TIMEOUT:
            self._count("deadline_miss")
            inc_counter("serve.timeouts")
        inc_counter(f"serve.{status}")

    # -- the scheduler pass ------------------------------------------------
    def _tick(self, carry: bool = False) -> bool:
        """One scheduler pass: expire, then each group's turn (harvest,
        cancel, join, dispatch its next segment).  Returns True when any
        work was done (the loop idles on False).  When it returns, every
        segment it dispatched is harvested — synchronous and sleep-free:
        tests drive it directly under a VirtualClock — unless `carry`
        (the loop thread's own): the segments then stay in flight into
        the next pass, each group's until its next turn, so the device
        works on one group while this thread works on the other."""
        t0 = monotonic()
        # profiler-only (no tracer handle), like `serve.idle_wait`: an
        # idle engine passes here a hundred times a second, which would
        # scroll the run's ring of records
        with span_on_tracer(None, "serve.tick"):
            worked = self._pass()
            if not carry:
                self._harvest_all()
        self._count("tick_s", monotonic() - t0)
        return worked

    def _pass(self) -> bool:
        """The pass itself; `_tick` times it."""
        if (self._guard is not None and self._guard.triggered
                and self._state == READY):
            # SIGTERM arrived (PreemptionGuard flag): drain, never die
            # mid-decode — checked here as well as in the loop so inline
            # (threadless) engines honor the signal too
            self.begin_drain("sigterm")
        now = self.now()
        worked = False
        # 1. expire queued requests whose deadline already passed
        for req in self.admission.drop_expired(now):
            self._complete(req, TIMEOUT, "expired in queue")
            worked = True
        # 2. drain-deadline enforcement: past it, cancel everything left
        # (what the segments in flight produced still reaches its rows)
        if self._state == DRAINING and now >= (self._drain_deadline or 0):
            worked = self._harvest_all() or worked
            for g in self._groups.values():
                for i in g.live_slots():
                    self._complete(g.rows[i], CANCELLED,
                                   "drain timeout")
                    g.release(i)
                    worked = True
            for job in self._pending:
                for req in job["reqs"]:
                    self._complete(req, CANCELLED, "drain timeout")
                    worked = True
                self._release_job_lease(job)
            self._pending.clear()
            for req in self.admission.drop_expired(float("inf")):
                self._complete(req, CANCELLED, "drain timeout")
                worked = True
            self._groups.clear()
            return worked
        # 3. a group for every (bucket, lane) with queued work
        queued = self.admission.queued_buckets()
        for bucket, lane in queued:
            if (bucket, lane) not in self._groups:
                self._groups[(bucket, lane)] = _Group(
                    bucket, self.cfg.max_batch)
        # 4. the groups take turns
        for (bucket, lane), g in list(self._groups.items()):
            if self._turn(g, lane, now, (bucket, lane) in queued):
                worked = True
            if not (g.live_slots() or g.reserved
                    or self.admission.pending()):
                # empty group with no queued work: drop the cache memory
                del self._groups[(bucket, lane)]
        return worked

    def _turn(self, g: _Group, lane: str, now: float, queued: bool) -> bool:
        """One group's turn: harvest its segment in flight, cancel its
        expired rows, join queued work into its free slots, run one chunk
        of each of its chunked prefills, dispatch its next segment (a
        speculative lane: one whole round).  The harvest comes first:
        every later step writes the row state that it overwrites.  True
        when anything was done."""
        worked = self._harvest(g)
        # cancel expired resident rows at the boundary
        for i in g.live_slots():
            req = g.rows[i]
            if req.deadline <= now:
                self._complete(req, TIMEOUT, "cancelled at boundary")
                trace_event("serve.cancel", cat="serve",
                            request=req.id, at_step=int(g.t_row[i]))
                g.release(i)
                worked = True
        # joins: pull queued work into free slots
        free = g.free_slots() if queued else []
        reqs = self._take(g.bucket, len(free), lane) if free else []
        if reqs:
            slots = free[:len(reqs)]
            if self._prefix is not None and lane == "primary":
                # peel prefix-pool hits off the cohort: each resumes
                # from its donor rows (only the novel suffix
                # prefills); misses keep the normal cohort path
                reqs, slots = self._join_prefix_hits(g, lane, reqs, slots)
            if reqs:
                if self._engines[lane].serve_prefill_chunks(g.bucket):
                    self._start_chunked_join(g, lane, reqs, slots)
                else:
                    self._join(g, lane, reqs, slots)
            worked = True
        # advance each of its in-flight chunked prefills by ONE chunk —
        # the point of chunking: the long forward yields to the segment
        # between chunks instead of holding the tick for the whole prompt
        for job in [j for j in self._pending if j["group"] is g]:
            self._advance_prefill(job)
            worked = True
        # a queued join has dispatched the group's segment already
        if g.live_slots() and not self._in_flight(g):
            self._advance(g, lane)
            worked = True
        return worked

    def _in_flight(self, g: _Group) -> bool:
        # list(): `cancel_request` asks from other threads
        return any(f.group is g for f in list(self._flights))

    def _take(self, bucket: int, n: int, lane: str) -> list:
        """A turn's pull from the admission queue; the wait of each
        request taken ends here (`joined`, `queue_wait_s`)."""
        with span_on_tracer(self._tracer, "serve.admit", cat="serve",
                            bucket=bucket, lane=lane):
            reqs = self.admission.take(bucket, n, lane)
            if reqs:
                now = self.now()
                self._count_all({
                    "joined": len(reqs),
                    "queue_wait_s": sum(now - r.arrival for r in reqs)})
        return reqs

    def _cohort(self, g: _Group, reqs: list) -> tuple:
        """Pack a join cohort: padded to a power of two (capped at
        capacity) so join batches reuse a handful of compiled shapes.
        Counts the prompt tokens the prefill is for beside the positions
        it computes (`prefill_tokens_true`, `prefill_tokens_padded`)."""
        with span_on_tracer(self._tracer, "serve.admit", cat="serve",
                            bucket=g.bucket, joins=len(reqs)):
            k = len(reqs)
            n = 1
            while n < k:
                n *= 2
            n = min(n, g.capacity)
            prompts = np.zeros((n, g.bucket), np.int32)
            true_len = np.ones(n, np.int32)
            live = np.zeros(n, bool)
            ids = np.zeros(n, np.int32)
            for j, req in enumerate(reqs):
                prompts[j, :req.true_len] = req.prompt
                true_len[j] = req.true_len
                live[j] = True
                ids[j] = req.id
            self._count_all({
                "prefill_tokens_true": int(true_len[live].sum()),
                "prefill_tokens_padded": n * g.bucket})
        return prompts, true_len, live, ids

    def _join(self, g: _Group, lane: str, reqs: list, slots: list) -> None:
        """Prefill a join cohort and splice it into the resident batch.

        Queued: the merge and the group's next segment are dispatched
        right behind the prefill, the segment starting from the cohort's
        first tokens and `done` where the prefill left them on the device
        (`serve_seat`), and the first tokens are fetched and emitted after
        that, while the device works through the three.  Otherwise the
        first tokens are fetched before the splice, and the segment waits
        for the turn's end."""
        eng = self._engines[lane]
        variables = self._variables[lane]
        prompts, true_len, live, ids = self._cohort(g, reqs)
        src = list(range(len(reqs)))
        # queued unless the host needs the first tokens first: a
        # speculative lane's round drafts from them, a prefill-tier
        # engine exports them
        queued = not (eng.spec_tokens or self.role == "prefill")
        t0 = monotonic()
        with span_on_tracer(self._tracer, "serve.prefill", cat="serve",
                            bucket=g.bucket, cohort=len(ids),
                            joins=len(reqs), lane=lane):
            tok, done, caches = eng.serve_prefill(
                variables, prompts, true_len, live, self._row_keys(ids))
            counts = self._take_counts(eng)
            if not queued:
                [tok_h], own = self._fetch(eng, tok, counts=counts,
                                           dispatched=t0)
        if not queued:
            self._count("prefill_s", monotonic() - t0)
            self.estimator.observe_prefill(g.bucket, own)
            self._splice(g, lane, reqs, slots, src, tok_h, caches, prompts)
            return
        dispatch_s = monotonic() - t0
        self._splice(g, lane, reqs, slots, src, None, caches, prompts)
        self._advance(g, lane, (tok, done, slots))
        t1 = monotonic()
        [tok_h], own = self._fetch(eng, tok, counts=counts, dispatched=t0,
                                   after=self._flights[-1])
        self._count("prefill_s", dispatch_s + monotonic() - t1)
        self.estimator.observe_prefill(g.bucket, own)
        with span_on_tracer(self._tracer, "serve.emit", cat="serve",
                            bucket=g.bucket, rows=len(reqs)):
            self._welcome(g, lane, reqs, slots, src, tok_h, caches)

    def _join_prefix_hits(self, g: _Group, lane: str, reqs: list,
                          slots: list) -> tuple:
        """Try each join candidate against the prefix pool.  Hits resume
        from their donor rows — inline, or as a pending chunked-resume
        job when chunked prefill covers the suffix — and misses return
        for the normal cohort path.  The donor lease holds until the
        hit's splice lands (lease pinning: an in-flight resume can never
        lose its slots to eviction)."""
        eng = self._engines[lane]
        miss_reqs, miss_slots = [], []
        for req, slot in zip(reqs, slots):
            # match only whole chunks STRICTLY inside the prompt, so the
            # resumed prefill always recomputes the last prompt
            # position's logits itself
            limit = ((req.true_len - 1) // self._prefix.chunk
                     ) * self._prefix.chunk
            hit = (self._prefix.acquire(req.prompt, limit)
                   if limit else None)
            if hit is None:
                miss_reqs.append(req)
                miss_slots.append(slot)
                continue
            matched = hit.n_tokens
            self._count("prefix_hits")
            inc_counter("serve.prefix_hit")
            self._record_prefix({
                "event": "hit", "request": req.id, "bucket": g.bucket,
                "lane": lane, "matched": matched,
                "suffix": int(req.true_len) - matched})
            if eng.serve_resume_chunks(g.bucket, matched):
                self._start_chunked_resume(g, lane, req, slot, hit)
            else:
                self._join_resume(g, lane, req, slot, hit)
        return miss_reqs, miss_slots

    def _join_resume(self, g: _Group, lane: str, req: Request, slot: int,
                     hit) -> None:
        """Resume one prefix hit inline: dequantize/grow the donor rows,
        prefill the whole novel suffix in one traced-offset chunk call,
        finish, and splice — the same (tok, done, caches) contract as a
        fresh cohort prefill, so greedy outputs stay byte-identical."""
        eng = self._engines[lane]
        variables = self._variables[lane]
        matched = hit.n_tokens
        prompts = np.zeros((1, g.bucket), np.int32)
        prompts[0, :req.true_len] = req.prompt
        true_len = np.asarray([req.true_len], np.int32)
        ids = np.asarray([req.id], np.int32)
        self._count_resume_tokens(g, req, matched)
        t0 = monotonic()
        try:
            with span_on_tracer(self._tracer, "serve.prefill_resume",
                                cat="serve", bucket=g.bucket, lane=lane,
                                matched=matched,
                                suffix=int(req.true_len) - matched):
                tok, done, caches = eng.serve_prefill_resume(
                    variables, prompts, true_len, matched,
                    _assemble_prefix_row(hit.rows), np.ones(1, bool),
                    self._row_keys(ids))
                [tok_h], own = self._fetch(
                    eng, tok, counts=self._take_counts(eng), dispatched=t0)
            self._count("prefill_s", monotonic() - t0)
            self.estimator.observe_prefill(g.bucket, own)
            self._splice(g, lane, [req], [slot], [0], tok_h, caches,
                         prompts)
        finally:
            self._prefix.release(hit)

    def _count_resume_tokens(self, g: _Group, req: Request,
                             matched: int) -> None:
        # a resumed row prefills the suffix past its donor prefix only
        self._count_all({
            "prefill_tokens_true": int(req.true_len) - matched,
            "prefill_tokens_padded": g.bucket - matched})

    def _start_chunked_resume(self, g: _Group, lane: str, req: Request,
                              slot: int, hit) -> None:
        """Queue a chunked RESUME: like `_start_chunked_join`, but the
        state opens from the donor rows and the chunk index starts past
        the matched prefix — `_advance_prefill` then runs the suffix one
        chunk per tick through the ordinary prefill_chunk program.  The
        donor lease holds across ticks until the splice."""
        eng = self._engines[lane]
        matched = hit.n_tokens
        prompts = np.zeros((1, g.bucket), np.int32)
        prompts[0, :req.true_len] = req.prompt
        self._count_resume_tokens(g, req, matched)
        g.reserved.add(slot)
        state = eng.serve_resume_init(_assemble_prefix_row(hit.rows),
                                      g.bucket)
        self._pending.append(dict(
            group=g, lane=lane, reqs=[req], slots=[slot],
            prompts=prompts,
            true_len=np.asarray([req.true_len], np.int32),
            live=np.ones(1, bool),
            ids=np.asarray([req.id], np.int32), state=state,
            index=matched // eng.prefill_chunk,
            chunks=eng.serve_prefill_chunks(g.bucket), elapsed=0.0,
            hit=hit))

    def _release_job_lease(self, job: dict) -> None:
        hit = job.get("hit")
        if hit is not None and self._prefix is not None:
            self._prefix.release(hit)
            job["hit"] = None

    def _start_chunked_join(self, g: _Group, lane: str, reqs: list,
                            slots: list) -> None:
        """Queue a chunked join: slots are reserved (not yet resident)
        and `_advance_prefill` runs ONE prompt chunk per tick until the
        cohort finishes and splices in."""
        prompts, true_len, live, ids = self._cohort(g, reqs)
        g.reserved.update(slots)
        eng = self._engines[lane]
        self._pending.append(dict(
            group=g, lane=lane, reqs=reqs, slots=slots, prompts=prompts,
            true_len=true_len, live=live, ids=ids, state=None, index=0,
            chunks=eng.serve_prefill_chunks(g.bucket), elapsed=0.0))

    def _advance_prefill(self, job: dict) -> None:
        """One chunk of an in-flight chunked prefill; on the last chunk,
        finish (sample + quantize) and splice the cohort in.  The
        estimator's prefill EWMA sees the SUMMED chunk time — feasibility
        math reflects the full prompt cost, not one slice of it."""
        g: _Group = job["group"]
        lane = job["lane"]
        eng = self._engines[lane]
        variables = self._variables[lane]
        t0 = monotonic()
        with span_on_tracer(self._tracer, "serve.prefill_chunk",
                            cat="serve", bucket=g.bucket, lane=lane,
                            index=job["index"], chunks=job["chunks"]):
            job["state"] = eng.serve_prefill_chunk(
                variables, job["prompts"], job["true_len"], job["index"],
                job["state"])
        elapsed = monotonic() - t0
        self._count("prefill_s", elapsed)
        job["elapsed"] += elapsed
        if self._run is not None:
            rec = {"event": "prefill_chunk", "bucket": g.bucket,
                   "lane": lane, "index": job["index"],
                   "chunks": job["chunks"],
                   "requests": [r.id for r in job["reqs"]]}
            traces = [r.trace.trace_id for r in job["reqs"]
                      if getattr(r, "trace", None) is not None]
            if traces:
                rec["traces"] = traces
            self._record_serve(rec)
        job["index"] += 1
        if job["index"] < job["chunks"]:
            return
        self._pending.remove(job)
        g.reserved.difference_update(job["slots"])
        t0 = monotonic()
        tok, done, caches = eng.serve_prefill_finish(
            job["state"], job["live"], self._row_keys(job["ids"]))
        [tok_h], own = self._fetch(
            eng, tok, counts=self._take_counts(eng), dispatched=t0)
        self._count("prefill_s", monotonic() - t0)
        job["elapsed"] += own
        self.estimator.observe_prefill(g.bucket, job["elapsed"])
        # requests whose deadline passed while their prompt was still
        # chunking: finish as timeouts, splice only the survivors
        now = self.now()
        reqs, slots, src = [], [], []
        for j, (req, slot) in enumerate(zip(job["reqs"], job["slots"])):
            if req.finished:
                continue
            if req.deadline <= now:
                self._complete(req, TIMEOUT, "expired during prefill")
                continue
            reqs.append(req)
            slots.append(slot)
            src.append(j)
        if reqs:
            self._splice(g, lane, reqs, slots, src, tok_h, caches,
                         job["prompts"])
        self._release_job_lease(job)

    def _splice(self, g: _Group, lane: str, reqs: list, slots: list,
                src: list, tok_h, caches, prompts) -> None:
        """Merge cohort cache rows (and, on speculative lanes, the
        cohort's draft cache rows) into the group and seat the requests.

        On a PREFILL-tier engine this is where the work leaves: the
        finished cohort's caches go to the handoff bus instead of a
        resident slot, and each engine request ends `handoff` — the
        router's fleet request stays open until a decode replica splices
        the shipped rows and finishes the decode attempt.

        `tok_h` None: a queued join (`_join`), whose first tokens are
        still on the device; the rows are seated without them, and
        `_welcome` emits them once fetched."""
        with span_on_tracer(self._tracer, "serve.splice", cat="serve",
                            bucket=g.bucket, lane=lane, joins=len(reqs)):
            eng = self._engines[lane]
            if self.role == "prefill" and self.handoff_export is not None:
                now = self.now()
                self.handoff_export(bucket=g.bucket, lane=lane, reqs=reqs,
                                    src=src, tok_h=tok_h, caches=caches)
                for req in reqs:
                    self._count("handoffs")
                    trace_event("serve.handoff_out", cat="serve",
                                request=req.id, bucket=g.bucket, lane=lane,
                                **self._trace_fields(req))
                    req.finish(HANDOFF, now)
                return
            if g.caches is None:
                g.caches = eng.empty_state(g.capacity, g.bucket)
            g.caches = DecodeEngine.merge_cache_rows(
                g.caches, caches, slots, src, mesh=eng.mesh,
                kinds=eng.state_kinds)
            if eng.spec_tokens:
                dc = eng.serve_draft_prefill(self._draft_vars, prompts)
                if g.draft_caches is None:
                    g.draft_caches = eng.empty_state(g.capacity, g.bucket,
                                                     draft=True)
                g.draft_caches = DecodeEngine.merge_cache_rows(
                    g.draft_caches, dc, slots, src, mesh=eng.mesh)
            for j, (req, slot) in zip(src, zip(reqs, slots)):
                g.rows[slot] = req
                if tok_h is not None:
                    g.tok[slot] = tok_h[j]
                g.true_len[slot] = req.true_len
                g.budget[slot] = req.max_new_tokens
                g.t_row[slot] = 0
                g.row_ids[slot] = req.id
                g.done[slot] = False
            self._count_all({"join_cohorts": 1,
                             "join_cohorts_queued": int(tok_h is None)})
            if tok_h is not None:
                self._welcome(g, lane, reqs, slots, src, tok_h, caches)

    def _welcome(self, g: _Group, lane: str, reqs: list, slots: list,
                 src: list, tok_h, caches) -> None:
        """A spliced cohort's first tokens reach its rows (`src[i]` the
        cohort row of `reqs[i]`, seated at `slots[i]`), and its prompts
        go to the prefix pool."""
        for j, (req, slot) in zip(src, zip(reqs, slots)):
            if req.finished:
                # withdrawn between a queued join's dispatch and this
                # fetch: the segment's harvest frees the slot
                continue
            trace_event("serve.join", cat="serve", request=req.id,
                        bucket=g.bucket, slot=slot, lane=lane,
                        **self._trace_fields(req))
            self._record_serve({"event": "join", "request": req.id,
                                "bucket": g.bucket, "slot": slot,
                                "lane": lane, **self._trace_fields(req)})
            # attempt-level TTFT: arrival at THIS engine to its first
            # emitted token (the fleet-level TTFT, arrival at the
            # router to the decode-tier splice, lands in handoff.py)
            ttft = self.now() - req.arrival
            self._ttfts.append(ttft)
            if self._run is not None:
                self._run.observe_hist("serve.ttft_s", ttft)
            self._emit(g, slot, [int(tok_h[j])])
        if self._prefix is not None and lane == "primary":
            self._insert_prefix_rows(reqs, src, caches)
            self._gauge_prefix()

    def _insert_prefix_rows(self, reqs: list, src: list, caches) -> None:
        """Pool each freshly spliced request's prompt-prefix slots: the
        greatest chunk multiple STRICTLY inside the prompt, so a later
        resume always recomputes the final prompt position itself.
        First-writer-wins per chunk; a refused eviction (every candidate
        leased) skips the deeper chunks rather than forcing anything."""
        chunk = self._prefix.chunk
        for j, req in zip(src, reqs):
            n = ((req.true_len - 1) // chunk) * chunk
            if n < chunk:
                continue
            row = [tuple(t[j:j + 1] for t in layer) for layer in caches]
            res = self._prefix.insert(req.prompt, n, row)
            if res["inserted"]:
                self._count("prefix_inserts", res["inserted"])
                self._record_prefix({
                    "event": "insert", "request": req.id,
                    "chunks": res["inserted"], "tokens": n})
            if res["evicted"]:
                self._count("prefix_evictions", res["evicted"])
                self._record_prefix({
                    "event": "evict", "chunks": res["evicted"],
                    "request": req.id})
            if res["refused"]:
                self._count("prefix_evictions_refused")
                inc_counter("serve.prefix_eviction_refused")
                self._record_prefix({"event": "evict_refused",
                                     "request": req.id})

    def splice_remote(self, prompt: np.ndarray, max_new_tokens: int,
                      deadline: float, first_tok: int, src_caches,
                      lane: str = "primary", trace=None) -> Optional[Request]:
        """Seat one handed-off row (decode tier): merge the deserialized
        1-row cache into this engine's resident batch via the jitted
        `merge_cache_rows` and decode it to completion like any join.
        `trace` is the TraceContext that rode the kv_begin header — the
        decode attempt keeps the fleet request's trace id.  Returns the
        seated engine Request, or None when no slot is free or the
        engine is not alive — the handoff bus retries next tick (bounded
        by the transfer timeout and the request deadline)."""
        if not self.alive:
            return None
        eng = self._engines[lane]
        arr = np.asarray(prompt, np.int32)
        bucket = eng.bucket_for(arr.size)
        g = self._groups.get((bucket, lane))
        if g is None:
            g = self._groups[(bucket, lane)] = _Group(
                bucket, self.cfg.max_batch)
        self._harvest(g)       # before a row of the group is written
        free = g.free_slots()
        if not free:
            return None
        slot = free[0]
        now = self.now()
        req = Request(self._new_id(), arr, bucket, max_new_tokens, now,
                      float(deadline))
        req.trace = trace
        if g.caches is None:
            g.caches = eng.empty_state(g.capacity, bucket)
        g.caches = DecodeEngine.merge_cache_rows(
            g.caches, src_caches, [slot], [0], mesh=eng.mesh,
            kinds=eng.state_kinds)
        g.rows[slot] = req
        g.tok[slot] = int(first_tok)
        g.true_len[slot] = req.true_len
        g.budget[slot] = req.max_new_tokens
        g.t_row[slot] = 0
        g.row_ids[slot] = req.id
        g.done[slot] = False
        self._count("remote_joins")
        trace_event("serve.handoff_in", cat="serve", request=req.id,
                    bucket=bucket, slot=slot, lane=lane,
                    **self._trace_fields(req))
        self._record_serve({"event": "remote_join", "request": req.id,
                            "bucket": bucket, "slot": slot, "lane": lane,
                            **self._trace_fields(req)})
        self._emit(g, slot, [int(first_tok)])
        if self._prefix is not None and lane == "primary":
            # the pool lives on the DECODE tier of a disaggregated
            # fleet: handed-off rows are the tier's only prefill source,
            # so they are what populates it (the prefill tier never
            # double-caches — ServeConfig rejects prefix_cache there)
            self._insert_prefix_rows([req], [0], src_caches)
            self._gauge_prefix()
        return req

    def _emit(self, g: _Group, slot: int, tokens: list) -> None:
        """Append emitted tokens to a row's request, honoring its budget
        and stop tokens; completes the request when finished, and frees
        its row unless the group's segment is in flight (a queued join's
        first token: the harvest frees it)."""
        req = g.rows[slot]
        stopped = False
        appended = False
        for tok in tokens:
            if len(req.tokens) >= req.max_new_tokens:
                break
            req.tokens.append(int(tok))
            appended = True
            if self._stops.size and int(tok) in self._stops:
                stopped = True
                break
        if stopped or len(req.tokens) >= req.max_new_tokens:
            self._complete(req, OK)
            if not self._in_flight(g):
                g.release(slot)
        elif appended:
            # segment-boundary flush point: wake any streaming reader
            # (finish() notifies on its own for the completed case)
            req.note_tokens()

    def _advance(self, g: _Group, lane: str, joined=None) -> None:
        """Dispatch one mixed-age segment for a group, its harvest left
        to the group's next turn (`_harvest`) — or, on speculative lanes,
        run one whole draft-verify round.  `joined`: a queued join's
        (first tokens, `done`, slots), still on the device, which the
        segment starts from in those rows (`serve_seat`)."""
        if self._engines[lane].spec_tokens:
            self._advance_spec(g, lane)
            return
        eng = self._engines[lane]
        variables = self._variables[lane]
        seg = self.cfg.segment_steps
        live = g.live_slots()
        max_t = int(g.t_row[live].max()) if live else 0
        window = eng.serve_window(g.bucket, max_t, seg)
        # the segment steps at the whole cache width, whatever the masks
        # (a resident cache never shrinks: `serve_step`)
        read = max(window, eng.state_window(g.caches))
        # overlapped: behind another program of this engine that nobody
        # has fetched yet (a prefill is fetched where it is dispatched, so
        # that is another group's segment)
        overlapped = any(f.lane == lane for f in self._flights)
        t0 = monotonic()
        with span_on_tracer(self._tracer, "serve.segment", cat="serve",
                            bucket=g.bucket, lane=lane, seg_len=seg,
                            window=window, occupancy=round(
                                len(live) / g.capacity, 3)):
            if joined is None:
                tok, done = np.asarray(g.tok), np.asarray(g.done)
            else:
                tok, done = eng.serve_seat(g.tok, g.done, *joined)
            g.caches, toks, tok, done = eng.serve_step(
                variables, g.caches, tok, done, g.true_len, g.budget,
                g.bucket, g.t_row, self._group_keys(g), seg, window)
        self._flights.append(_Flight(
            g, lane, toks, tok, done, self._take_counts(eng), live, read,
            t0))
        self._count_all({"segments_dispatched": 1,
                         "segments_overlapped": int(overlapped)})

    def _harvest_all(self) -> bool:
        """Harvest every segment in flight, oldest first."""
        return any([self._harvest(f.group) for f in list(self._flights)])

    def _harvest(self, g: _Group) -> bool:
        """Fetch the segment `g` has in flight, if any, and hand its
        tokens to the rows: emit, complete, free.  True when it had
        one."""
        f = next((f for f in self._flights if f.group is g), None)
        if f is None:
            return False
        eng = self._engines[f.lane]
        seg, live = self.cfg.segment_steps, f.live
        (toks_h, tok_h, done_h), own = self._fetch(
            eng, f.toks, f.tok, f.done, counts=f.counts,
            dispatched=f.dispatched, flight=f)
        self._flights.remove(f)
        self.estimator.observe_step(g.bucket, own / seg)
        self._record_serve({"event": "segment", "bucket": g.bucket,
                            "lane": f.lane, "rows": len(live)})
        if self._run is not None:
            # per-token pacing: one sample per segment (the segment's own
            # time over its decode steps), not per token — bounded-cost by
            # design
            self._run.observe_hist("serve.inter_token_s", own / seg)
        g.tok = tok_h.astype(np.int32)
        g.done = done_h.astype(bool)
        # the segment's masks are its dispatch's rows: count before the
        # rows move on
        blocks_read, blocks_dense = eng.serve_blocks(
            g.true_len, g.bucket, g.t_row, seg, f.read)
        # a row is live for the steps whose tokens it keeps: one that
        # stops or spends its budget inside the segment is frozen for
        # the rest.  At its s-th step it sees its prompt and
        # t_row + s generated slots
        steps_live = keys_live = 0
        with span_on_tracer(self._tracer, "serve.emit", cat="serve",
                            bucket=g.bucket, rows=len(live)):
            for i in live:
                req = g.rows[i]
                if req is None or req.finished:
                    # withdrawn while the segment ran (`cancel_request`,
                    # a replica's `fail_inflight`): the slot is free
                    g.release(i)
                    continue
                had = len(req.tokens)
                seen = int(g.true_len[i] + g.t_row[i])
                self._emit(g, i, toks_h[i].tolist())
                kept = len(req.tokens) - had
                steps_live += kept
                keys_live += kept * seen + kept * (kept + 1) // 2
                if g.rows[i] is not None:
                    g.t_row[i] += seg
        self._count_all({
            "slot_steps_live": steps_live,
            "slot_steps_capacity": g.capacity * seg,
            "decode_keys_live": keys_live,
            "decode_keys_read": g.capacity * seg * f.read,
            "decode_blocks_read": blocks_read,
            "decode_blocks_dense": blocks_dense})
        if self._run is not None:
            self._run.gauge("serve.queue_depth", self.admission.pending())
            self._run.gauge("serve.in_flight", self.in_flight())
            self._gauge_prefix()
        return True

    def _advance_spec(self, g: _Group, lane: str) -> None:
        """One speculative round: the draft proposes, one target forward
        verifies, each row advances by its accepted count (+1).  The
        estimator's per-step EWMA sees round time divided by tokens
        actually emitted per live row — feasibility math tracks the
        measured speculative speedup, not the optimistic bound."""
        eng = self._engines[lane]
        variables = self._variables[lane]
        k1 = eng.spec_tokens + 1
        live = g.live_slots()
        max_t = int(g.t_row[live].max()) if live else 0
        window = eng.serve_window(g.bucket, max_t, k1)
        t0 = monotonic()
        with span_on_tracer(self._tracer, "serve.spec_round", cat="serve",
                            bucket=g.bucket, lane=lane, window=window,
                            occupancy=round(len(live) / g.capacity, 3)):
            (caches, draft_caches, toks, counts, tok, done,
             accepted) = eng.serve_spec_round(
                variables, self._draft_vars, g.caches, g.draft_caches,
                np.asarray(g.tok), np.asarray(g.done), g.true_len,
                g.budget, g.bucket, g.t_row, g.spec_rounds,
                self._group_keys(g), window)
            (toks_h, counts_h, tok_h, done_h, accepted_h), elapsed = (
                self._fetch(eng, toks, counts, tok, done, accepted,
                            counts=self._take_counts(eng), dispatched=t0))
        g.spec_rounds += 1
        emitted = int(counts_h[live].sum())
        per_row = emitted / max(1, len(live))
        self.estimator.observe_step(g.bucket, elapsed / max(1.0, per_row))
        inc_counter("serve.spec_drafted_tokens",
                    eng.spec_tokens * len(live))
        inc_counter("serve.spec_accepted_tokens",
                    int(accepted_h[live].sum()))
        self._record_serve({"event": "segment", "bucket": g.bucket,
                            "lane": lane, "rows": len(live),
                            "spec": True, "emitted": emitted})
        if self._run is not None:
            self._run.observe_hist("serve.inter_token_s",
                                   elapsed / max(1.0, per_row))
        g.caches = caches
        g.draft_caches = draft_caches
        g.tok = tok_h.astype(np.int32)
        g.done = done_h.astype(bool)
        for i in live:
            if g.rows[i] is None:
                continue
            take = int(counts_h[i])
            if take:
                self._emit(g, i, toks_h[i][:take].tolist())
            if g.rows[i] is not None:
                g.t_row[i] += take
        if self._run is not None:
            self._run.gauge(
                "serve.spec_acceptance_rate",
                round(float(accepted_h[live].sum())
                      / max(1, eng.spec_tokens * len(live)), 4))
            self._run.gauge("serve.queue_depth", self.admission.pending())
            self._run.gauge("serve.in_flight", self.in_flight())

    # -- the loop (spawned by serve/lifecycle.py) -------------------------
    def _drained(self) -> bool:
        return (self._state == DRAINING and self.in_flight() == 0
                and self.admission.pending() == 0)

    def _loop(self) -> None:
        """The scheduler thread body: tick (the segments it dispatches
        carried into the next pass), check the SIGTERM guard, idle on
        the condition when there is no work."""
        while True:
            if (self._guard is not None and self._guard.triggered
                    and self._state == READY):
                self.begin_drain("sigterm")
            if self._state == STOPPED:
                return
            worked = self._tick(carry=True)
            if self._drained():
                self._finish_drain()
                return
            if not worked:
                with span_on_tracer(None, "serve.idle_wait"), self._wake:
                    self._wake.wait(timeout=0.01)

    # -- stats -------------------------------------------------------------
    @staticmethod
    def _percentile(samples, q: float) -> Optional[float]:
        # list(): a snapshot, the loop thread appends meanwhile
        samples = list(samples)
        if not samples:
            return None
        return float(np.percentile(np.asarray(samples), q))

    def stats(self) -> dict:
        """Counts + latency percentiles (seconds) + breaker state — the
        dict the drills, bench arm, and gauges read."""
        out = dict(self._counts)
        out["in_flight"] = self.in_flight()
        out["queued"] = self.admission.pending()
        out["state"] = self._state
        out["breaker_state"] = self.breaker.state
        out["weights_device_bytes"] = _device_bytes(
            (self._variables, self._draft_vars))   # lanes and the draft
        # of them, the leaves held in the compute dtype and not the
        # bundle's (0: a float32 or an int8 model)
        out["weights_cast_bytes"] = self._cast_bytes
        # gauges: bytes of the resident rows' state, by kind (a window
        # that grows by cache_chunk; a fixed leaf a row)
        held = {WINDOW: 0, FIXED: 0}
        for (_, lane), g in list(self._groups.items()):
            caches = g.caches
            if caches is not None:
                for kind, n in self._engines[lane].state_bytes(
                        caches).items():
                    held[kind] += n
        out["state_bytes_window"] = held[WINDOW]
        out["state_bytes_fixed"] = held[FIXED]
        # a count: bytes of state that the lanes' dispatched programs
        # re-tiled, as each decoding says of its own programs (0 for a
        # segment: it steps on the resident layout as it is)
        out["state_relayout_bytes"] = sum(
            eng.relayout_bytes for eng in self._engines.values())
        # counts: the per-row writes of a decode step's new K and V into
        # window leaves that the dispatched segments (and speculative
        # rounds) made, and those of them that loop over the rows on the
        # device and are not one flat scatter (0 for a decode step)
        out["row_writes"] = sum(
            eng.row_writes for eng in self._engines.values())
        out["row_writes_looped"] = sum(
            eng.row_writes_looped for eng in self._engines.values())
        # counts, the PROCESS's (observe/compiles.py): programs traced,
        # lowered and compiled or loaded from the compile cache, with
        # their seconds; their rise over a window is the compile work
        # inside it.  `compiles_after_ready`: programs that closed after
        # this engine's `ready`, each a shape class the warm-up missed
        for key, value in compiles.totals().items():
            if key != "saved_s":
                out["compile_" + key] = value
        out["compiles_after_ready"] = 0.0 if self._ready_at is None else \
            compiles.totals(since=self._ready_at)["programs"]
        # gauges: seconds of this engine's `setup.warmup` and
        # `setup.place_weights`, its `warmup_program` events, and the
        # package's import (`/statz` adds the ledger's table, `programs`)
        out["warmup_s"] = self._warmup_s
        out["warmup_programs"] = self._warmup_programs
        out["weights_place_s"] = self._place_s
        out["import_s"] = compiles.import_s
        if self._prefix is not None:
            out["prefix"] = self._prefix.stats()
        for name, q in (("p50", 50), ("p95", 95), ("p99", 99)):
            p = self._percentile(self._latencies, q)
            out[f"latency_{name}_s"] = round(p, 6) if p is not None else None
        for name, q in (("p50", 50), ("p95", 95)):
            p = self._percentile(self._ttfts, q)
            out[f"ttft_{name}_s"] = round(p, 6) if p is not None else None
        return out

    def prefix_stats(self) -> Optional[dict]:
        """The prefix pool's live stats dict (None when the pool is off)
        — surfaced per replica in `Replica.health()` and `/statz`."""
        return self._prefix.stats() if self._prefix is not None else None

    def _gauge_stats(self) -> None:
        if self._run is None:
            return
        for name, q in (("p50", 50), ("p95", 95), ("p99", 99)):
            p = self._percentile(self._latencies, q)
            if p is not None:
                self._run.gauge(f"serve.latency_{name}_ms", p * 1e3)
        for key in ("admitted", "shed", "ok", "timeout", "cancelled",
                    "degraded", "goodput_tokens"):
            self._run.gauge(f"serve.{key}", self._counts.get(key, 0))
