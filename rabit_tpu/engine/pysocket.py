"""Pure-Python TCP collective engine (the "base engine").

TPU-native rebuild of the reference's non-fault-tolerant base engine
(reference: src/allreduce_base.{h,cc}): tracker rendezvous, persistent
worker-worker links, and the core collectives.  This is the DCN/host
fallback transport and the substrate the robust layer builds on; the C++
native engine implements the same wire behaviour for the performance path,
and the XLA engine replaces the data plane entirely with ICI collectives.

Algorithmic departures from the reference (deliberate):

* Large allreduces use **ring reduce-scatter + all-gather** (bandwidth
  optimal, every link equally loaded) instead of the reference's pipelined
  binary tree (src/allreduce_base.cc:326-491); small payloads use the tree
  (log₂n hops beats n hops on latency).
* Any-root broadcast is a plain tree flood: the root sends on all its tree
  links, everyone else forwards from their first-arriving link to the rest
  — same idea as the reference's in-link probing (src/allreduce_base.cc:
  500-588) without the slot machinery.
"""
from __future__ import annotations

import collections
import json
import os
import random
import select
import signal
import socket
import struct
import threading
import time
from typing import Callable, Optional

import numpy as np

from rabit_tpu import chaos as chaos_mod
from rabit_tpu import codec as codec_mod
from rabit_tpu import obs
from rabit_tpu.codec import kernel as ck_mod
from rabit_tpu import sched as sched_mod
from rabit_tpu import transport as tr
from rabit_tpu.engine.interface import (AsyncOrderError, CollectiveHandle,
                                        Engine)
from rabit_tpu.ops import ReduceOp
from rabit_tpu.ops.reduce_ops import apply_op_numpy
from rabit_tpu.tracker import protocol as P
from rabit_tpu.transport import IntegrityError, LinkError
from rabit_tpu.utils.checks import RabitError, check
from rabit_tpu.utils.units import parse_byte_size

# Payloads at or below this ride the tree (latency-bound); above, the ring
# (bandwidth-bound).  This module global is the DEFAULT for the static
# crossover; rabit_ring_threshold_bytes overrides it per engine, and
# rabit_sched replaces the whole static dispatch with forced or
# auto-tuned schedule selection (doc/performance.md).
TREE_RING_CROSSOVER_BYTES = 64 << 10
# Chunk size for full-duplex streaming on the ring.
CHUNK_BYTES = 256 << 10
# Hop-pipeline chunk FLOOR (rabit_pipeline_chunk): a pipelined hop
# splits each reduce-buffer chunk ``depth`` ways but never below this —
# every chunk boundary is a synchronization point (a pop is a per-chunk
# recv barrier), and on small hops the sync cost eats the overlap win,
# so hops that cannot produce at least two floor-sized chunks run the
# serial loop instead (doc/performance.md "Hop pipelining").
PIPE_CHUNK_BYTES = 64 << 10
# Default in-flight chunk window (rabit_pipeline_depth): 2 = classic
# double buffering — chunk k+1's exchange is on the wire while chunk k
# is merged.  1 = the legacy serial hop loop, byte- and bit-identical.
PIPE_DEPTH = 2
# Async small-op coalescing budget (rabit_bucket_bytes): same-op/same-dtype
# allreduces at or below this size fuse into one wire op.
DEFAULT_BUCKET_BYTES = 1 << 20


# LinkError/IntegrityError live in rabit_tpu.transport.base now (every
# transport raises them); re-imported above so the historical
# `from rabit_tpu.engine.pysocket import LinkError` spelling — used by
# the robust layer, tests and downstream code — keeps working.


class AdmissionError(LinkError):
    """The tracker refused this job's registration across the full
    admission retry budget (multi-tenant admission control:
    ``--max-jobs`` / ``--max-total-workers``, doc/fault_tolerance.md
    "Multi-tenant tracker").

    An over-capacity submission is not an outage: each rejection is a
    typed wire reply, the worker backs off and re-registers
    (``rabit_admission_retries``), and the tracker re-admits the moment
    a finishing job drains — so a submission racing a completing job
    gets in.  Only when every attempt is refused does this escape,
    carrying the tracker's last ``code``/``reason``.  A LinkError like
    :class:`TrackerLostError`: overload degrades to a typed failure,
    never a hang."""

    def __init__(self, msg: str, code: int = 0, reason: str = "") -> None:
        super().__init__(msg)
        self.code = int(code)
        self.reason = reason


class ShardMovedError(LinkError):
    """Every tracker the directory pointed at kept redirecting this
    job's registration elsewhere across the full ``rabit_shard_retries``
    budget (sharded control plane, doc/fault_tolerance.md "Sharded
    tracker").

    A single ``REJECT_SHARD_MOVED`` reply is not an error: the reason
    carries the owning shard's generation and endpoint, the worker
    re-targets and re-registers — one extra round trip, paid only when
    a cached directory went stale.  Redirects that keep chasing a
    moving owner past the budget mean the directory and the shards
    disagree persistently (split membership view, mid-rebalance churn);
    that surfaces here as a typed LinkError — the robust recover loop
    treats it like any dead link — carrying the last redirect's
    ``generation``, ``shard`` and ``endpoint``, so a postmortem can
    tell a stale cache (old generation, live endpoint) from a dead
    fleet (current generation, nothing answering)."""

    def __init__(self, msg: str, generation: int = -1,
                 shard: int = -1, endpoint: str = "") -> None:
        super().__init__(msg)
        self.generation = int(generation)
        self.shard = int(shard)
        self.endpoint = str(endpoint)


class TrackerLostError(LinkError):
    """The tracker stayed unreachable across the full registration
    retry budget — the job's coordinator is gone.

    A rendezvous registration (start/recover/rescale) retries the whole
    dial+register exchange with backoff, so a tracker merely
    *restarting* (crash + supervisor relaunch on the same port, its
    journal replayed — doc/fault_tolerance.md "Elastic membership &
    tracker HA") reads as a stall, never an error.  Only when every
    attempt fails does this escape: from ``init()`` it reaches the
    application directly; inside the robust engine's recover loop it is
    an ordinary link failure (this class IS a :class:`LinkError`) and
    surfaces wrapped in ``RecoveryError`` once the recover budget is
    also spent."""


class WorldChangedError(RabitError):
    """The world was rescaled out from under this collective/checkpoint.

    Raised (on every member, consistently) after an elastic membership
    epoch completes: the tracker reassigned ranks for a grown or shrunk
    world, so results, replay caches and rank-affine data shards from
    the old world are void.  The committed checkpoint is NOT lost — the
    contract is: catch this, call ``load_checkpoint()`` (served from
    the survivors' RAM replicas or the durable tier), re-shard
    rank-affine state for the new ``(rank, world)`` (e.g. with
    :func:`rabit_tpu.learn.splitrows.rows_for_rank`), and resume the
    loop from the returned version.  Carries ``old_world``,
    ``new_world`` and the new ``epoch``."""

    def __init__(self, old_world: int, new_world: int, epoch: int) -> None:
        super().__init__(
            f"world rescaled from {old_world} to {new_world} rank(s) "
            f"(membership epoch {epoch}): reload the last committed "
            f"checkpoint and re-shard rank-affine state")
        self.old_world = int(old_world)
        self.new_world = int(new_world)
        self.epoch = int(epoch)


class AsyncPumpError(RuntimeError):
    """The async progress pump died; queued collectives can never run.

    Raised at ``CollectiveHandle.wait()`` for every op that was queued
    behind (or issued after) the pump's death — the stream is poisoned
    so callers fail loudly instead of hanging on handles nobody will
    ever resolve."""


class _ScratchArena:
    """Pooled reusable byte buffers for the chunked collective paths.

    The tree/ring pumps borrow per-chunk scratch from here instead of
    allocating a fresh ``bytearray`` per call — on the small-op hot path
    (consensus words, bucketed streams) the allocator churn was
    measurable.  Buffers are handed out as exact-size memoryviews over a
    possibly-larger pooled backing store; the pool is bounded, so worst
    case memory is a few ``rabit_reduce_buffer`` chunks.
    """

    # Only small-to-middling buffers are worth retaining: the pool
    # exists for small-op allocator churn, and keeping multi-hundred-MB
    # tree leases alive for the engine's lifetime would trade transient
    # scratch for permanent retention.
    MAX_POOLED_BYTES = 4 << 20

    def __init__(self, max_buffers: int = 8) -> None:
        self._free: list[bytearray] = []
        self._max = max_buffers
        self._lock = threading.Lock()

    def take(self, nbytes: int) -> memoryview:
        with self._lock:
            for i, b in enumerate(self._free):
                if len(b) >= nbytes:
                    return memoryview(self._free.pop(i))[:nbytes]
        return memoryview(bytearray(max(nbytes, 1)))[:nbytes]

    def give(self, mv: memoryview) -> None:
        backing = mv.obj
        if not isinstance(backing, bytearray):
            return
        if len(backing) > self.MAX_POOLED_BYTES:
            return  # oversized lease: let the allocator reclaim it
        with self._lock:
            if len(self._free) < self._max:
                self._free.append(backing)


class _TransportEvents(tr.Events):
    """Transport-layer telemetry routed into the engine's obs plumbing
    (counters + trace events), gated on the single _obs_on bool like
    every other engine call site."""

    def __init__(self, eng: "PySocketEngine") -> None:
        self._eng = eng

    def counter(self, name: str, n: int = 1) -> None:
        eng = self._eng
        if eng._obs_on:
            eng._metrics.counter(name).inc(n)

    def event(self, name: str, **fields) -> None:
        eng = self._eng
        if eng._obs_on:
            eng._trace.emit(name, rank=eng._rank, **fields)


class PySocketEngine(Engine):
    def __init__(self) -> None:
        self._rank = 0
        self._world = 1
        self._links: dict[int, tr.Link] = {}
        self._tree_links: list[int] = []
        self._parent = P.NONE
        self._ring_prev = P.NONE
        self._ring_next = P.NONE
        self._tracker_addr: Optional[tuple[str, int]] = None
        self._task_id = "0"
        # Multi-tenant job id (rabit_job_id / RABIT_JOB_ID): names the
        # tenant on every tracker connection.  The default job speaks
        # the classic wire byte-for-byte (old trackers still work).
        self._job_id = P.DEFAULT_JOB
        self._listener: Optional[socket.socket] = None
        self._version = 0
        self._epoch = 0    # membership epoch of the current topology
        self._global: Optional[bytes] = None
        self._local: Optional[bytes] = None
        self._timeout = 600.0  # overridden in init()
        self._relaunched = False
        # Connect retry policy (rabit_connect_retries /
        # rabit_backoff_base_ms): capped exponential backoff with full
        # jitter, mirroring the native layer's ConnectRetry
        # (native/src/socket.cc) on every dial.
        self._connect_retries = 4
        self._backoff_base_ms = 100.0
        self._admission_retries = 10
        # Sharded control plane (rabit_directory): built in init().
        self._directory = None
        self._shard_retries = 4
        # Fault-injection plan (rabit_chaos); None = chaos off, and
        # every touchpoint gates on that single check.
        self._chaos: Optional[chaos_mod.ChaosPlan] = None
        self._sock_buf = 0          # rabit_sock_buf (0 = kernel default)
        # The link layer (rabit_tpu/transport/): the factory owns
        # link construction + integrity negotiation; built for real in
        # init() once the knobs resolve.
        self._lf = tr.LinkFactory(tr.TransportConfig(),
                                  timeout=self._timeout)
        # Wire codec (rabit_wire_codec): the ONE lossy wire-format
        # seam — None is the classic full-width wire, Bf16Codec is the
        # historical rabit_wire_dtype=bf16 cast, the block-scaled
        # int8/int4 codecs quantize with error feedback.  _op_codec/
        # _op_cstate are the per-dispatch window the schedules' merge
        # seam (_wire_merge) consults; ops are serialized (the async
        # pump owns the links while handles are in flight), so one
        # slot suffices.
        self._codec: Optional[codec_mod.Codec] = None
        self._codec_label = "none"  # tuning-cache key dimension
        self._codec_block = codec_mod.DEFAULT_BLOCK
        self._codec_min_bytes = codec_mod.DEFAULT_MIN_BYTES
        # Directive codec overrides (doc/performance.md "Online
        # adaptation"): lazily-built codec instances for the per-bucket
        # ``bytes:sched/codec`` form of the controller's directive —
        # same replicated block/floor config as the job codec, so the
        # override stays a collective decision.
        self._codec_byname: dict[str, Optional[codec_mod.Codec]] = {}
        self._feedback = codec_mod.FeedbackBuffer()
        self._op_codec = None
        self._op_cstate = None
        # Compiled codec kernels (rabit_codec_impl, codec/kernel.py):
        # the block-scale hop math runs through librabit_codec.so when
        # it loads, numpy otherwise — bit-identical by contract, so
        # this is a per-rank perf knob like the pipeline depth, never
        # a collective decision.  _op_elem_k arms the native bf16
        # elementwise merge for one dispatch window; _op_ck_time
        # accumulates this op's codec kernel/hop-math seconds for the
        # obs plane (codec.kernel.seconds).
        self._codec_kernel: Optional[codec_mod.CodecKernel] = None
        self._codec_impl = "numpy"
        self._op_elem_k = None
        self._op_ck_time = 0.0
        self._bucket_bytes = DEFAULT_BUCKET_BYTES
        self._arena = _ScratchArena()
        # Hop pipelining (rabit_pipeline_depth / rabit_pipeline_chunk):
        # the schedules' chunked exchange+merge loops keep up to
        # _pipe_depth chunk exchanges in flight so merge compute hides
        # behind wire IO.  Depth 1 is the legacy serial loop; the wire
        # byte stream is depth-independent, so mixed-depth worlds
        # interoperate (doc/performance.md "Hop pipelining").
        self._pipe_depth = PIPE_DEPTH
        self._pipe_chunk = PIPE_CHUNK_BYTES
        # Collective schedule selection (rabit_sched): "static" keeps
        # the tree/ring crossover, "auto" consults the tuning cache, a
        # schedule name forces it wherever it applies.  The topology
        # handout's host groups feed the hierarchical schedule.
        self._sched_name = "static"
        self._ring_threshold: Optional[int] = None  # None -> module global
        self._tune_dir: Optional[str] = None
        self._tuner: Optional[sched_mod.TuningCache] = None
        self._groups: list[int] = []
        self._last_sched: Optional[str] = None  # trace on choice change
        # Live adaptation state from the topology handout (tracker
        # AdaptiveController, doc/performance.md "Online adaptation"):
        # a per-payload-bucket schedule directive consulted before the
        # static/auto pick, and the straggler-demoted ranks excluded
        # from hierarchical leadership.  Both land on EVERY rank in the
        # same rendezvous round, so dispatch stays a collective
        # decision.
        self._sched_live: dict[int, str] = {}
        self._demoted: frozenset = frozenset()
        # Async collective stream: a single background progress thread
        # (created lazily on the first *_async call) executes queued ops
        # strictly in issue order, so seqno/replay layers above see the
        # exact op sequence a blocking caller would produce.
        self._aq: collections.deque = collections.deque()
        self._aq_cv = threading.Condition()
        self._aq_thread: Optional[threading.Thread] = None
        self._aq_inflight = 0   # queued-but-unfinished op groups
        self._pump_error: Optional[Exception] = None  # pump died: poisoned
        self._issue_idx = 0     # async handles issued (user ops)
        self._wait_idx = 0      # next handle index allowed to wait()
        self._pending: Optional[dict] = None  # open coalescing bucket
        # Heartbeat liveness channel (rabit_heartbeat_sec): one
        # persistent tracker connection fed by a background thread so
        # the control plane learns about a hung/dead worker proactively
        # instead of waiting for a collective to touch the corpse.
        self._hb_sec = 0.0
        self._hb_thread: Optional[threading.Thread] = None
        self._hb_stop = threading.Event()
        # Telemetry (rabit_tpu.obs): off until init() resolves the
        # config; every call site gates on the single _obs_on bool so
        # the disabled cost is one attribute check per collective.
        self._obs_on = False
        self._obs_dir: Optional[str] = None
        self._metrics: Optional[obs.Metrics] = None
        self._trace: Optional[obs.EventTrace] = None
        # Live telemetry plane (doc/observability.md "Live telemetry"):
        # with telemetry on and rabit_obs_flush_sec > 0, the heartbeat
        # thread ships one delta frame + the buffered collective spans
        # per flush period over the persistent heartbeat connection.
        self._obs_flush_sec = 0.0
        self._span_buf: Optional[obs.SpanBuffer] = None
        self._exporter: Optional[obs.DeltaExporter] = None
        self._span_seq = 0          # span seq fallback (no protocol seqno)
        self._op_sched: Optional[str] = None  # schedule of the last dispatch
        self._op_wire = "none"  # effective wire format of the last op
        # Causal hop tracing (doc/observability.md "Causal tracing &
        # postmortem"): rabit_trace_sample arms per-hop/per-chunk/codec
        # -window records on every Nth op — the decision is
        # deterministic in the op seqno, so all ranks trace the SAME
        # ops and the tracker assembles complete cross-rank timelines.
        # Off (_op_traced False, sample 0), every emit site is one
        # attribute check.
        self._trace_sample = 0
        self._hop_buf: Optional[obs.HopBuffer] = None
        self._op_traced = False
        self._op_trace_key: Optional[tuple] = None
        self._hop_idx = 0           # op-local hop index while traced
        self._op_count = 0          # lockstep op index (seqno fallback)
        # Flight recorder: the always-on crash ring; persists under
        # rabit_trace_dir on every fault path (LinkError escalation,
        # SIGTERM, recovery budget exhaustion).
        self._flight: Optional[obs.FlightRecorder] = None
        self._trace_dir: Optional[str] = None
        self._log = obs.log.Logger(self._obs_role(), self._log_ctx)

    def _obs_role(self) -> str:
        return "pysocket"

    def _log_ctx(self) -> dict:
        """Structured-log prefix: co-tenant jobs' merged stderr must be
        attributable, so a named job rides in every line."""
        if self._job_id != P.DEFAULT_JOB:
            return {"job": self._job_id, "rank": self._rank}
        return {"rank": self._rank}

    # ------------------------------------------------------------------
    # lifecycle / rendezvous
    # ------------------------------------------------------------------
    def init(self, params: dict) -> None:
        uri = params.get("rabit_tracker_uri") or os.environ.get("RABIT_TRACKER_URI")
        port = params.get("rabit_tracker_port") or os.environ.get("RABIT_TRACKER_PORT")
        check(uri is not None and port is not None,
              "pysocket engine needs rabit_tracker_uri/rabit_tracker_port")
        self._tracker_addr = (str(uri), int(port))
        self._task_id = str(params.get("rabit_task_id")
                            or os.environ.get("RABIT_TASK_ID", "0"))
        # Tenant identity (rabit_job_id / RABIT_JOB_ID): scopes every
        # tracker-side structure (rank map, barriers, heartbeats,
        # journal, obs dirs) to this job on a multi-tenant tracker.
        # Path-safe by contract — it names directories on the tracker.
        self._job_id = str(params.get("rabit_job_id")
                           or os.environ.get("RABIT_JOB_ID")
                           or P.DEFAULT_JOB)
        check(P.valid_job_id(self._job_id),
              "rabit_job_id must be a path-safe token "
              "([A-Za-z0-9][A-Za-z0-9._-]{0,63}), got %r", self._job_id)
        self._world_hint = int(params.get("rabit_world_size")
                               or os.environ.get("RABIT_WORLD_SIZE", 0))
        # Peer-link IO timeout: a hung-but-alive peer surfaces as
        # LinkError (-> recovery) after this long instead of wedging the
        # job for the old hard-coded 600 s (reference analogue: errno
        # classification, src/allreduce_base.cc:392-397).  Tracker waits
        # keep their own generous bound — barrier waits are legitimately
        # long while a dead rank restarts.
        self._timeout = float(params.get("rabit_timeout_sec")
                              or os.environ.get("RABIT_TIMEOUT_SEC", 600))
        if self._timeout <= 0:
            self._timeout = None  # <=0 disables the timeout (like native)
        # Collective scratch budget: payloads larger than this stream
        # through the tree/ring in budget-sized chunks, so per-op scratch
        # is bounded by configuration, not payload size (reference:
        # rabit_reduce_buffer, src/allreduce_base.cc:31,117-132).
        self._reduce_buffer = parse_byte_size(
            params.get("rabit_reduce_buffer")
            or os.environ.get("RABIT_REDUCE_BUFFER", "256MB"))
        self.scratch_peak_bytes = 0
        def _size_or_zero(raw, default: int) -> int:
            if raw is None or str(raw).strip() == "":
                return default
            if str(raw).strip() == "0":
                return 0  # explicit disable (parse_byte_size rejects 0)
            return parse_byte_size(raw)

        def _param_or_env(key: str):
            # `params.get(k) or env` would drop an explicit integer 0 —
            # the documented "disable" value — so test None, not truth.
            raw = params.get(key)
            return raw if raw is not None else os.environ.get(key.upper())

        # Small-op coalescing budget for the async path (0 disables
        # fusion; async ops still overlap).  Buckets are collective ops,
        # so this MUST be uniform across ranks — which is why it is
        # never derived from rank-local knobs like rabit_reduce_buffer
        # (doc/performance.md).
        self._bucket_bytes = _size_or_zero(
            _param_or_env("rabit_bucket_bytes"), DEFAULT_BUCKET_BYTES)
        # Socket buffer sizes (SO_SNDBUF/SO_RCVBUF) for worker-worker
        # links; 0 keeps the kernel default, which silently caps ring
        # throughput on fat links (doc/performance.md).
        self._sock_buf = _size_or_zero(_param_or_env("rabit_sock_buf"), 0)
        # Schedule selection (doc/performance.md "Schedule selection").
        # Like the bucket budget, BOTH knobs decide collective behaviour
        # and must be uniform across ranks: every rank dispatches the
        # same (op, size, world) point to the same algorithm or the
        # peer patterns deadlock.
        raw = _param_or_env("rabit_sched")
        self._sched_name = (str(raw).strip().lower()
                            if raw not in (None, "") else "static")
        check(self._sched_name in sched_mod.MODES,
              "rabit_sched must be one of %s, got %r",
              "/".join(sched_mod.MODES), self._sched_name)
        raw = _param_or_env("rabit_ring_threshold_bytes")
        self._ring_threshold = (None if raw in (None, "")
                                else _size_or_zero(raw, None))
        # Sketch plan for the synthesized schedule (sched/synth.py):
        # an optional plan JSON carrying link costs / chunk count and
        # optionally a precomputed cycle from the offline CLI.  Like
        # rabit_sched it decides collective behaviour: every rank must
        # load IDENTICAL plan content or the synthesized peer patterns
        # diverge and deadlock.
        raw = _param_or_env("rabit_synth_plan")
        self._synth_plan = (sched_mod.load_plan(str(raw))
                            if raw not in (None, "") else None)
        raw = _param_or_env("rabit_tune_dir")
        self._tune_dir = str(raw) if raw not in (None, "") else None
        self._tuner = None
        if self._sched_name == "auto":
            if self._tune_dir:
                self._tuner = sched_mod.TuningCache.load(self._tune_dir)
            if self._tuner is None:
                # Loud in both miss shapes — unset dir and unusable
                # cache — or the user has no signal the tuner never
                # engaged and every op quietly rides static.
                self._log.info(
                    "rabit_sched=auto: %s; falling back to the static "
                    "crossover",
                    f"no usable tuning cache under {self._tune_dir}"
                    if self._tune_dir else "rabit_tune_dir not set")
        # Optional lossy wire formats (doc/performance.md "Quantized
        # wire codecs"): rabit_wire_codec selects bf16 (half bytes,
        # the historical rabit_wire_dtype=bf16 cast — that alias keeps
        # working but is deprecated) or the block-scaled int8/int4
        # codecs (2-4x fewer wire bytes, error-feedback compensated).
        # Like the schedule knobs, ALL codec config decides collective
        # behaviour and must be uniform across ranks.
        wire = str(params.get("rabit_wire_dtype")
                   or os.environ.get("RABIT_WIRE_DTYPE", "native")).lower()
        check(wire in ("native", "bf16"),
              "rabit_wire_dtype must be 'native' or 'bf16', got %r", wire)
        raw = _param_or_env("rabit_codec_block")
        self._codec_block = (int(raw) if raw not in (None, "")
                             else codec_mod.DEFAULT_BLOCK)
        self._codec_min_bytes = _size_or_zero(
            _param_or_env("rabit_codec_min_bytes"),
            codec_mod.DEFAULT_MIN_BYTES)
        # Which IMPLEMENTATION runs the block-scale hop math: the
        # compiled kernels (native/src/codec_kernels.c via the ctypes
        # seam) or the numpy reference.  Bit-identical by contract
        # (tests/test_native_codec.py), so unlike every knob above this
        # is NOT a collective decision — ranks may mix freely, and
        # auto's fallback on a toolchain-free box changes nothing but
        # speed.  The resolved label (native / numpy / numpy-fallback)
        # is surfaced per rank in /status and rabit_top so a silent
        # degrade is visible in one glance.
        self._codec_kernel, self._codec_impl = codec_mod.resolve_impl(
            _param_or_env("rabit_codec_impl"), log=self._log)
        self._codec = codec_mod.resolve(
            _param_or_env("rabit_wire_codec"), wire,
            self._codec_block, self._codec_min_bytes, log=self._log,
            kernel=self._codec_kernel)
        self._codec_label = (self._codec.name if self._codec is not None
                             else "none")
        self._codec_byname = {self._codec_label: self._codec}
        self._feedback = codec_mod.FeedbackBuffer()
        # Hop pipelining (doc/performance.md "Hop pipelining"): depth 1
        # disables (the legacy serial hop loop); the wire byte stream
        # is depth-independent, so unlike the codec/schedule knobs this
        # is a per-rank perf knob, not a collective decision — though
        # uniform values give uniform timing.
        raw = _param_or_env("rabit_pipeline_depth")
        self._pipe_depth = int(raw) if raw not in (None, "") else PIPE_DEPTH
        check(1 <= self._pipe_depth <= 64,
              "rabit_pipeline_depth must be in [1, 64], got %r",
              self._pipe_depth)
        self._pipe_chunk = _size_or_zero(
            _param_or_env("rabit_pipeline_chunk"), PIPE_CHUNK_BYTES)
        check(self._pipe_chunk > 0,
              "rabit_pipeline_chunk must be > 0")
        # Connect retry policy: a refused/timed-out dial (a peer merely
        # slow to listen, a tracker restarting) is retried with capped
        # exponential backoff + full jitter instead of killing the
        # worker on the first SYN (native analogue: ConnectRetry,
        # native/src/socket.cc).
        raw = _param_or_env("rabit_connect_retries")
        self._connect_retries = int(raw) if raw not in (None, "") else 4
        check(self._connect_retries >= 0,
              "rabit_connect_retries must be >= 0")
        raw = _param_or_env("rabit_backoff_base_ms")
        self._backoff_base_ms = float(raw) if raw not in (None, "") else 100.0
        check(self._backoff_base_ms > 0, "rabit_backoff_base_ms must be > 0")
        # Admission retry budget: a typed admission reject (multi-tenant
        # tracker at capacity) is re-registered with backoff this many
        # extra times — long enough for a finishing co-tenant job to
        # drain and free the slot — before a typed AdmissionError.
        raw = _param_or_env("rabit_admission_retries")
        self._admission_retries = int(raw) if raw not in (None, "") else 10
        check(self._admission_retries >= 0,
              "rabit_admission_retries must be >= 0")
        # Sharded control plane (rabit_directory / RABIT_DIRECTORY):
        # with a job directory configured, a REJECT_SHARD_MOVED redirect
        # re-targets the owning shard, and a dead tracker address is
        # re-resolved through the directory before the dial budget is
        # spent — shard failover reads as a bounded stall.  Without it,
        # nothing changes: the single-tracker wire stays byte-identical.
        raw = _param_or_env("rabit_directory")
        self._directory = None
        if raw not in (None, ""):
            from rabit_tpu.tracker.directory import DirectoryClient
            self._directory = DirectoryClient(str(raw).strip())
        raw = _param_or_env("rabit_shard_retries")
        self._shard_retries = int(raw) if raw not in (None, "") else 4
        check(self._shard_retries >= 0,
              "rabit_shard_retries must be >= 0")
        # Proactive liveness: send one keepalive per rabit_heartbeat_sec
        # on a persistent tracker connection (0 disables; the tracker's
        # miss budget is rabit_heartbeat_miss periods — doc/
        # fault_tolerance.md "Durable checkpoints & heartbeats").
        raw = _param_or_env("rabit_heartbeat_sec")
        self._hb_sec = float(raw) if raw not in (None, "") else 0.0
        check(self._hb_sec >= 0, "rabit_heartbeat_sec must be >= 0")
        cfg = obs.configure(params)
        self._obs_on = cfg.enabled
        self._obs_dir = cfg.obs_dir
        self._metrics = obs.Metrics()
        self._trace = obs.EventTrace(capacity=cfg.trace_capacity)
        if cfg.enabled:
            self._obs_flush_sec = cfg.flush_sec
            self._span_buf = obs.SpanBuffer()
            self._exporter = obs.DeltaExporter(self._metrics)
            if cfg.trace_sample:
                # Hop records ride the streaming frames, so sampling
                # without the live plane would trace into a void.
                self._trace_sample = cfg.trace_sample
                self._hop_buf = obs.HopBuffer()
        # The flight recorder is ALWAYS on (a ring append per op is the
        # whole cost) — with rabit_trace_dir set, fault paths persist it
        # for tools/postmortem.py.
        self._trace_dir = cfg.trace_dir
        self._flight = obs.FlightRecorder(capacity=cfg.flight_events)
        self._install_flight_sigterm()
        # Deterministic fault injection (rabit_chaos): the plan wraps
        # every socket touchpoint from the first rendezvous on.
        self._chaos = chaos_mod.configure(params, identity=self._task_id,
                                          on_inject=self._chaos_inject)
        # Integrity framing (doc/parameters.md "Transports";
        # doc/fault_tolerance.md "Links & integrity").  The default
        # keeps the wire byte-identical; framing is negotiated per
        # link at rendezvous.
        raw = _param_or_env("rabit_wire_integrity")
        integrity = (str(raw).strip().lower()
                     if raw not in (None, "") else "off")
        # Egress pacing (bench/test knob, doc/parameters.md): emulate a
        # constrained cross-host link budget on loopback so bandwidth-
        # regime measurements (wire codecs, schedule crossovers) run in
        # the regime they target.  0 (the default) = unpaced.
        raw = _param_or_env("rabit_link_mbps")
        link_mbps = float(raw) if raw not in (None, "") else 0.0
        cfg = tr.TransportConfig(integrity=integrity,
                                 link_mbps=link_mbps)
        self._lf = tr.LinkFactory(
            cfg, timeout=self._timeout, sock_buf=self._sock_buf,
            wrap=self._wrap_link, events=_TransportEvents(self),
            log=self._log)
        self._rendezvous(P.CMD_START)
        self._start_heartbeat()

    # Lower bound for waits on a REGISTERED tracker socket: rendezvous
    # replies legitimately wait out a dead rank's restart, so the
    # barrier keeps a generous floor even when rabit_timeout_sec is
    # tuned aggressively low for fast hung-peer detection.
    TRACKER_BARRIER_MIN_SEC = 600.0

    # Exponential backoff doubles up to this many times, so the delay
    # cap is rabit_backoff_base_ms * 2**5 = 32x the base.
    BACKOFF_CAP_DOUBLINGS = 5

    def _chaos_inject(self, kind: str, site: str, ordinal: int,
                      detail: str) -> None:
        """Plan callback: every injected fault is logged and (with
        telemetry on) counted + traced, so the tracker's merged
        obs_report timeline can pair each fault with the retry/recovery
        it forced."""
        self._log.info("chaos: injected %s at %s (#%d, %s)",
                       kind, site, ordinal, detail)
        if self._obs_on:
            self._metrics.counter("chaos.injected").inc()
            self._metrics.counter(f"chaos.injected.{kind}").inc()
            self._trace.emit("chaos", kind=kind, site=site, rank=self._rank,
                             ordinal=ordinal)

    def _backoff_delay_ms(self, attempt: int) -> float:
        """One capped-exponential-full-jitter backoff step:
        uniform(0, min(base * 2**(attempt-1), 32 * base)).  Full jitter
        (not a fixed schedule) so a world of workers hammering one
        rendezvous point decorrelates instead of thundering in lockstep.
        """
        base = self._backoff_base_ms
        cap_ms = base * (1 << min(attempt - 1, self.BACKOFF_CAP_DOUBLINGS))
        return random.uniform(0.0, cap_ms)

    def _backoff(self, site: str, attempt: int,
                 err: Optional[Exception],
                 max_ms: Optional[float] = None) -> None:
        """Sleep one backoff step before a connect retry, under the
        dial-level ``net.*`` telemetry (recover-rendezvous pacing has
        its own instruments — see robust.py).  ``max_ms`` clamps the
        sleep to a caller's remaining time budget."""
        delay_ms = self._backoff_delay_ms(attempt)
        if max_ms is not None:
            delay_ms = min(delay_ms, max(max_ms, 0.0))
        if self._obs_on:
            self._metrics.counter("net.connect.retries").inc()
            self._metrics.histogram("net.backoff.seconds").observe(
                delay_ms / 1000.0)
            self._trace.emit("net", phase="backoff", site=site,
                             rank=self._rank, attempt=attempt,
                             delay_ms=round(delay_ms, 3),
                             error=type(err).__name__ if err else None)
        self._log.debug("connect to %s failed (%s); retry #%d after "
                        "%.0f ms", site, err, attempt, delay_ms)
        time.sleep(delay_ms / 1000.0)

    def _dial_retry(self, addr: tuple[str, int], site: str,
                    chaos: bool = True) -> socket.socket:
        """Dial with retries: up to rabit_connect_retries + 1 attempts,
        backed off between failures, within ONE rabit_timeout_sec of
        total wall time — retrying must never multiply how long a dead
        peer can wedge a rendezvous round (each attempt's connect
        timeout shrinks to the remaining budget, so SYN-dropped hosts
        still fail in one timeout like the un-retried dial did, while
        instantly-refused dials get every attempt).  Raises LinkError
        (an OSError) carrying the last failure once either budget is
        spent."""
        attempts = self._connect_retries + 1
        deadline = (None if self._timeout is None
                    else time.monotonic() + self._timeout)
        last: Optional[OSError] = None
        made = 0
        for attempt in range(attempts):
            if attempt:
                # Budget check BEFORE the sleep (a retry past the
                # deadline would neither sleep honestly nor dial), and
                # the sleep itself is clamped to what's left.
                left_ms = (None if deadline is None
                           else (deadline - time.monotonic()) * 1000.0)
                if left_ms is not None and left_ms <= 0:
                    break
                self._backoff(site, attempt, last, max_ms=left_ms)
            remaining = None
            if deadline is not None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
            try:
                made += 1
                if chaos and self._chaos is not None:
                    self._chaos.connect(site)
                return socket.create_connection(addr, timeout=remaining)
            except OSError as e:
                last = e
                if self._obs_on:
                    self._metrics.counter("net.connect.failures").inc()
        raise LinkError(f"connect to {site} {addr[0]}:{addr[1]} failed "
                        f"after {made} attempt(s): {last}") from last

    def _redirect_tracker(self) -> bool:
        """Re-resolve this job's owning shard through the directory
        after a tracker failure; True when the target changed.  Covers
        every tracker touchpoint downstream of :meth:`_tracker_connect`
        — registrations, heartbeat re-dials, epoch polls and the
        shutdown goodbye all follow a shard failover to the survivor,
        so a handed-off job still closes its books as *finished*."""
        if self._directory is None:
            return False
        try:
            self._directory.invalidate()
            owner = self._directory.owner(self._job_id)
        except (OSError, ValueError) as e:
            self._log.debug("directory re-resolve failed: %s", e)
            return False
        if owner is None:
            return False
        idx, host, port = owner
        if (host, port) == self._tracker_addr:
            return False
        self._log.info("directory: job %r now owned by shard %d at "
                       "%s:%d", self._job_id, idx, host, port)
        if self._obs_on:
            self._metrics.counter("net.tracker.redirects").inc()
        self._tracker_addr = (host, port)
        return True

    def _tracker_connect(self, cmd: str, chaos: bool = True) -> socket.socket:
        # Connection ESTABLISHMENT honors rabit_timeout_sec (a dead or
        # unreachable tracker fails fast, like the link IO path) and
        # retries with backoff; the barrier wait after registration
        # keeps its own generous bound.  ``chaos=False`` exempts a dial
        # from fault injection: the heartbeat thread's dials interleave
        # nondeterministically with the op stream, and letting them
        # consult the plan would break the seed-replay contract.
        try:
            sock = self._dial_retry(self._tracker_addr,
                                    chaos_mod.SITE_TRACKER, chaos=chaos)
        except LinkError:
            # The shard may be dead, not restarting: ask the directory
            # who owns the job now, then spend one more dial budget on
            # the survivor.  Without a directory the failure stands.
            if not self._redirect_tracker():
                raise
            sock = self._dial_retry(self._tracker_addr,
                                    chaos_mod.SITE_TRACKER, chaos=chaos)
        sock.settimeout(None if self._timeout is None
                        else max(self._timeout, self.TRACKER_BARRIER_MIN_SEC))
        P.send_hello(sock, cmd, self._task_id, self._world_hint,
                     job=self._job_id)
        return sock

    def _rendezvous(self, cmd: str) -> None:
        """Register with the tracker, receive topology, wire up links."""
        self._close_links()
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.bind(("0.0.0.0", 0))
        self._listener.listen(64)
        my_port = self._listener.getsockname()[1]
        my_host = self._advertised_host()

        topo = self._register(cmd, my_host, my_port)

        self._rank = topo.rank
        self._world = topo.world
        self._epoch = topo.epoch
        self._relaunched = self._relaunched or bool(topo.relaunched)
        self._parent = topo.parent
        self._tree_links = list(topo.neighbors)
        self._ring_prev = topo.ring_prev
        self._ring_next = topo.ring_next
        # Host-group handout for the topology-aware schedules (one id
        # per rank; empty from a pre-sched tracker).
        self._groups = list(topo.groups)
        # Live adaptation handout: the controller's schedule directive
        # and demotion set (empty from a pre-adaptive tracker).
        demoted = frozenset(int(r) for r in topo.demoted)
        live = sched_mod.decode_directive(topo.sched)
        if live != self._sched_live or demoted != self._demoted:
            self._log.info("adaptive handout: sched=%r demoted=%s",
                           topo.sched, sorted(demoted))
            if self._obs_on:
                self._trace.emit("sched_directive", rank=self._rank,
                                 directive=topo.sched or None,
                                 demoted=sorted(demoted),
                                 epoch=self._epoch)
        self._sched_live = live
        self._demoted = demoted
        os.environ["RABIT_TPU_LOG_TAG"] = f"rank{self._rank}"
        self._lf.rank = self._rank   # the link hello carries it
        self._reconnect_links(topo)

    def _register(self, cmd: str, my_host: str,
                  my_port: int) -> P.TopologyReply:
        """One rendezvous registration with the tracker, retried whole.

        The single dial already carries the connect retry/backoff
        schedule; this loop additionally survives the tracker dying
        UNDER the exchange — mid-handshake, or while this worker sat
        parked in the barrier (the reply recv fails when the
        coordinator's sockets vanish).  A supervisor restarting the
        tracker on the same port (journal replayed) therefore costs the
        workers one backoff walk, not the job.  Exhausting the budget
        raises :class:`TrackerLostError` (a LinkError: the robust
        recover loop treats it like any dead link and gives it the
        recover-attempt budget on top).

        A typed ADMISSION reject (multi-tenant tracker at --max-jobs /
        --max-total-workers capacity) rides its own, separate budget
        (``rabit_admission_retries``): the tracker re-admits the moment
        a finishing job frees the slot, so each backoff walk re-polls
        admission rather than giving up — and an exhausted budget
        raises typed :class:`AdmissionError`, never a hang."""
        attempts = max(self._connect_retries + 1, 1)
        adm_attempts = max(self._admission_retries + 1, 1)
        last: Optional[OSError] = None
        net_tries = 0
        adm_tries = 0
        shard_tries = 0
        while True:
            sock = None
            reply: P.TopologyReply | P.RejectReply | None = None
            try:
                sock = self._tracker_connect(cmd)
                if self._chaos is not None:
                    # Control-plane chaos (hello site): an injected
                    # reset tears the registration exchange exactly
                    # where a dying shard would — detected below as a
                    # net.tracker.register_retries walk (the pairing
                    # the chaos gates assert).
                    kind = self._chaos.link(chaos_mod.SITE_HELLO)
                    if kind == chaos_mod.KIND_RESET:
                        raise ConnectionResetError(
                            "[chaos] injected hello reset")
                P.send_str(sock, my_host)
                P.send_u32(sock, my_port)
                reply = P.TopologyReply.recv_or_reject(sock)
            except OSError as e:
                last = e
                net_tries += 1
                if self._obs_on:
                    self._metrics.counter("net.tracker.register_retries"
                                          ).inc()
                if net_tries >= attempts:
                    raise TrackerLostError(
                        f"tracker {self._tracker_addr[0]}:"
                        f"{self._tracker_addr[1]} unreachable: "
                        f"registration (cmd={cmd}) failed "
                        f"{net_tries} time(s): {last}") from last
                self._log.info("tracker registration (cmd=%s) failed "
                               "(%s); re-registering (attempt %d/%d)",
                               cmd, e, net_tries + 1, attempts)
                self._backoff(chaos_mod.SITE_TRACKER, net_tries, e)
                continue
            finally:
                if sock is not None:
                    try:
                        sock.close()
                    except OSError:
                        pass
            if isinstance(reply, P.RejectReply) \
                    and reply.code == P.REJECT_SHARD_MOVED:
                # Sharded control plane: the job hashes to another
                # shard.  The reason carries the owner's generation and
                # endpoint — re-target without a directory round trip;
                # an old-format reason falls back to a full refresh.
                shard_tries += 1
                if self._obs_on:
                    self._metrics.counter("net.tracker.shard_redirects"
                                          ).inc()
                parsed = P.parse_shard_moved(reply.reason)
                if shard_tries > max(self._shard_retries, 0):
                    last_ep = (f"{parsed[2]}:{parsed[3]}" if parsed
                               else f"{self._tracker_addr[0]}:"
                                    f"{self._tracker_addr[1]}")
                    last_gen = parsed[0] if parsed else -1
                    raise ShardMovedError(
                        f"job {self._job_id!r} redirected "
                        f"{shard_tries} time(s) without landing on its "
                        f"owning shard (cmd={cmd}; last redirect: "
                        f"generation {last_gen}, endpoint {last_ep}): "
                        f"{reply.reason}",
                        generation=last_gen,
                        shard=parsed[1] if parsed else -1,
                        endpoint=last_ep)
                if parsed is not None:
                    gen, owner, host, port = parsed
                    self._log.info(
                        "tracker redirect: job %r owned by shard %d at "
                        "%s:%d (generation %d)", self._job_id, owner,
                        host, port, gen)
                    self._tracker_addr = (host, port)
                    if self._directory is not None:
                        self._directory.invalidate(gen)
                    if shard_tries >= 2:
                        # A second redirect in one walk means the
                        # membership is mid-flip (migration landing,
                        # leader failover): exponential full-jitter
                        # backoff so a world of redirected workers
                        # converges decorrelated instead of hammering
                        # each hop of a moving target in lockstep.
                        self._backoff(chaos_mod.SITE_TRACKER,
                                      shard_tries - 1, None)
                elif not self._redirect_tracker():
                    # No redirect payload and no directory to consult:
                    # back off and re-ask the same endpoint (its view
                    # may settle).
                    self._backoff(chaos_mod.SITE_TRACKER, shard_tries,
                                  None)
                continue
            if isinstance(reply, P.RejectReply):
                adm_tries += 1
                if self._obs_on:
                    self._metrics.counter("net.tracker.admission_rejects"
                                          ).inc()
                if reply.code == P.REJECT_BAD_HANDSHAKE:
                    # Not a capacity race: the tracker could not parse
                    # us (version/config skew) — retrying can't help.
                    raise AdmissionError(
                        f"tracker rejected the registration handshake "
                        f"(cmd={cmd}, job={self._job_id!r}): "
                        f"{reply.reason}",
                        code=reply.code, reason=reply.reason)
                if adm_tries >= adm_attempts:
                    raise AdmissionError(
                        f"job {self._job_id!r} refused admission "
                        f"{adm_tries} time(s) (cmd={cmd}): "
                        f"{reply.reason}",
                        code=reply.code, reason=reply.reason)
                self._log.info(
                    "tracker admission refused job %r (%s); backing off "
                    "and re-polling (attempt %d/%d)", self._job_id,
                    reply.reason, adm_tries + 1, adm_attempts)
                self._backoff(chaos_mod.SITE_TRACKER, adm_tries, None)
                continue
            return reply

    def _wrap_link(self, s: socket.socket, peer_rank: int):
        """Chaos interposition for an established link (after the
        handshake — connect-stage faults have their own sites)."""
        if self._chaos is None:
            return s
        return chaos_mod.ChaosSocket(s, self._chaos, peer_rank)

    def _reconnect_links(self, topo) -> None:
        """Wire the worker-worker links for a fresh topology.

        Outgoing dials (to lower ranks, already listening) honor
        rabit_timeout_sec AND the connect retry/backoff policy — during
        a rendezvous a peer is routinely slow to reach listen(), and
        one refused SYN must not kill the worker (native analogue:
        ConnectRetry, native/src/socket.cc).  Incoming accepts are
        bounded like the dials: a peer that died between its tracker
        reply and dialing us must surface as a timeout (-> rendezvous
        retry / fail-fast), not an unbounded accept() wedge.

        Each established socket is handed to the transport factory,
        which runs the link handshake (classic bytes under default
        config), negotiates integrity framing where configured, and
        applies the shared socket setup (rabit_sock_buf, TCP_NODELAY,
        timeout) on EVERY link creation path — first wiring and
        recovery re-dials alike.
        """
        for peer_rank, host, port in topo.connect:
            s = self._dial_retry((host, port), chaos_mod.SITE_CONNECT)
            self._links[peer_rank] = self._lf.dial(s, peer_rank)
        self._listener.settimeout(self._timeout)
        for _ in range(topo.naccept):
            if self._chaos is not None:
                self._chaos.connect(chaos_mod.SITE_ACCEPT)
            s, _addr = self._listener.accept()
            link, peer_rank = self._lf.accept(s)
            self._links[peer_rank] = link
        self._listener.close()
        self._listener = None

    def _advertised_host(self) -> str:
        # Single-host jobs (tests, local launcher) rendezvous via loopback;
        # multi-host workers advertise the interface that routes to the
        # tracker.
        from rabit_tpu.utils.net import routable_ip

        return routable_ip(self._tracker_addr)

    def _close_links(self) -> None:
        for s in self._links.values():
            try:
                s.close()
            except OSError:
                pass
        self._links.clear()
        if self._listener is not None:
            self._listener.close()
            self._listener = None

    # ------------------------------------------------------------------
    # heartbeat liveness channel
    # ------------------------------------------------------------------
    def _start_heartbeat(self) -> None:
        """One persistent CMD_HEARTBEAT connection, fed by a daemon
        thread: the tracker's deadline sweep turns missing beats into a
        dead verdict (and a supervisor kill) without any collective op
        having to touch the hung rank first.  A SIGSTOP'd process stops
        this thread with everything else — which is exactly the
        signal.

        The **live telemetry plane** rides the same connection: with
        telemetry streaming armed (``rabit_obs`` + a non-zero
        ``rabit_obs_flush_sec``) the thread also ships one obs frame
        (delta metrics + buffered spans) per flush period — and opens
        the channel even when heartbeats proper are off, with the flush
        period as the advertised beat period, since frames prove
        liveness exactly like beats."""
        streaming = (self._obs_on and self._obs_flush_sec > 0
                     and self._world > 1)
        if (self._hb_sec <= 0 and not streaming) \
                or self._tracker_addr is None:
            return
        self._hb_stop = threading.Event()
        self._hb_thread = threading.Thread(
            target=self._hb_loop, name="rabit-heartbeat", daemon=True)
        self._hb_thread.start()

    def _hb_period(self) -> float:
        return self._hb_sec if self._hb_sec > 0 else self._obs_flush_sec

    def _hb_dial(self) -> socket.socket:
        sock = self._tracker_connect(P.CMD_HEARTBEAT, chaos=False)
        P.send_u32(sock, max(int(self._hb_period() * 1000), 1))
        return sock

    def _hb_loop(self) -> None:
        sock: Optional[socket.socket] = None
        beat = 0
        sent: dict[int, float] = {}   # beat -> send time (rtt pairing)
        rbuf = bytearray()            # echo bytes straddling reads
        hb = self._hb_period()
        flush = (self._obs_flush_sec
                 if self._obs_on and self._obs_flush_sec > 0 else 0.0)
        now = time.monotonic()
        next_beat = now                # beat immediately at startup
        next_flush = now + flush if flush else None
        drops_row = 0                  # consecutive failed periods
        while True:
            now = time.monotonic()
            due = next_beat if next_flush is None \
                else min(next_beat, next_flush)
            if self._hb_stop.wait(max(due - now, 0.0)):
                break
            now = time.monotonic()
            try:
                if sock is None:
                    sock = self._hb_dial()
                    rbuf.clear()
                    sent.clear()
                    drops_row = 0
                    if self._obs_on:
                        self._metrics.counter("hb.connects").inc()
                if self._chaos is not None:
                    # Control-plane chaos (hb site): consult once per
                    # wake.  An injected reset drops the channel into
                    # the OSError path below (counted as hb.drops — the
                    # detection half of the pairing gate); the re-dial
                    # next period is the recovery under test.  Per-rule
                    # counters keep the other sites' schedules intact.
                    kind = self._chaos.link(chaos_mod.SITE_HB)
                    if kind == chaos_mod.KIND_RESET:
                        raise ConnectionResetError(
                            "[chaos] injected heartbeat reset")
                if now >= next_beat:
                    beat += 1
                    if flush:
                        sent[beat] = time.perf_counter()
                        while len(sent) > 64:  # bound: unechoed beats
                            sent.pop(min(sent))
                    P.send_u32(sock, beat)
                    if self._obs_on:
                        self._metrics.counter("hb.sent").inc()
                    next_beat = now + hb
                if next_flush is not None and now >= next_flush:
                    self._obs_send_frame(sock)
                    next_flush = now + flush
                if flush:
                    # Wait briefly for the just-sent beat's echo: an
                    # rtt sample recorded only at the NEXT wake would
                    # measure the loop period, not the round trip.
                    self._hb_drain_echoes(sock, sent, rbuf,
                                          wait_sec=min(0.25, hb / 4))
                    # Beats a non-echoing tracker (pre-obs) never
                    # answers must not pin the wait branch on forever:
                    # expire them after a few periods.
                    cut = time.perf_counter() - 4 * hb
                    for b in [b for b, t in sent.items() if t < cut]:
                        del sent[b]
            except OSError as e:
                # Tracker unreachable (restarting, mid-teardown): drop
                # the channel and re-dial next period — liveness is
                # best effort, never a reason to kill a healthy worker.
                # Pacing: push every deadline one period out so a dead
                # tracker never turns this loop into a re-dial spin.
                self._log.debug("heartbeat send/dial failed: %s", e)
                if self._obs_on:
                    self._metrics.counter("hb.drops").inc()
                if sock is not None:
                    try:
                        sock.close()
                    except OSError:
                        pass
                    sock = None
                drops_row += 1
                if drops_row >= 2:
                    # Two consecutive failed periods is a DEAD endpoint,
                    # not a restart blip: re-resolve the job's owner so
                    # a migrated/failed-over job's liveness channel
                    # follows it (one injected chaos reset can never
                    # reach here — a reset only fires on an open
                    # channel, i.e. right after a successful dial
                    # zeroed the run, so the seeded schedules and the
                    # injected↔detected pairing stay intact).
                    if self._redirect_tracker():
                        drops_row = 0
                now = time.monotonic()
                next_beat = now + hb
                if next_flush is not None:
                    next_flush = now + flush
        if sock is not None:
            try:
                if flush:
                    self._obs_send_frame(sock)  # final deltas + spans
                P.send_u32(sock, P.HEARTBEAT_BYE)  # clean shutdown
                sock.close()
            except OSError:
                pass

    def _obs_send_frame(self, sock: socket.socket) -> None:
        """Ship one delta frame + the buffered spans (wire format:
        protocol.HEARTBEAT_OBS, u32 length, JSON)."""
        obs.note_drops(self._metrics, self._trace)
        payload = {"rank": self._rank, "world": self._world,
                   "engine": type(self).__name__, "epoch": self._epoch,
                   # The wire codec (replicated config): keys the
                   # controller's online TuningCache merges, so
                   # schedule verdicts measured over a quantized wire
                   # never answer a full-width job.
                   "codec": self._codec_label,
                   # Which implementation runs the codec hop math
                   # (native / numpy / numpy-fallback): purely
                   # informational — bit-identical either way — but a
                   # silent fallback to numpy is a silent perf cliff,
                   # so /status and rabit_top surface it per rank.
                   "codec_impl": self._codec_impl,
                   # Send-side wall clock: with the hb-RTT estimate the
                   # tracker turns (arrival - ts - rtt/2) into a clock-
                   # offset sample, so assembled hop timelines survive
                   # cross-host clock skew (TraceAssembler.note_offset).
                   "ts": round(time.time(), 6)}
        payload.update(self._exporter.frame())
        spans = self._span_buf.drain()
        if spans:
            payload["spans"] = spans
        if self._span_buf.dropped:
            payload["spans_dropped"] = self._span_buf.dropped
        if self._hop_buf is not None:
            hops = self._hop_buf.drain()
            if hops:
                payload["hops"] = hops
            if self._hop_buf.dropped:
                payload["hops_dropped"] = self._hop_buf.dropped
        raw = json.dumps(payload).encode()
        # Pad to a u32 boundary (JSON tolerates trailing whitespace):
        # every frame then occupies whole 4-byte words, so a reader
        # that treats the stream as plain u32 beats — a pre-obs
        # tracker — stays ALIGNED and still recognizes the final
        # HEARTBEAT_BYE (no payload word can collide: ASCII JSON and
        # 0x20 padding never form 0xFFFFFFFF).
        raw += b" " * (-len(raw) % 4)
        sock.sendall(struct.pack("<II", P.HEARTBEAT_OBS, len(raw)) + raw)
        self._metrics.counter("obs.frames").inc()

    def _hb_drain_echoes(self, sock: socket.socket, sent: dict[int, float],
                         rbuf: bytearray,
                         wait_sec: float = 0.0) -> None:
        """Consume whatever beat echoes the tracker sent back and fold
        them into the ``hb.rtt.seconds`` histogram.  ``wait_sec``
        bounds how long to wait for the first echo (rtt is measured at
        READ time, so the wait right after a beat keeps the sample an
        actual round trip instead of a loop period); once nothing is
        outstanding or the budget is spent, reads go non-blocking.  A
        tracker that never echoes (pre-obs version) just yields no
        samples."""
        deadline = time.monotonic() + wait_sec
        while True:
            left = deadline - time.monotonic()
            if not sent:
                left = 0.0
            readable, _, _ = select.select([sock], [], [], max(left, 0.0))
            if not readable:
                return
            data = sock.recv(4096)
            if not data:
                raise ConnectionResetError("tracker closed the "
                                           "heartbeat channel")
            rbuf += data
            now = time.perf_counter()
            while len(rbuf) >= 4:
                (echo,) = struct.unpack_from("<I", rbuf)
                del rbuf[:4]
                t0 = sent.pop(echo, None)
                if t0 is not None:
                    self._metrics.histogram("hb.rtt.seconds").observe(
                        now - t0)

    def _stop_heartbeat(self) -> None:
        t = self._hb_thread
        if t is None:
            return
        self._hb_stop.set()
        t.join(timeout=5)
        self._hb_thread = None

    def shutdown(self) -> None:
        self._fence()
        self._stop_pump()
        self._stop_heartbeat()
        self._obs_flush()
        if self._tracker_addr is not None:
            try:
                sock = self._tracker_connect(P.CMD_SHUTDOWN)
                sock.close()
            except OSError as e:
                self._log.debug("shutdown notify failed (tracker gone?): %s",
                                e)
        self._close_links()

    # ------------------------------------------------------------------
    # telemetry (rabit_tpu.obs)
    # ------------------------------------------------------------------
    def stats(self) -> dict:
        if not self._obs_on or self._metrics is None:
            return {}  # disabled telemetry reports nothing (interface.py)
        return self._metrics.snapshot()

    def events(self) -> list[dict]:
        return self._trace.events() if self._trace is not None else []

    def _op_seqno(self) -> Optional[int]:
        """Robust-protocol seqno for op events (None on the base engine,
        which has no op numbering)."""
        return None

    def _op_done(self, kind: str, nbytes: int, t0: float,
                 replayed: bool = False) -> None:
        """Record one completed collective (call sites gate on _obs_on)."""
        dt = time.perf_counter() - t0
        obs.record_op(self._metrics, self._trace, kind, nbytes, dt,
                      self._rank, seqno=self._op_seqno(),
                      version=self._version, replayed=replayed)
        if self._span_buf is not None and not replayed:
            # Cross-rank span for the live plane: keyed (epoch,
            # version, seq, kind) so the tracker can merge the same op
            # across ranks.  The protocol seqno is the shared
            # coordinate on pyrobust; the base engine's op stream is
            # lockstep program order, so a per-engine running index
            # aligns the same way.  REPLAYED ops ship no span — a
            # relaunched rank re-serving (version, seq) minutes after
            # the survivors executed it would otherwise merge into
            # their group as a giant bogus lateness.
            seq = self._op_seqno()
            if seq is None:
                seq = self._span_seq
                self._span_seq += 1
            end = time.time()
            self._span_buf.add(
                seq, self._epoch, self._version, kind,
                self._op_sched if kind.startswith("allreduce") else None,
                nbytes, end - dt, end,
                # Per-op EFFECTIVE wire format: the tracker scopes the
                # controller's schedule evidence (and hence the tuner
                # merges) to the job's codec wire — an opted-out or
                # ineligible op's full-width measurement never answers
                # codec-keyed rows (span.py sched_costs).
                wire=(self._op_wire if kind.startswith("allreduce")
                      else "none"))

    def _obs_flush(self) -> None:
        """Ship the rank-local summary to the tracker's obs channel and
        dump the event trace under rabit_obs_dir (both best-effort; runs
        once, at the head of shutdown)."""
        if not self._obs_on:
            return
        obs.note_drops(self._metrics, self._trace)
        if self._tracker_addr is not None and self._world > 1:
            obs.ship_summary(
                self.tracker_print, self._log, type(self).__name__,
                self._rank, self._world, self._metrics.snapshot(),
                [e for e in self._trace.events()
                 if e.get("name") not in ("op", "sched", "span")],
                job=self._job_id)
        if self._obs_dir:
            obs.dump_events(self._log, self._obs_dir, self._rank,
                            self._trace.events())

    # ------------------------------------------------------------------
    # flight recorder (doc/observability.md "Causal tracing & postmortem")
    # ------------------------------------------------------------------
    def flight_persist(self, reason: str, **fields) -> Optional[str]:
        """Persist this rank's flight record (atomic, best effort;
        no-op without ``rabit_trace_dir``).  Public: the serving plane
        calls it on drain, supervisors may call it before teardown."""
        if self._flight is None or not self._trace_dir:
            return None
        return self._flight.persist(
            self._trace_dir, self._rank, reason, job=self._job_id,
            world=self._world, epoch=self._epoch,
            engine=type(self).__name__, **fields)

    def _install_flight_sigterm(self) -> None:
        """Chain a flight-record persist in front of whatever SIGTERM
        behaviour the process already has — a supervisor's kill then
        leaves forensics behind.  Only possible from the main thread
        (signal module rule); engines constructed elsewhere simply keep
        the LinkError/recovery persist paths."""
        if not self._trace_dir:
            return
        try:
            prev = signal.getsignal(signal.SIGTERM)

            def _on_term(signum, frame):
                self.flight_persist("sigterm")
                if callable(prev):
                    prev(signum, frame)
                else:
                    # Restore the default disposition and re-raise so
                    # the exit status still says "killed by SIGTERM".
                    signal.signal(signal.SIGTERM, signal.SIG_DFL)
                    os.kill(os.getpid(), signal.SIGTERM)

            signal.signal(signal.SIGTERM, _on_term)
        except ValueError:
            # Not the main thread of the main interpreter.
            self._log.debug("flight recorder: SIGTERM hook unavailable "
                            "off the main thread")

    # ------------------------------------------------------------------
    # identity
    # ------------------------------------------------------------------
    @property
    def rank(self) -> int:
        return self._rank

    @property
    def world_size(self) -> int:
        return self._world

    @property
    def was_relaunched(self) -> bool:
        return self._relaunched

    @property
    def epoch(self) -> int:
        """Membership epoch of the topology this engine runs under
        (bumped by the tracker per completed elastic rescale round)."""
        return self._epoch

    # One SHORT dial per commit-boundary epoch poll: the poll is
    # best-effort by contract, so it must never inherit the rendezvous
    # dial's retry schedule (up to rabit_timeout_sec — default 600 s —
    # against a SYN-dropping partitioned tracker, at EVERY commit on
    # EVERY rank).
    EPOCH_POLL_TIMEOUT_SEC = 2.0

    def _tracker_epoch_poll(self) -> Optional[tuple[int, int, int]]:
        """One-shot ``cmd=epoch`` membership poll: reports this rank's
        committed version, returns ``(epoch, target_epoch,
        target_world)`` — or None when the tracker is unreachable,
        which callers must read as "no change" (an elastic job keeps
        training through a coordinator outage; only rendezvous truly
        needs the tracker).  Dials raw with a short timeout and no
        retries — a restarting tracker costs a commit at most
        EPOCH_POLL_TIMEOUT_SEC, never the connect budget.  Chaos-exempt
        like the heartbeat channel: polls interleave with the op stream
        nondeterministically, so letting them consume the plan would
        break seed replay."""
        try:
            sock = socket.create_connection(
                self._tracker_addr, timeout=self.EPOCH_POLL_TIMEOUT_SEC)
        except OSError:
            return None
        try:
            sock.settimeout(self.EPOCH_POLL_TIMEOUT_SEC)
            P.send_hello(sock, P.CMD_EPOCH, self._task_id,
                         self._world_hint, job=self._job_id)
            P.send_u32(sock, self._version & 0xFFFFFFFF)
            return (P.recv_u32(sock), P.recv_u32(sock), P.recv_u32(sock))
        except OSError as e:
            self._log.debug("epoch poll failed (tracker restarting?): %s",
                            e)
            return None
        finally:
            try:
                sock.close()
            except OSError:
                pass

    def tracker_print(self, msg: str) -> None:
        # One-shot command connect, best effort by design: a tracker
        # that died after the last barrier must never turn a worker's
        # successful exit into a traceback — the message falls back to
        # the local stream instead (interface.py's default behaviour).
        try:
            sock = self._tracker_connect(P.CMD_PRINT)
            P.send_str(sock, msg)
            sock.close()
        except OSError as e:
            self._log.debug("tracker print failed (tracker gone?): %s", e)
            if not msg.startswith(obs.OBS_SUMMARY_PREFIX):
                print(f"@tracker[{self._rank}] {msg}", flush=True)

    # ------------------------------------------------------------------
    # link IO helpers (delegating to rabit_tpu/transport)
    # ------------------------------------------------------------------
    def _note_link_error(self, exc: LinkError) -> None:
        """Every LinkError lands in the flight recorder and (with
        ``rabit_trace_dir`` set) persists it: a surviving rank's record
        names the peer it was blocked on at the moment the world broke,
        which is exactly the evidence ``tools/postmortem.py`` votes the
        first-dead rank from.  Recovery itself handles the error as
        always."""
        peer = getattr(getattr(exc, "link", None), "peer", None)
        if self._flight is not None:
            self._flight.note("link_error", rank=self._rank, peer=peer,
                              error=type(exc).__name__,
                              detail=str(exc)[:160])
            self.flight_persist("link_error", peer=peer)

    def _send(self, rank: int, data: bytes | memoryview) -> None:
        try:
            self._links[rank].sendall(data)
        except LinkError as e:
            self._note_link_error(e)
            raise

    def _recv(self, rank: int, nbytes: int, into: memoryview | None = None):
        try:
            return self._links[rank].recv_exact(nbytes, into)
        except LinkError as e:
            self._note_link_error(e)
            raise

    def _sendv(self, rank: int, *parts) -> None:
        """Scatter-gather send: coalesce several buffers (header +
        payload, fused-op member blocks) into as few syscalls as the
        transport allows — the byte stream is identical to sequential
        ``sendall`` calls."""
        try:
            self._links[rank].sendv(parts)
        except LinkError as e:
            self._note_link_error(e)
            raise

    def _recv_all(self, ranks: list[int], nbytes: int,
                  bufs: list[memoryview]) -> None:
        """Multi-link pump: fill ``bufs[i][:nbytes]`` from ``ranks[i]``,
        draining every link concurrently (bytes are consumed in arrival
        order across links, so one slow child no longer serializes its
        siblings).  Callers merge in deterministic rank order afterwards
        — reduction order is unchanged."""
        try:
            tr.recv_all([self._links[r] for r in ranks], nbytes, bufs,
                        self._timeout)
        except LinkError as e:
            self._note_link_error(e)
            raise

    def _exchange(self, send_rank: int, send_data: memoryview,
                  recv_rank: int, recv_buf: memoryview) -> None:
        """Full-duplex: stream send_data to one peer while filling
        recv_buf from another — avoids ring deadlock without threads."""
        self._exchange_v(send_rank, [send_data], recv_rank, [recv_buf])

    def _exchange_v(self, send_rank: int, send_parts: list,
                    recv_rank: int, recv_parts: list) -> None:
        """Vectored full-duplex exchange: scatter-gather send of
        ``send_parts`` (no intermediate concatenation copy) while
        filling ``recv_parts`` in order.  The fused segmented-ring hot
        path moves every member's block through here."""
        try:
            tr.exchange(self._links[send_rank], send_parts,
                        self._links[recv_rank], recv_parts,
                        self._timeout)
        except LinkError as e:
            self._note_link_error(e)
            raise

    # ------------------------------------------------------------------
    # hop pipelining (doc/performance.md "Hop pipelining")
    # ------------------------------------------------------------------
    def _hop_exchange_merge(self, send_rank: int, sblk, recv_rank: int,
                            rbytes: int, cbytes: int, item: int,
                            merge, what: str = "hop") -> None:
        """One collective hop: stream ``sblk`` to ``send_rank`` while
        receiving ``rbytes`` from ``recv_rank`` in chunks, folding each
        received chunk via ``merge(coff, rl, src)`` (``rl`` bytes at
        hop byte-offset ``coff``).  This is the schedules' pipelined
        exchange+merge primitive: with ``rabit_pipeline_depth`` > 1 and
        a hop large enough to split, up to depth chunk exchanges stay
        in flight while earlier chunks merge — the NIC no longer idles
        during ``_wire_merge`` (or the codec's dequant/requant) and the
        CPU no longer idles during the wire.  Depth 1 (or a hop that
        fits one pipeline chunk) runs the legacy serial loop.  Results
        are bit-identical across depths: merges touch disjoint
        item-aligned ranges in the same order with the same values, and
        the per-link byte stream is depth-independent — mixed-depth
        peers interoperate.

        ``cbytes`` is the caller's reduce-buffer chunk budget; the
        pipeline sub-chunk is ``cbytes // depth`` floored at
        ``rabit_pipeline_chunk`` (item-aligned) — each chunk boundary
        is a sync point, so tiny chunks are never worth it — and the
        in-flight window is capped so its leases together never exceed
        the single-chunk budget: ``rabit_reduce_buffer`` stays an
        honest per-op scratch ceiling with the pipeline armed
        (``_note_scratch`` covers every lease).  Either side may be
        empty (the halving fold pre-step pipelines a recv-only drain).
        Ragged tails and zero-length sides take the same clamped
        sub-steps on both ends of every link."""
        slen = len(sblk)
        # Sampled-op tracing: one "hop" record per call (the op-local
        # hop index and the egress peer key the cross-rank timeline),
        # emitted on SUCCESS only — a hop that died leaves its evidence
        # in the flight recorder instead.
        traced = self._op_traced
        t_hop = time.perf_counter() if traced else 0.0
        depth = self._pipe_depth
        if depth > 1 and (slen or rbytes):
            pcb = min(cbytes, max(cbytes // depth, self._pipe_chunk))
            pcb = max(pcb - pcb % item, item)
            nsteps = max(-(-slen // pcb), -(-rbytes // pcb))
            # Window cap: the in-flight leases (window * pcb) must fit
            # the CONFIGURED budget — cbytes may be block-capped well
            # below it, and a floor-raised pcb may not divide it.
            window = min(depth, nsteps,
                         max(self._reduce_buffer // pcb, 1))
            if nsteps >= 2 and window >= 2:
                self._hop_pipelined(send_rank, sblk, recv_rank, rbytes,
                                    pcb, merge, nsteps, window, what)
                if traced:
                    self._trace_hop("hop", send_rank, max(slen, rbytes),
                                    time.perf_counter() - t_hop)
                return
        # Legacy serial hop loop (depth 1, or nothing to overlap):
        # exchange one chunk, merge it, repeat — byte-identical to the
        # pre-pipeline engine.
        nsteps = max(-(-slen // cbytes), -(-rbytes // cbytes), 0)
        if not nsteps:
            return
        lease = self._arena.take(min(cbytes, max(rbytes, 1)))
        self._note_scratch(len(lease))
        try:
            for ci in range(nsteps):
                coff = ci * cbytes
                sl = min(cbytes, max(slen - coff, 0))
                rl = min(cbytes, max(rbytes - coff, 0))
                self._exchange(send_rank, sblk[coff:coff + sl],
                               recv_rank, lease[:rl])
                if rl:
                    merge(coff, rl, lease[:rl])
        finally:
            self._arena.give(lease)
        if traced:
            self._trace_hop("hop", send_rank, max(slen, rbytes),
                            time.perf_counter() - t_hop)

    def _pipe_run(self, send_rank: int, recv_rank: int, what: str,
                  body) -> None:
        """Run ``body(pipe)`` under the choreography every pipelined
        hop shares: open (pump_begin may raise on a dead link), flush
        + restore on success, ABORT on any exception (framed backlog
        dropped — recovery rewires the links from scratch), and
        LinkError attribution through :meth:`_note_link_error`.  One
        copy of the discipline, used by :meth:`_hop_pipelined` and the
        fused segmented ring."""
        pipe = None
        try:
            try:
                pipe = tr.HopPipeline(self._links[send_rank],
                                      self._links[recv_rank],
                                      self._timeout, what)
                body(pipe)
                pipe.close()
            except BaseException:
                if pipe is not None:
                    pipe.abort()
                raise
        except LinkError as e:
            self._note_link_error(e)
            raise

    def _hop_pipelined(self, send_rank: int, sblk, recv_rank: int,
                       rbytes: int, pcb: int, merge, nsteps: int,
                       window: int, what: str) -> None:
        """The depth-window body of :meth:`_hop_exchange_merge`: chunk
        k merges while chunk k+1's exchange is in flight on the
        transport pump.  Scratch: one recv lease per window slot —
        chunk ci reuses lease ``ci % window``, safe because ci only
        pushes after ci-window (the slot's previous user) was popped
        and merged."""
        depth = window
        slen = len(sblk)
        lease_bytes = min(pcb, max(rbytes, 1))
        leases = [self._arena.take(lease_bytes) for _ in range(depth)]
        self._note_scratch(lease_bytes * depth)
        track = self._obs_on
        traced = self._op_traced
        t_overlap = 0.0

        def body(pipe) -> None:
            nonlocal t_overlap

            def pop_merge() -> None:
                nonlocal t_overlap
                coff, rl, li = pipe.pop()
                if not rl:
                    return
                if (track and pipe.inflight) or traced:
                    t0 = time.perf_counter()
                    merge(coff, rl, leases[li][:rl])
                    dt = time.perf_counter() - t0
                    if track and pipe.inflight:
                        t_overlap += dt
                    if traced:
                        # Per-chunk record: one pipelined merge window
                        # (shares the enclosing hop's index — the hop
                        # record files after the pipe drains).
                        self._trace_hop("chunk", recv_rank, rl, dt)
                else:
                    merge(coff, rl, leases[li][:rl])

            for ci in range(nsteps):
                if ci >= depth:
                    pop_merge()
                coff = ci * pcb
                sl = min(pcb, max(slen - coff, 0))
                rl = min(pcb, max(rbytes - coff, 0))
                pipe.push([sblk[coff:coff + sl]] if sl else [],
                          [leases[ci % depth][:rl]] if rl else [],
                          (coff, rl, ci % depth))
            while pipe.inflight:
                pop_merge()

        try:
            self._pipe_run(send_rank, recv_rank, what, body)
        finally:
            for lease in leases:
                self._arena.give(lease)
        if track:
            m = self._metrics
            m.counter("pipe.ops").inc()
            m.counter("pipe.chunks").inc(nsteps)
            m.gauge("pipe.chunks_inflight").set(min(depth, nsteps))
            m.gauge("pipe.scratch_bytes").set(lease_bytes * depth)
            m.histogram("pipe.overlap.seconds").observe(t_overlap)

    # ------------------------------------------------------------------
    # collectives
    # ------------------------------------------------------------------
    def allreduce(
        self,
        buf: np.ndarray,
        op: ReduceOp,
        prepare_fun: Optional[Callable[[], None]] = None,
        codec: bool = True,
    ) -> np.ndarray:
        self._fence()
        return self._allreduce_blocking(buf, op, prepare_fun, codec)

    def _allreduce_blocking(
        self,
        buf: np.ndarray,
        op: ReduceOp,
        prepare_fun: Optional[Callable[[], None]] = None,
        codec: bool = True,
    ) -> np.ndarray:
        """The blocking op body, also run (in issue order) by the async
        progress thread — which must not re-enter the fence.
        ``codec=False`` is the per-op precision opt-out: this op rides
        the classic full-width wire even with a lossy codec armed
        (program order, hence deterministic across ranks — like
        ``fuse=False``)."""
        if prepare_fun is not None:
            prepare_fun()
        if self._world == 1:
            return buf
        if not self._obs_on:
            self._allreduce_impl(buf, op, codec)
            return buf
        t0 = time.perf_counter()
        self._allreduce_impl(buf, op, codec)
        self._op_done("allreduce", buf.nbytes, t0)
        return buf

    def _wire_eligible(self, dtype, op: ReduceOp, nbytes: int = 1) -> bool:
        """Does an ELEMENTWISE wire codec (bf16) apply?  One predicate
        for the cast itself and for fused-member classification — the
        two must never disagree on which algorithm a payload rides.
        Block-scaled codecs answer False here (their wire elements are
        whole blocks, not castable member views — fused buckets take
        the concatenate path instead)."""
        c = self._codec
        return (c is not None and c.elementwise
                and c.eligible(dtype, op, nbytes))

    def _wire_cast(self, buf: np.ndarray, op: ReduceOp):
        """When the bf16 wire format applies to this op, return the
        (transport_u16_array, reduce_dtype) pair; else None (see
        codec/base.py — the cast itself now lives on the codec)."""
        if not self._wire_eligible(buf.dtype, op, buf.nbytes):
            return None
        return self._codec.encode(buf)

    def _solo_wire_nbytes(self, dtype, op: ReduceOp, nbytes: int) -> int:
        """TRUE wire bytes a solo dispatch of this payload would move:
        the codec's honest ratio (codec.wire_nbytes) — never a
        hardcoded per-format special case — so schedule selection and
        the adaptive controller account real bytes for every codec."""
        c = self._codec
        if c is not None and c.eligible(dtype, op, nbytes):
            return c.wire_nbytes(nbytes)
        return nbytes

    def _op_codec_for(self, nbytes: int) -> Optional["codec_mod.Codec"]:
        """The codec THIS dispatch rides: the job codec
        (``rabit_wire_codec``), unless the adaptive controller's live
        directive names a per-op override for the op's payload bucket
        (the ``bytes:sched/codec`` entry form — doc/performance.md
        "Online adaptation").  The directive is part of the replicated
        topology handout and the block/floor config is uniform, so the
        override is a collective decision exactly like the job codec;
        instances are built once and cached.  An unknown codec name
        (version skew) keeps the job codec, loudly, once.  Like the
        directive's schedule half, the override never applies over an
        explicitly forced ``rabit_sched=<name>`` (forced modes are the
        operator's pin; the replicated mode string keeps the skip a
        collective decision too)."""
        if not self._sched_live or self._sched_name not in ("static",
                                                            "auto"):
            return self._codec
        name = sched_mod.directive_codec(self._sched_live, nbytes)
        if name is None or name == self._codec_label:
            return self._codec
        got = self._codec_byname.get(name, False)
        if got is False:
            if name in codec_mod.CODECS or name in codec_mod.ALIASES:
                got = codec_mod.make(name, self._codec_block,
                                     self._codec_min_bytes,
                                     kernel=self._codec_kernel)
            else:
                self._log.info(
                    "directive codec %r is not in this engine's "
                    "vocabulary; the bucket keeps the job codec (%s)",
                    name, self._codec_label)
                got = self._codec
            self._codec_byname[name] = got
        return got

    def _wire_merge(self, op: ReduceOp, rflat: np.ndarray, e0: int,
                    ne: int, src: np.ndarray,
                    record: bool = True) -> None:
        """The schedules' single reduction primitive: fold ``ne``
        received elements into ``rflat[e0:e0+ne]``.  Classic and
        elementwise-codec ops reduce with ``apply_op_numpy`` in the
        schedule's red dtype; under an armed block-scaled codec the
        elements ARE encoded blocks and the codec's
        dequantize→accumulate→requantize merge runs instead, recording
        the requantization residual at the matching positions (``e0``
        is the absolute element offset within the full wire array).
        ``record=False`` merges identically but skips the residual
        ledger — for schedules whose pairings run the same merge on
        BOTH sides (swing), where recording twice would double the
        error-feedback correction for one quantization event.

        Codec hop math (both impls) is timed into ``_op_ck_time`` so
        the obs plane can report per-op codec kernel seconds
        (``codec.kernel.seconds``) — the honest kernel-vs-numpy A/B
        coordinate.  Classic full-width merges stay untimed."""
        c = self._op_codec
        if c is None:
            k = self._op_elem_k
            if k is not None and ne:
                # Armed native bf16 elementwise merge: the same
                # upcast-add-RNE ml_dtypes performs, compiled.
                t0 = time.perf_counter()
                k.bf16_merge(ck_mod.pu16(rflat[e0:e0 + ne]),
                             ck_mod.pu16(src), ne)
                self._op_ck_time += time.perf_counter() - t0
                return
            if self._op_wire != "none":
                t0 = time.perf_counter()
                apply_op_numpy(op, rflat[e0:e0 + ne], src[:ne])
                self._op_ck_time += time.perf_counter() - t0
                return
            apply_op_numpy(op, rflat[e0:e0 + ne], src[:ne])
        else:
            t0 = time.perf_counter()
            c.merge(self._op_cstate, rflat, e0, ne, src, record)
            self._op_ck_time += time.perf_counter() - t0

    def _allreduce_impl(self, buf: np.ndarray, op: ReduceOp,
                        codec_ok: bool = True) -> None:
        """One allreduce through the wire, instrumented for forensics:
        the flight recorder learns what's in flight (kind, seqno,
        epoch, version — cleared only on success, so a fault-path
        persist names the op the world died in), and on a sampled op
        (``rabit_trace_sample``) the hop/chunk/codec-window trace
        records arm.  Both keys are deterministic in the op seqno
        (protocol seqno on pyrobust, the lockstep op index here), so
        every rank traces the SAME ops.  Shared with the robust layer's
        retry path — a replayed op re-arms, its wire work is real."""
        seq = self._op_seqno()
        if seq is None:
            seq = self._op_count
            self._op_count += 1
        fl = self._flight
        if fl is not None:
            fl.op_begin("allreduce", seq, self._epoch, self._version,
                        buf.nbytes)
        if self._hop_buf is not None \
                and obs.trace_sampled(seq, self._trace_sample):
            self._op_traced = True
            self._op_trace_key = (seq, self._epoch, self._version,
                                  "allreduce")
            self._hop_idx = 0
            try:
                self._allreduce_wire(buf, op, codec_ok)
            finally:
                self._op_traced = False
        else:
            self._allreduce_wire(buf, op, codec_ok)
        if fl is not None:
            fl.op_end()

    def _trace_hop(self, phase: str, peer: int, nbytes: int,
                   dt: float) -> None:
        """File one hop/chunk/codec-window record for the armed op
        (callers gate on ``_op_traced``).  Stamped like spans: wall
        clock at END minus the perf_counter-measured duration."""
        seq, epoch, version, kind = self._op_trace_key
        hop = self._hop_idx
        if phase == "hop":
            self._hop_idx = hop + 1
        end = time.time()
        self._hop_buf.add(seq, epoch, version, kind, hop, peer, phase,
                          nbytes, end - dt, end)

    def _allreduce_wire(self, buf: np.ndarray, op: ReduceOp,
                        codec_ok: bool = True) -> None:
        """Uninstrumented schedule dispatch (shared with the robust
        layer's retry path, which does its own accounting), wrapped in
        the wire-codec window when one applies.  ``codec_ok=False`` is
        the per-op precision escape hatch (api ``codec=False``).

        Block-scaled path: encode (carried residual added in) → the
        structured wire array rides ANY schedule (dispatch sees the
        true wire bytes) with merges routed through _wire_merge →
        decode + transactional feedback commit.  A LinkError escapes
        BEFORE the commit, so pyrobust's retry re-encodes identical
        bytes from the pristine buffer."""
        c = self._op_codec_for(buf.nbytes)
        if c is None or not codec_ok \
                or not c.eligible(buf.dtype, op, buf.nbytes):
            # Classic full-width wire — including per-op opt-outs and
            # ineligible ops in a codec-armed job, whose tuner picks
            # must answer from the full-width rows, never the codec's.
            self._op_wire = "none"
            self._allreduce_dispatch(buf, op, pick_codec="none")
            return
        self._op_wire = c.name  # span label: this op rode the codec
        traced = self._op_traced  # codec windows of a sampled op
        self._op_ck_time = 0.0  # per-op codec hop-math seconds
        if c.elementwise:
            t0 = time.perf_counter() if traced else 0.0
            w, red = c.encode(buf)
            if traced:
                self._trace_hop("encode", -1, buf.nbytes,
                                time.perf_counter() - t0)
            # Arm the compiled bf16 merge for this window only
            # (eligibility already pinned op == SUM): the schedules'
            # elementwise merges run the same upcast-add-RNE the
            # ml_dtypes path performs, bit for bit.
            if self._codec_kernel is not None and c.name == "bf16":
                self._op_elem_k = self._codec_kernel
            try:
                self._allreduce_dispatch(w, op, red,
                                         logical_nbytes=buf.nbytes,
                                         pick_codec=c.name)
            finally:
                self._op_elem_k = None
            t0 = time.perf_counter() if traced else 0.0
            buf.reshape(-1)[:] = c.decode(w, red)
            if traced:
                self._trace_hop("decode", -1, buf.nbytes,
                                time.perf_counter() - t0)
            self._note_codec_op(c, buf.nbytes, w.nbytes)
            return
        flat = buf.reshape(-1)
        t0 = time.perf_counter()
        state = c.begin(flat, self._feedback)
        dt = time.perf_counter() - t0
        self._op_ck_time += dt
        if traced:
            self._trace_hop("encode", -1, flat.nbytes, dt)
        self._op_codec, self._op_cstate = c, state
        try:
            self._allreduce_dispatch(state.wire, op,
                                     logical_nbytes=flat.nbytes,
                                     pick_codec=c.name)
        finally:
            self._op_codec, self._op_cstate = None, None
        t0 = time.perf_counter()
        res = c.finish(state, flat, self._feedback)
        dt = time.perf_counter() - t0
        self._op_ck_time += dt
        if traced:
            self._trace_hop("decode", -1, flat.nbytes, dt)
        self._note_codec_op(c, flat.nbytes, state.wire.nbytes, res)

    def _note_codec_op(self, c, logical: int, wire: int,
                       res: Optional[np.ndarray] = None) -> None:
        """Codec telemetry: bytes saved, compression ratio, the
        error-feedback norm and the per-op codec kernel time (hop math
        seconds, either implementation — the kernel-vs-numpy A/B
        coordinate), live-streamed like every other counter.  The
        ``codec.impl.native`` gauge makes a silent numpy fallback
        visible wherever metrics land (rabit_top, /status)."""
        if not self._obs_on:
            return
        m = self._metrics
        m.counter("codec.ops").inc()
        m.counter(f"codec.ops.{c.name}").inc()
        m.counter("codec.bytes.logical").inc(logical)
        m.counter("codec.bytes.wire").inc(wire)
        m.counter("codec.bytes_saved").inc(max(logical - wire, 0))
        if logical:
            m.gauge("codec.ratio").set(round(wire / logical, 4))
        m.gauge("codec.impl.native").set(
            1 if self._codec_kernel is not None else 0)
        m.histogram("codec.kernel.seconds").observe(self._op_ck_time)
        if res is not None and res.size:
            m.histogram("codec.feedback.norm").observe(
                float(np.abs(res).mean()))

    # ------------------------------------------------------------------
    # schedule selection (rabit_tpu/sched/)
    # ------------------------------------------------------------------
    def _ring_crossover(self) -> int:
        """Static tree/ring byte crossover: the configured
        rabit_ring_threshold_bytes, else the module default (kept as a
        module global so tests/benches can pin it process-wide)."""
        return (self._ring_threshold if self._ring_threshold is not None
                else TREE_RING_CROSSOVER_BYTES)

    def _static_schedule(self, nbytes: int) -> "sched_mod.Schedule":
        if nbytes <= self._ring_crossover() or self._world == 2:
            return sched_mod.TREE
        return sched_mod.RING

    def _pick_schedule(self, nbytes: int, op: ReduceOp,
                       logical_nbytes: Optional[int] = None,
                       pick_codec: str = "none") -> "sched_mod.Schedule":
        """Resolve the schedule for one dispatch point.  Every input is
        replicated across ranks (payload size, op, world, topology
        handout, the uniform rabit_sched/threshold/tuning-cache config),
        so all ranks pick the same algorithm — a collective decision,
        like bucket boundaries.

        Two size domains, deliberately distinct: ``nbytes`` is the TRUE
        wire size (what the static crossover and ``applies()`` reason
        about), while the MEASUREMENT lookups — the live directive and
        the tuning cache — key by ``logical_nbytes``, because spans
        (`_op_done`) and bench rows (collectives_bench's per-size
        table) both record logical payload sizes.  ``pick_codec`` is
        THIS op's effective wire format: a ``codec=False`` or
        ineligible op in an int8 job answers from the full-width rows,
        never the codec's."""
        logical = logical_nbytes if logical_nbytes is not None else nbytes
        name = self._sched_name
        if self._sched_live and name in ("static", "auto"):
            # Live directive from the tracker's adaptive controller:
            # the freshest measurement wins over the static crossover
            # and the offline cache — but never over an explicitly
            # FORCED schedule name, and only where it applies (the
            # fallback below keeps a stale directive from deadlocking).
            # Codec-scoped like the cache: a plain entry's evidence was
            # measured on the JOB's codec wire, a slashed
            # ``name/codec`` entry on its OWN named wire (which
            # ``_op_codec_for`` armed for this op) — either way the
            # entry answers only ops riding the wire it measured, so a
            # full-width opt-out/ineligible op — moving 2-4x the real
            # bytes — skips it and answers from its own format's rows.
            pick, dcodec = sched_mod.directive_entry(self._sched_live,
                                                     logical)
            want = dcodec if dcodec is not None else self._codec_label
            if pick is not None and pick_codec == want:
                s = sched_mod.SCHEDULES.get(pick)
                if s is not None and s.applies(self, nbytes):
                    return s
        if name == "static":
            return self._static_schedule(nbytes)
        if name == "auto":
            pick = (self._tuner.pick("allreduce", logical, self._world,
                                     codec=pick_codec)
                    if self._tuner is not None else None)
            s = sched_mod.SCHEDULES.get(pick) if pick else None
            if s is not None and s.applies(self, nbytes):
                return s
            return self._static_schedule(nbytes)
        s = sched_mod.SCHEDULES[name]
        if s.applies(self, nbytes):
            return s
        return self._static_schedule(nbytes)

    def set_schedule(self, name: str) -> None:
        """Switch the selection mode at runtime (bench/tests hook).
        Like rabit_sched itself, the value MUST be uniform across ranks
        and changed only between collectives."""
        check(name in sched_mod.MODES,
              "schedule must be one of %s, got %r",
              "/".join(sched_mod.MODES), name)
        self._sched_name = name

    def _allreduce_dispatch(self, buf: np.ndarray, op: ReduceOp,
                            red_dtype=None,
                            logical_nbytes: Optional[int] = None,
                            pick_codec: str = "none") -> None:
        if buf.nbytes == 0:
            self._op_sched = None  # no wire phase: no schedule label
            return  # zero-size payloads move no wire bytes anywhere
        s = self._pick_schedule(buf.nbytes, op, logical_nbytes,
                                pick_codec)
        self._op_sched = s.name  # span label for the live plane
        if self._obs_on:
            self._metrics.counter(f"sched.pick.{s.name}").inc()
            self._metrics.counter(f"sched.pick.{s.name}.bytes").inc(
                buf.nbytes)
            if s.name != self._last_sched:
                # Trace on choice CHANGE only: per-op spans already
                # carry the stream, and flooding the bounded ring
                # buffer with one event per dispatch would evict them.
                self._trace.emit("sched", sched=s.name, nbytes=buf.nbytes,
                                 rank=self._rank, world=self._world,
                                 mode=self._sched_name)
                self._last_sched = s.name
        s.run(self, buf, op, red_dtype)

    def _children(self) -> list[int]:
        return [r for r in self._tree_links if r != self._parent]

    def _note_scratch(self, nbytes: int) -> None:
        if nbytes > self.scratch_peak_bytes:
            self.scratch_peak_bytes = nbytes

    def _drain_merge(self, peers: list[int], nitems: int, item: int,
                     merge, after_chunk=None) -> int:
        """Chunked concurrent drain-and-merge from ``peers``, the
        deadlock-sensitive inner pump shared by the tree collective and
        the hierarchical schedule's leader phase.

        Peers drain CONCURRENTLY through the transport pump (one slow
        peer no longer serializes its sibling), but merges stay in
        fixed peer order so the reduction order — and hence every
        result bit — matches the sequential protocol.  The
        rabit_reduce_buffer chunk budget divides across the peer
        buffers (chunk size never changes the per-link byte stream, so
        mixed-budget peers still interoperate); ``merge(off, n, src)``
        folds ``n`` items of received bytes ``src`` into the payload at
        item offset ``off``, and ``after_chunk(off, n)`` runs once per
        chunk window after its merges (the tree pump forwards the
        merged window to its parent there).  Returns the chunk size so
        callers can stream a symmetric follow-up phase.
        """
        denom = item * max(len(peers), 1)
        chunk = min(max(self._reduce_buffer // denom, 1), nitems)
        leases = [self._arena.take(chunk * item) for _ in peers]
        # scratch_peak reports the chunked working-set BUDGET (floored
        # at one chunk): peer-less ranks lease no scratch, but still
        # stream through chunk-sized windows, and the pre-existing
        # `0 < peak <= budget` contract (tests/workers/
        # check_reduce_buffer.py) holds on every rank.
        self._note_scratch(chunk * item * max(len(peers), 1))
        try:
            for off in range(0, nitems, chunk):
                n = min(chunk, nitems - off)
                if len(peers) == 1:
                    self._recv(peers[0], n * item, leases[0][: n * item])
                elif peers:
                    self._recv_all(peers, n * item, leases)
                for ci in range(len(peers)):
                    merge(off, n, leases[ci][: n * item])
                if after_chunk is not None:
                    after_chunk(off, n)
        finally:
            for lease in leases:
                self._arena.give(lease)
        return chunk

    def _tree_chunked(self, view: memoryview, nitems: int, item: int,
                      merge) -> None:
        """Two-phase chunked tree collective, shared by the built-in and
        custom allreduce paths.

        Chunked to the rabit_reduce_buffer budget in two strictly
        one-directional phases (all chunks up, then all chunks down):
        blocking sockets cannot deadlock, chunks stream across tree
        levels, and the per-link byte stream matches the unchunked
        protocol, so peers with different budgets interoperate.
        ``merge(off, n, src)`` folds ``n`` items of received bytes
        ``src`` into the payload at item offset ``off``.

        Sampled-op tracing files one "hop" record per phase (up-drain,
        down-broadcast), keyed by the parent link — the link a non-root
        rank actually waits on in both phases; the root keys by its
        first child (the link its pump drives).  Small worlds default
        to this schedule, so the causal timeline covers them too.
        """
        children = self._children()
        traced = self._op_traced
        hop_peer = self._parent if self._parent != P.NONE else (
            children[0] if children else -1)
        t_ph = time.perf_counter() if traced else 0.0
        send_up = None
        if self._parent != P.NONE:
            def send_up(off: int, n: int) -> None:
                self._send(self._parent,
                           view[off * item:(off + n) * item])
        # Phase 1: reduce up.
        chunk = self._drain_merge(children, nitems, item, merge,
                                  after_chunk=send_up)
        if traced:
            self._trace_hop("hop", hop_peer, nitems * item,
                            time.perf_counter() - t_ph)
            t_ph = time.perf_counter()
        # Phase 2: broadcast down.
        for off in range(0, nitems, chunk):
            n = min(chunk, nitems - off)
            if self._parent != P.NONE:
                self._recv(self._parent, n * item,
                           view[off * item:(off + n) * item])
            for r in children:
                self._send(r, view[off * item:(off + n) * item])
        if traced:
            self._trace_hop("hop", hop_peer, nitems * item,
                            time.perf_counter() - t_ph)

    def _tree_allreduce(self, buf: np.ndarray, op: ReduceOp,
                        red_dtype=None) -> None:
        """Reduce up the binary tree, broadcast the result down.

        ``red_dtype`` decouples the element type the merge runs in from
        the transport array's dtype (the bf16 wire path moves uint16
        bytes but reduces in bf16); None means they coincide.
        """
        flat = buf.reshape(-1)
        if flat.nbytes == 0:
            return  # zero-size payloads move no wire bytes on any rank
        red = red_dtype if red_dtype is not None else flat.dtype
        rflat = flat.view(red)

        def merge(off: int, n: int, src: memoryview) -> None:
            self._wire_merge(op, rflat, off, n,
                             np.frombuffer(src, dtype=red, count=n))

        self._tree_chunked(memoryview(flat).cast("B"), len(flat),
                           flat.itemsize, merge)

    def _ring_allreduce(self, buf: np.ndarray, op: ReduceOp,
                        red_dtype=None) -> None:
        """Bandwidth-optimal ring (the pump itself lives in
        rabit_tpu/sched/ring.py, generalized to sub-rings for the
        hierarchical schedule's leader phase)."""
        sched_mod.ring_allreduce(self, buf, op, red_dtype)

    def allreduce_custom(self, buf: np.ndarray, reducer, prepare_fun=None
                         ) -> np.ndarray:
        """Tree-fold custom allreduce: the Python ``reducer(dst, src)``
        merges per tree edge, O(log n) payload hops — replacing the
        interface's allgather-and-fold default (O(world x payload)), and
        matching the native engine's TreeAllreduceFn shape on the wire
        (reference analogue: ReduceHandle, include/rabit/engine.h:
        215-253).  Chunked row-wise to the reduce-buffer budget like
        _tree_allreduce; the reducer must be associative+commutative
        (merge order is tree order).
        """
        self._fence()
        return self._allreduce_custom_blocking(buf, reducer, prepare_fun)

    def _allreduce_custom_blocking(self, buf: np.ndarray, reducer,
                                   prepare_fun=None) -> np.ndarray:
        if prepare_fun is not None:
            prepare_fun()
        if self._world == 1:
            return buf
        if not self._obs_on:
            return self._allreduce_custom_impl(buf, reducer)
        t0 = time.perf_counter()
        out = self._allreduce_custom_impl(buf, reducer)
        self._op_done("allreduce_custom", buf.nbytes, t0)
        return out

    def _allreduce_custom_impl(self, buf: np.ndarray, reducer) -> np.ndarray:
        # Custom allreduces always ride the tree fold — label the span
        # honestly instead of leaking the previous dispatch's choice.
        # Never codec'd: the Python reducer owns the byte semantics.
        self._op_sched = "tree"
        self._op_wire = "none"
        rows = buf.shape[0] if buf.ndim > 0 else buf.size
        check(rows > 0, "allreduce_custom: empty buffer")
        if buf.nbytes == 0:
            return buf  # zero-size rows: nothing to merge or move
        row_shape = buf.shape[1:] if buf.ndim > 1 else ()
        flat = buf.reshape(rows, -1)
        item = flat.shape[1] * flat.itemsize  # bytes per axis-0 row
        dst_rows = buf.reshape((rows,) + row_shape)

        def merge(off: int, n: int, src: memoryview) -> None:
            rows_in = np.frombuffer(src, dtype=buf.dtype,
                                    count=n * flat.shape[1])
            reducer(dst_rows[off:off + n], rows_in.reshape((n,) + row_shape))

        self._tree_chunked(memoryview(flat).cast("B"), rows, item, merge)
        return buf

    def broadcast(self, data: Optional[bytes], root: int) -> bytes:
        self._fence()
        return self._broadcast_blocking(data, root)

    def _broadcast_blocking(self, data: Optional[bytes], root: int) -> bytes:
        if self._world == 1:
            check(data is not None, "broadcast: root rank must supply data")
            return data
        if not self._obs_on:
            return self._bcast_impl(data, root)
        t0 = time.perf_counter()
        out = self._bcast_impl(data, root)
        self._op_done("broadcast", len(out), t0)
        return out

    def _bcast_impl(self, data: Optional[bytes], root: int) -> bytes:
        """Uninstrumented tree flood (also the robust layer's recovery
        serving transport, which must not count as a user op)."""
        if self._rank == root:
            check(data is not None, "broadcast: root rank must supply data")
            header = struct.pack("<Q", len(data))
            view = memoryview(data)
            # Header + first chunk coalesce into one scatter-gather
            # write per link (the payload is resident at the root);
            # the byte stream per link is unchanged.
            for r in self._tree_links:
                self._sendv(r, header, view[:CHUNK_BYTES])
            for off in range(CHUNK_BYTES, len(data), CHUNK_BYTES):
                chunk = view[off:off + CHUNK_BYTES]
                for r in self._tree_links:
                    self._send(r, chunk)
            return data
        # Non-root: the payload arrives on exactly one tree link — the
        # first hop on the tree path toward the root, computable locally
        # (no probing needed, unlike the reference's in-link slot scan).
        # Chunk-pipelined: each chunk is forwarded downstream as soon as
        # it arrives, so the payload streams through the tree instead of
        # paying full-payload latency per level (same idea as the
        # reference's per-link ring buffers, src/allreduce_base.cc:
        # 500-588; byte stream per link is unchanged).
        src = self._toward(root)
        raw = self._recv(src, 8)
        (size,) = struct.unpack("<Q", bytes(raw))
        payload = memoryview(bytearray(size))
        header = struct.pack("<Q", size)
        downstream = [r for r in self._tree_links if r != src]
        for r in downstream:
            self._send(r, header)
        for off in range(0, size, CHUNK_BYTES):
            end = min(off + CHUNK_BYTES, size)
            self._recv(src, end - off, payload[off:end])
            for r in downstream:
                self._send(r, payload[off:end])
        return bytes(payload)

    def _toward(self, root: int) -> int:
        """First hop on the binary-heap-tree path from this rank to ``root``.

        Walk ``root``'s ancestor chain (indices strictly decrease); if it
        passes through this rank, the hop is the child we came through,
        else it is our parent.
        """
        r, prev = root, P.NONE
        while r > self._rank:
            prev = r
            r = (r - 1) // 2
        return prev if r == self._rank else self._parent

    def allgather(self, buf: np.ndarray) -> np.ndarray:
        self._fence()
        return self._allgather_blocking(buf)

    def _allgather_blocking(self, buf: np.ndarray) -> np.ndarray:
        if self._world == 1:
            return buf[None]
        if not self._obs_on:
            return self._allgather_impl(buf)
        t0 = time.perf_counter()
        out = self._allgather_impl(buf)
        self._op_done("allgather", out.nbytes, t0)
        return out

    def _allgather_impl(self, buf: np.ndarray) -> np.ndarray:
        """Ring all-gather: n-1 steps, each forwarding the newest block."""
        n = self._world
        out = np.empty((n,) + buf.shape, dtype=buf.dtype)
        out[self._rank] = buf
        for s in range(n - 1):
            send_b = (self._rank - s) % n
            recv_b = (self._rank - s - 1) % n
            self._exchange(
                self._ring_next, memoryview(out[send_b]).cast("B"),
                self._ring_prev, memoryview(out[recv_b]).cast("B"))
        return out

    # ------------------------------------------------------------------
    # async collectives: progress thread + small-op bucket fusion
    # ------------------------------------------------------------------
    # One background progress thread owns the links while async ops are
    # in flight; queued ops run strictly in issue order, so the wire (and
    # any robust-protocol layer above) sees exactly the op sequence a
    # blocking caller would produce.  Blocking entry points _fence()
    # first, which also flushes the coalescing bucket — mixing the two
    # styles is always safe, never reordered.

    def _ensure_pump(self) -> None:
        if self._aq_thread is None:
            self._aq_thread = threading.Thread(
                target=self._pump, name="rabit-async-pump", daemon=True)
            self._aq_thread.start()

    def _stop_pump(self) -> None:
        t = self._aq_thread
        if t is None:
            return
        with self._aq_cv:
            self._aq.append(None)
            self._aq_cv.notify_all()
        t.join(timeout=30)
        self._aq_thread = None

    def _pump(self) -> None:
        try:
            while True:
                with self._aq_cv:
                    while not self._aq:
                        self._aq_cv.wait()
                    item = self._aq.popleft()
                if item is None:
                    return
                fn, handles = item
                try:
                    fn()
                except Exception as e:  # noqa: BLE001 — surfaces at wait()
                    self._async_fail(e, handles)
                except BaseException as e:  # pump-killing failure
                    self._async_fail(e, handles)
                    raise
                finally:
                    with self._aq_cv:
                        self._aq_inflight -= 1
                        if self._obs_on:
                            self._metrics.gauge("async.queue_depth").set(
                                self._aq_inflight)
                        self._aq_cv.notify_all()
        except BaseException as e:  # noqa: BLE001 — pump death: poison,
            self._poison_pending(e)  # never a downstream hang

    def _poison_pending(self, cause: BaseException) -> None:
        """The pump thread is dying: every queued (and future) async op
        can never run.  Fail their handles so ``wait()`` raises
        :class:`AsyncPumpError` instead of hanging forever, and wake
        any ``_fence()`` waiter."""
        err = AsyncPumpError(f"async progress pump died: "
                             f"{type(cause).__name__}: {cause}")
        err.__cause__ = cause
        self._log.error("async progress pump died (%s: %s); poisoning "
                        "%d queued op group(s)", type(cause).__name__,
                        cause, len(self._aq))
        if self._obs_on:
            self._metrics.counter("async.pump_deaths").inc()
            self._trace.emit("async", phase="pump_death", rank=self._rank,
                             error=type(cause).__name__)
        with self._aq_cv:
            self._pump_error = err
            drained = list(self._aq)
            self._aq.clear()
            self._aq_inflight = 0
            self._aq_cv.notify_all()
        for item in drained:
            if item is None:
                continue
            for h in item[1]:
                if not h.done():
                    h._fail(err)

    def _async_fail(self, exc: BaseException, handles: tuple) -> None:
        """Progress-thread failure path: no bare thread tracebacks — the
        error travels through the structured logger + event trace and
        re-raises at the caller's ``wait()`` (a link failure surfaces
        there as :class:`LinkError`, same as the blocking path)."""
        self._log.warn("async collective failed in the progress thread: "
                       "%s: %s", type(exc).__name__, exc)
        if self._obs_on:
            self._metrics.counter("async.errors").inc()
            self._trace.emit("async", phase="error", rank=self._rank,
                             error=type(exc).__name__)
        for h in handles:
            if not h.done():
                h._fail(exc)
        if isinstance(exc, WorldChangedError):
            # Every queued op was issued against the old world: fail
            # them all NOW with the same typed error (their issue-order
            # slots can never run), but keep the pump alive — after the
            # app reloads the checkpoint the async stream is usable
            # again, unlike a pump death.
            with self._aq_cv:
                drained = [it for it in self._aq if it is not None]
                self._aq = collections.deque(
                    it for it in self._aq if it is None)
                self._aq_inflight -= len(drained)
                # Realign the wait cursor past every drained slot: the
                # app catches the rescale at ONE wait() and abandons
                # the other failed handles (their wait() still raises
                # the stored error, as an idempotent re-wait) — the
                # first op issued after the reload must not trip the
                # issue-order check on slots that can never run.  Under
                # _aq_cv so a concurrent _before_wait's check-and-set
                # cannot clobber the realignment back down.
                self._wait_idx = self._issue_idx
                self._aq_cv.notify_all()
            for _fn, hs in drained:
                for h in hs:
                    if not h.done():
                        h._fail(exc)

    def _submit(self, fn: Callable[[], None], handles: tuple) -> None:
        # The pump-death check and the enqueue must be one atomic
        # section: _poison_pending drains the queue under this same
        # lock, so an item appended here is either drained by the
        # poison pass or observed the error first — never enqueued
        # behind a pump that already exited.
        with self._aq_cv:
            if self._pump_error is None:
                self._ensure_pump()
                self._aq.append((fn, handles))
                self._aq_inflight += 1
                if self._obs_on:
                    self._metrics.gauge("async.queue_depth").set(
                        self._aq_inflight)
                self._aq_cv.notify_all()
                return
            err = self._pump_error
        # The pump is dead; the op can never run.  Poison the handles
        # at issue so wait() raises immediately.
        for h in handles:
            if not h.done():
                h._fail(err)

    def _fence(self) -> None:
        """Drain the async stream: flush the pending bucket and wait for
        every queued op to finish.  Called by every blocking collective,
        checkpoint and shutdown (never from the pump itself)."""
        if self._pending is not None:
            self._flush_bucket()
        if self._aq_thread is None:
            return
        with self._aq_cv:
            while self._aq_inflight:
                self._aq_cv.wait()

    def _new_handle(self) -> CollectiveHandle:
        h = CollectiveHandle(on_wait=self._before_wait)
        h._issue_index = self._issue_idx
        h._t_submit = time.perf_counter()
        h._t_done = None
        self._issue_idx += 1
        return h

    def _resolve_handle(self, h: CollectiveHandle, result) -> None:
        h._t_done = time.perf_counter()
        h._resolve(result)

    def _before_wait(self, h: CollectiveHandle) -> None:
        idx = h._issue_index
        # Check-and-advance under _aq_cv: the pump's rescale drain
        # realigns _wait_idx concurrently, and an unlocked read-modify-
        # write here could clobber that realignment back down.
        with self._aq_cv:
            if idx > self._wait_idx:
                raise AsyncOrderError(
                    f"async handles must be waited in issue order: handle "
                    f"#{idx} waited before handle #{self._wait_idx}")
            if idx < self._wait_idx:
                return  # idempotent re-wait
            self._wait_idx = idx + 1
        if self._pending is not None:
            self._flush_bucket()
        if self._obs_on:
            now = time.perf_counter()
            end = h._t_done if h._t_done is not None else now
            # Overlap: how long the op ran in the background before the
            # caller blocked on it (the win over the blocking path).
            self._metrics.histogram("async.overlap.seconds").observe(
                max(min(end, now) - h._t_submit, 0.0))

    def allreduce_async(
        self,
        buf: np.ndarray,
        op: ReduceOp,
        prepare_fun: Optional[Callable[[], None]] = None,
        fuse: bool = True,
        codec: bool = True,
    ) -> CollectiveHandle:
        """``fuse=False`` is the lone-op escape hatch: a bucketed op
        only reaches the wire when its bucket flushes (next incompatible
        op, ``wait()``, or a fence), so a latency-sensitive op with no
        stream behind it should opt out of coalescing to start
        immediately and actually overlap the caller's compute.
        ``codec=False`` opts this op out of an armed lossy wire codec
        (full-precision classic bytes).  Both flags are program order,
        hence deterministic across ranks."""
        if self._world == 1:
            return CollectiveHandle.resolved(
                self.allreduce(buf, op, prepare_fun, codec))
        h = self._new_handle()
        if self._obs_on:
            self._metrics.counter("async.ops").inc()
        flat = buf.reshape(-1)
        if fuse and 0 < flat.nbytes <= self._bucket_bytes:
            self._bucket_add(flat, buf, op, prepare_fun, h, codec)
        else:
            self._flush_bucket()
            self._submit(lambda: self._resolve_handle(
                h, self._allreduce_blocking(buf, op, prepare_fun, codec)),
                (h,))
        return h

    def allgather_async(self, buf: np.ndarray) -> CollectiveHandle:
        if self._world == 1:
            return CollectiveHandle.resolved(self.allgather(buf))
        h = self._new_handle()
        if self._obs_on:
            self._metrics.counter("async.ops").inc()
        self._flush_bucket()
        self._submit(lambda: self._resolve_handle(
            h, self._allgather_blocking(buf)), (h,))
        return h

    def _bucket_add(self, flat: np.ndarray, buf: np.ndarray, op: ReduceOp,
                    prepare_fun, h: CollectiveHandle,
                    codec: bool = True) -> None:
        p = self._pending
        # The codec flag joins op/dtype as a bucket-compatibility key:
        # a fused wire op has ONE wire format, so a precision-opted-out
        # member must never share a bucket with codec-eligible ones.
        if p is not None and (p["op"] != op or p["dtype"] != flat.dtype
                              or p["codec"] != codec
                              or p["nbytes"] + flat.nbytes
                              > self._bucket_bytes):
            self._flush_bucket()
            p = None
        if p is None:
            p = self._pending = {"op": op, "dtype": flat.dtype,
                                 "codec": codec, "nbytes": 0, "items": []}
        p["items"].append((flat, buf, prepare_fun, h))
        p["nbytes"] += flat.nbytes

    def _flush_bucket(self) -> None:
        p, self._pending = self._pending, None
        if p is None:
            return
        items, op, codec = p["items"], p["op"], p["codec"]
        if len(items) == 1:
            flat, buf, prep, h = items[0]
            self._submit(lambda: self._resolve_handle(
                h, self._allreduce_blocking(buf, op, prep, codec)), (h,))
            return
        self._submit(lambda: self._fused_allreduce_exec(items, op, codec),
                     tuple(it[3] for it in items))

    def _record_fusion(self, nmembers: int, nbytes: int, t0: float,
                       replayed: bool = False) -> None:
        self._metrics.counter("async.fused.buckets").inc()
        self._metrics.counter("async.fused.members").inc(nmembers)
        self._metrics.counter("async.fused.bytes").inc(nbytes)
        self._op_done("allreduce_fused", nbytes, t0, replayed=replayed)

    @staticmethod
    def _scatter_fused(flats: list[np.ndarray], work: np.ndarray) -> None:
        off = 0
        for f in flats:
            f[:] = work[off:off + len(f)]
            off += len(f)

    def _fused_allreduce_exec(self, items: list, op: ReduceOp,
                              codec_ok: bool = True) -> None:
        """Runs ON the progress thread: one wire op for a whole bucket
        of small same-op/same-dtype allreduces.  The robust engine
        overrides this with the full consensus/cache/replay protocol
        (one seqno per bucket)."""
        t0 = time.perf_counter() if self._obs_on else 0.0
        for _flat, _buf, prep, _h in items:
            if prep is not None:
                prep()
        flats = [it[0] for it in items]
        self._fused_wire(flats, op, codec_ok)
        if self._obs_on:
            self._record_fusion(len(items),
                                sum(f.nbytes for f in flats), t0)
        for _flat, buf, _prep, h in items:
            self._resolve_handle(h, buf)

    def _member_rides_tree(self, flat: np.ndarray, op: ReduceOp,
                           codec_ok: bool = True) -> bool:
        """Would this member solo on the tree?  Classified on the WIRE
        size — the same quantity `_allreduce_impl` dispatches on after
        the codec encode (codec.wire_nbytes, the honest ratio; the
        historical hardcoded `//= 2` bf16 special case is gone) — so a
        member takes the identical algorithm (and reduction order)
        fused or solo."""
        if self._world == 2:
            return True
        nbytes = flat.nbytes
        if codec_ok:
            nbytes = self._solo_wire_nbytes(flat.dtype, op, nbytes)
        return nbytes <= self._ring_crossover()

    def _fused_wire(self, flats: list[np.ndarray], op: ReduceOp,
                    codec_ok: bool = True) -> None:
        """In-place fused reduction of same-op/same-dtype member arrays.

        Bit-transparency is the design constraint: fusion must not
        change any member's element-wise reduction ORDER.  Tree order is
        position-independent (children-then-parent for every element),
        so members that would solo on the tree reduce as one
        concatenated tree op — forced onto the tree even when the
        concatenation crosses the tree/ring size threshold; ring order
        depends on a member's own block partition, so ring-class members
        ride a SEGMENTED ring (per-member block bounds, vectored
        exchanges) and come out bit-identical to their solo runs.

        Under a non-static schedule mode (forced or auto-tuned) the
        bucket instead concatenates whole and rides the selected
        schedule for the concatenated size: the new peer patterns
        (halving/swing/hier) partition by block position, so per-member
        solo order cannot be preserved through fusion anyway — results
        are exact for exactly-representable payloads (the documented
        envelope, doc/performance.md) and deterministic either way, so
        pyrobust replay still serves identical bits.

        An armed BLOCK-SCALED codec also takes the concatenate path
        (when the concatenation is codec-eligible): its wire elements
        are whole quantization blocks, not per-member views, and the
        documented accuracy envelope already replaces bit-transparency
        — one encode over the concatenation beats per-member scales.
        """
        c = self._codec
        block_codec = (codec_ok and c is not None and not c.elementwise
                       and c.eligible(
                           flats[0].dtype, op,
                           int(sum(f.nbytes for f in flats))))
        if self._sched_name != "static" or block_codec:
            if len(flats) == 1:
                self._allreduce_impl(flats[0], op, codec_ok)
            else:
                work = np.concatenate(flats)
                self._allreduce_impl(work, op, codec_ok)
                self._scatter_fused(flats, work)
            return
        tree = [f for f in flats
                if self._member_rides_tree(f, op, codec_ok)]
        ring = [f for f in flats
                if not self._member_rides_tree(f, op, codec_ok)]
        # Span labels (live plane): a mixed bucket keeps the label of
        # its LAST wire phase — approximate by design; per-member exact
        # labels would need one span per member for one wire op.  The
        # wire label on the static path is the per-member bf16 cast
        # (block codecs took the concatenate branch above).
        self._op_sched = "ring" if ring else "tree"
        self._op_wire = ("bf16" if codec_ok and self._wire_eligible(
            flats[0].dtype, op, flats[0].nbytes) else "none")
        if len(tree) == 1:
            self._allreduce_impl(tree[0], op, codec_ok)
        elif tree:
            work = np.concatenate(tree)
            wire = self._wire_cast(work, op) if codec_ok else None
            if wire is not None:
                w, red = wire
                self._tree_allreduce(w, op, red)
                # codec telemetry: the static fused paths bypass
                # _allreduce_impl, so they file their own counts —
                # else the bulk fused traffic would vanish from the
                # codec.* counters exactly where the codec matters.
                self._note_codec_op(self._codec, work.nbytes, w.nbytes)
                work = w.view(red).astype(np.float32)
            else:
                self._tree_allreduce(work, op)
            self._scatter_fused(tree, work)
        if ring:
            self._ring_allreduce_fused(ring, op, codec_ok)

    def _ring_allreduce_fused(self, flats: list[np.ndarray],
                              op: ReduceOp,
                              codec_ok: bool = True) -> None:
        wires = ([self._wire_cast(f, op) for f in flats] if codec_ok
                 else [None for _ in flats])
        if wires[0] is None:  # eligibility is uniform (same op/dtype)
            self._ring_segmented(flats, op, flats[0].dtype)
            return
        transports = [w for w, _red in wires]
        red = wires[0][1]
        self._ring_segmented(transports, op, red)
        self._note_codec_op(self._codec,
                            int(sum(f.nbytes for f in flats)),
                            int(sum(t.nbytes for t in transports)))
        for f, t in zip(flats, transports):
            f[:] = t.view(red).astype(np.float32)

    def _ring_segmented(self, tflats: list[np.ndarray], op: ReduceOp,
                        red) -> None:
        """Fused multi-member segmented ring (pump extracted to
        rabit_tpu/sched/ring.py with the solo ring)."""
        sched_mod.ring_segmented(self, tflats, op, red)

    # ------------------------------------------------------------------
    # checkpoints (non-fault-tolerant: process-local, like the reference
    # base engine — the robust layer adds replication/recovery)
    # ------------------------------------------------------------------
    def load_checkpoint(self):
        self._fence()
        return (self._version, self._global, self._local)

    def checkpoint(self, global_model, local_model=None, lazy_global=None):
        self._fence()
        if global_model is None and lazy_global is not None:
            global_model = lazy_global()
        self._global = global_model
        self._local = local_model
        self._version += 1

    @property
    def version_number(self) -> int:
        return self._version
