"""Abstract engine interface.

TPU-native equivalent of the reference's IEngine
(reference: include/rabit/engine.h:22-157): the contract every collective
backend implements — in-place allreduce, any-root broadcast, the checkpoint
trio, and identity/topology queries.

Differences from the reference, by design:

* Buffers are numpy arrays (host engines) or ``jax.Array`` (XLA engine)
  rather than ``void*`` — the byte-level view lives in the native layer.
* ``allgather`` is added: it is a first-class XLA collective and several
  rabit-learn algorithms express better with it.
* Checkpoint payloads are ``bytes`` at this layer; object (de)serialization
  happens above (see rabit_tpu.utils.serial).
"""
from __future__ import annotations

import threading
from abc import ABC, abstractmethod
from typing import Callable, Optional

import numpy as np

from rabit_tpu.obs import program
from rabit_tpu.ops import ReduceOp


class AsyncOrderError(RuntimeError):
    """An async handle was waited out of issue order.

    Async collectives resolve strictly in issue order (the wire stream
    is one ordered sequence on every engine); waiting handle N before
    every handle issued earlier has been waited would deadlock or
    reorder the stream, so it fails loudly instead.
    """


class CollectiveHandle:
    """Waitable result of an async collective (``allreduce_async`` /
    ``allgather_async``).

    ``wait()`` blocks until the op completes and returns its result —
    the same object the blocking call would return (the caller's array
    for in-place allreduce, a new array for allgather).  A failure
    inside the engine's progress machinery (e.g. a peer death on a
    non-fault-tolerant engine) re-raises at ``wait()``.  ``wait()`` is
    idempotent; handles from an async-capable engine must be waited in
    issue order (see :class:`AsyncOrderError`).

    Engines without a real async path return handles that are born
    resolved (the op ran synchronously at issue time), so callers can
    use the handle API unconditionally.
    """

    def __init__(self, on_wait: Optional[Callable[["CollectiveHandle"],
                                                  None]] = None) -> None:
        self._event = threading.Event()
        self._result = None
        self._error: Optional[BaseException] = None
        self._on_wait = on_wait
        self._waited = False

    @classmethod
    def resolved(cls, result) -> "CollectiveHandle":
        """A handle born complete (synchronous engines)."""
        h = cls()
        h._resolve(result)
        return h

    def _resolve(self, result) -> None:
        self._result = result
        self._event.set()

    def _fail(self, exc: BaseException) -> None:
        self._error = exc
        self._event.set()

    def done(self) -> bool:
        """True once the op has completed (successfully or not)."""
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None):
        """Block until the op completes; return its result or re-raise
        the failure that stopped it."""
        if self._on_wait is not None and not self._waited:
            # Engine hook: issue-order enforcement, pending-bucket flush
            # and overlap accounting happen before we block.
            self._on_wait(self)
        self._waited = True
        if not self._event.wait(timeout):
            raise TimeoutError("CollectiveHandle.wait timed out")
        if self._error is not None:
            raise self._error
        return self._result


class Engine(ABC):
    """One collective-communication backend."""

    # ---- lifecycle ------------------------------------------------------
    @abstractmethod
    def init(self, params: dict) -> None:
        """Connect/rendezvous.  ``params`` are untyped name→value settings
        (reference: SetParam cascade, src/allreduce_base.cc:111-133)."""

    @abstractmethod
    def shutdown(self) -> None:
        """Leave the job cleanly (reference: IEngine::Shutdown)."""

    # ---- identity / topology -------------------------------------------
    @property
    @abstractmethod
    def rank(self) -> int: ...

    @property
    @abstractmethod
    def world_size(self) -> int: ...

    @property
    def host(self) -> str:
        import socket

        return socket.gethostname()

    def is_distributed(self) -> bool:
        return self.world_size > 1

    @property
    def was_relaunched(self) -> bool:
        """True iff this process is a mid-job relaunch of a worker that
        already completed a rendezvous round (tracker-detected — works
        even when the restarting platform passes a clean environment).
        Engines with a tracker override this."""
        return False

    @property
    def last_op_replayed(self) -> bool:
        """True iff the LAST collective's result was served from the
        fault-tolerance replay cache (the op completed before this
        relaunched rank joined).  Always False for engines without
        replay; the robust native engine overrides this.  The XLA
        engine uses it to avoid acting on a replayed device-plane
        re-formation."""
        return False

    # ---- collectives ----------------------------------------------------
    @abstractmethod
    def allreduce(
        self,
        buf: np.ndarray,
        op: ReduceOp,
        prepare_fun: Optional[Callable[[], None]] = None,
        codec: bool = True,
    ) -> np.ndarray:
        """In-place allreduce of ``buf`` across all ranks.

        ``prepare_fun`` is the lazy-preparation hook: it must fill ``buf``
        and is *skipped* when a cached result is replayed during recovery
        (reference: include/rabit/engine.h:58-76, src/allreduce_robust.cc:90).
        ``codec=False`` opts this op out of an armed lossy wire codec
        (``rabit_wire_codec`` — doc/performance.md "Quantized wire
        codecs"): precision-critical ops keep exact full-width bytes.
        Engines without a codec-capable wire accept and ignore it.
        """

    @abstractmethod
    def broadcast(self, data: Optional[bytes], root: int) -> bytes:
        """Any-root broadcast of a byte payload; returns the payload on all
        ranks (reference: IEngine::Broadcast, src/allreduce_base.cc:500-588)."""

    def allgather(self, buf: np.ndarray) -> np.ndarray:
        """Gather each rank's ``buf`` into shape (world, *buf.shape).

        Default implementation composes broadcasts; backends override with a
        real collective.  (Extension over the reference.)
        """
        parts = []
        for r in range(self.world_size):
            payload = buf.tobytes() if r == self.rank else None
            raw = self.broadcast(payload, root=r)
            parts.append(np.frombuffer(raw, dtype=buf.dtype).reshape(buf.shape))
        return np.stack(parts)

    # ---- async collectives ----------------------------------------------
    def allreduce_async(
        self,
        buf: np.ndarray,
        op: ReduceOp,
        prepare_fun: Optional[Callable[[], None]] = None,
        fuse: bool = True,
        codec: bool = True,
    ) -> CollectiveHandle:
        """Issue an in-place allreduce and return a waitable
        :class:`CollectiveHandle` instead of blocking.

        The default runs the op synchronously and returns a resolved
        handle, so every engine supports the handle API; engines with a
        background progress thread (pysocket/pyrobust) override this to
        overlap socket I/O with the caller's compute and to coalesce
        streams of small same-op/same-dtype payloads into fused wire
        ops (``rabit_bucket_bytes``; pass ``fuse=False`` for a lone
        latency-sensitive op so it dispatches eagerly instead of
        waiting in the bucket).  ``buf`` must not be touched between
        issue and ``wait()``.
        """
        return CollectiveHandle.resolved(
            self.allreduce(buf, op, prepare_fun, codec))

    def allgather_async(self, buf: np.ndarray) -> CollectiveHandle:
        """Issue an allgather; ``wait()`` returns the (world, *shape)
        result.  Default is synchronous (see :meth:`allreduce_async`)."""
        return CollectiveHandle.resolved(self.allgather(buf))

    def allreduce_custom(
        self,
        buf: np.ndarray,
        reducer: Callable[[np.ndarray, np.ndarray], None],
        prepare_fun: Optional[Callable[[], None]] = None,
    ) -> np.ndarray:
        """In-place allreduce with a user-defined reducer (an extension;
        the reference exposes this only in C++ — ReduceHandle,
        include/rabit/engine.h:215-253).

        ``reducer(dst, src)`` must fold ``src`` into ``dst`` in place and
        be **associative and commutative** — merge order is unspecified
        and engine-dependent (this default folds in rank order, but the
        native engine reduces in tree order; the reference's
        ReduceHandle implicitly assumes commutativity too).  Engines
        with a native custom path override this.
        """
        if prepare_fun is not None:
            prepare_fun()
        if self.world_size == 1:
            return buf
        parts = self.allgather(buf)
        acc = np.array(parts[0], copy=True)
        for r in range(1, self.world_size):
            reducer(acc, parts[r])
        buf[...] = acc
        return buf

    # ---- checkpointing --------------------------------------------------
    @abstractmethod
    def load_checkpoint(self) -> tuple[int, Optional[bytes], Optional[bytes]]:
        """Return (version, global_model_bytes, local_model_bytes).

        version==0 means fresh start (no checkpoint exists)
        (reference: IEngine::LoadCheckPoint, src/allreduce_robust.cc:159-196).
        """

    @abstractmethod
    def checkpoint(
        self,
        global_model: bytes,
        local_model: Optional[bytes] = None,
        lazy_global: Optional[Callable[[], bytes]] = None,
    ) -> None:
        """Commit a checkpoint and bump the version.

        ``lazy_global`` implements LazyCheckPoint: when given (and
        ``global_model`` is None) serialization is deferred until a peer
        actually needs the payload during recovery
        (reference: src/allreduce_robust.h:125-127, allreduce_robust.cc:744-751).
        """

    @property
    @abstractmethod
    def version_number(self) -> int:
        """Checkpoint version counter (reference: IEngine::VersionNumber)."""

    # ---- observability --------------------------------------------------
    def stats(self) -> dict:
        """Snapshot of this engine's telemetry metrics
        (``{"counters": .., "gauges": .., "histograms": ..}`` — see
        :class:`rabit_tpu.obs.Metrics`).  Engines instrumented by the
        telemetry subsystem override this; the default (and any engine
        running with telemetry disabled) reports nothing."""
        return {}

    def events(self) -> list[dict]:
        """Structured event trace (op spans, link errors, recovery
        phases, checkpoint commits) as a list of dicts — the ring
        buffer of :class:`rabit_tpu.obs.EventTrace`.  Empty for
        uninstrumented engines or when telemetry is disabled."""
        return []

    @property
    def path_stats(self) -> dict:
        """What the path through the program did in this process, flat
        and JSON-serialisable (``dict[str, int | float]``): every
        program span as ``<name>.n`` / ``<name>.total_s`` /
        ``<name>.max_s`` and every counter under its own name
        (:mod:`rabit_tpu.obs.program`; always on), merged with the
        engine's own counters where it keeps some (the XLA engine's
        ``device_ops`` / ``host_ops``)."""
        return program.stats()

    def event_trace(self):
        """The engine's LIVE :class:`rabit_tpu.obs.EventTrace`, or
        ``None`` when telemetry is off (the twin of :meth:`metrics`)."""
        if not getattr(self, "_obs_on", False):
            return None
        return getattr(self, "_trace", None)

    def metrics(self):
        """The engine's LIVE :class:`rabit_tpu.obs.Metrics` registry,
        or ``None`` when telemetry is off.  App-layer subsystems (the
        serving plane's ``serve.*`` instruments — doc/serving.md) file
        their counters/gauges/histograms here so they ride the same
        streamed delta frames, shutdown summary and tracker
        ``/metrics`` exposition as the engine's own — one telemetry
        plane, not two."""
        if not getattr(self, "_obs_on", False):
            return None
        return getattr(self, "_metrics", None)

    def tracker_print(self, msg: str) -> None:
        """Ship a log line to the job's single logging point.

        The reference forwards *any* rank's message to the tracker
        (reference: IEngine::TrackerPrint, src/allreduce_base.cc:97-105);
        engines with a live tracker connection override this.  The default
        prints locally, rank-tagged when distributed.
        """
        if self.is_distributed():
            print(f"@tracker[{self.rank}] {msg}", flush=True)
        else:
            print(msg, flush=True)
