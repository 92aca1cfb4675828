"""XLA engine: the TPU data plane.

This is the engine the reference cannot have: collectives execute on the
accelerator interconnect (ICI/DCN) as XLA programs instead of over host
TCP sockets.  The design splits rabit's two planes the TPU-native way
(SURVEY.md §7):

* **control plane** — rank rendezvous, byte broadcast, checkpoint
  replication, TrackerPrint, fault tolerance — delegates to an inner host
  engine (the native C++ robust engine, or the pure-Python socket engine)
  speaking the tracker protocol, exactly like the reference's control
  path (reference: src/allreduce_base.cc:138-158, tracker/rabit_tracker.py).
* **data plane** — ``jax.Array`` allreduce/allgather — runs as compiled
  XLA collectives over a process-level mesh.  The reference's equivalent
  is the hand-scheduled socket tree loop (reference:
  src/allreduce_base.cc:326-491); here XLA schedules onto the torus.

Numpy buffers route through the inner host engine: that path is
fault-tolerant (result caching + replay, reference:
src/allreduce_robust.cc:73-105) and latency-bound payloads don't benefit
from the device round-trip.  ``jax.Array`` buffers stay device-resident
and ride ICI; this bulk path is *not* replayed on failure — the
checkpoint/recover contract covers it at iteration granularity, which is
how the reference's apps use the API anyway (checkpoint per iteration,
reference: rabit-learn/kmeans/kmeans.cc:121-157).

Bootstrap: the inner engine's tracker rendezvous assigns the rank; the
tracker hosts a JAX coordination service and every process joins it as a
client with its tracker rank as the node id.  On the CPU backend that id
becomes ``jax.process_index()``; on a TPU the process index is the chip
runtime's own (the launcher gives each child one chip — doc/scaling.md
"One process per chip"), so the ranks exchange their process indices
through the coordination service and the process mesh is ordered by
rank either way (``_build_proc_mesh``).  If JAX is already
multi-process (TPU pod launched through its own orchestration), the
engine adopts JAX's identity instead.
"""
from __future__ import annotations

import os
import socket as pysocket
from typing import Callable, Optional

import numpy as np

from rabit_tpu import obs
from rabit_tpu.engine.interface import CollectiveHandle, Engine
from rabit_tpu.obs import program
from rabit_tpu.ops import ReduceOp
from rabit_tpu.utils.checks import check

PROC_AXIS = "proc"


# Transport failures from the CPU-collectives backend surface as bare
# ValueError("UNKNOWN: Gloo all-reduce failed ... Connection reset by
# peer") rather than a typed runtime error — recognize them by message.
_TRANSPORT_MARKERS = ("gloo", "connection reset", "connection refused",
                      "socket closed", "unavailable:", "deadline exceeded")


def _is_runtime_failure(e: BaseException) -> bool:
    """True for *runtime/peer* failures of a device collective (worth
    degrading to the host path); programming errors (shape/dtype bugs,
    tracer misuse) must propagate instead.  Resolved lazily so importing
    this module never imports jax.

    The message-marker fallback is restricted to the exception types the
    collective runtime actually raises (Gloo failures surface as bare
    ``ValueError``, XLA ones as ``RuntimeError`` subclasses) so a
    programming error that merely *mentions* a marker word is not
    silently swallowed into the degraded path."""
    import jax.errors

    if isinstance(e, (jax.errors.JaxRuntimeError, OSError)):
        return True
    if not isinstance(e, (ValueError, RuntimeError, OSError)):
        return False
    msg = str(e).lower()
    return any(m in msg for m in _TRANSPORT_MARKERS)


class XLAEngine(Engine):
    def __init__(self) -> None:
        self._inner: Optional[Engine] = None
        self._rank = 0
        self._world = 1
        self._job_id = "default"   # resolved in init() (multi-tenant)
        self._adopted_jax = False
        # Pure adopt mode (no tracker): numpy/bytes ops must ride
        # device collectives; there is no inner transport.  The MIXED
        # mode (tracker + externally initialized JAX) keeps the
        # fault-tolerant host transport: degradation works, but the
        # device plane can never be re-formed (the engine does not
        # own the external runtime) — _maybe_reform gates on
        # _adopted_jax for that reason.
        self._no_host_transport = False
        self._we_initialized_jax = False
        self._proc_mesh = None
        self._reduce_cache: dict = {}
        self._degraded = False
        self._reform_enabled = True
        self._device_epoch = 0
        self._init_timeout = 300
        # Device-plane allreduce lowering: "psum" (XLA's own ICI
        # collective, the default) or "pallas_ring" (the credit-flow
        # remote-DMA ring in ops/ring_allreduce.py) for payloads at or
        # above rabit_pallas_min_bytes — the chunked per-link ring the
        # reference hand-pipelines (src/allreduce_base.h:256-295),
        # expressed as a kernel the scheduler can't deschedule.
        self._device_impl = "psum"
        self._pallas_min_bytes = 1 << 20
        # observable path counters (tests assert post-reform collectives
        # ride the device mesh again, not the degraded host path).
        # Reported in path_stats beside the program spans (so named
        # because Engine.stats() is the telemetry snapshot method).
        self._path_counts = {"device_ops": 0, "host_ops": 0}
        # Telemetry (rabit_tpu.obs): resolved in init().
        self._obs_on = False
        self._obs_dir: Optional[str] = None
        self._metrics: Optional[obs.Metrics] = None
        self._trace: Optional[obs.EventTrace] = None
        self._obs_log = obs.log.Logger("xla", lambda: {"rank": self._rank})

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def init(self, params: dict) -> None:
        import jax

        cfg = obs.configure(params)
        self._obs_on = cfg.enabled
        self._obs_dir = cfg.obs_dir
        self._metrics = obs.Metrics()
        self._trace = obs.EventTrace(capacity=cfg.trace_capacity)
        self._device_impl = str(
            params.get("rabit_device_impl")
            or os.environ.get("RABIT_DEVICE_IMPL", "psum")).lower()
        check(self._device_impl in ("psum", "pallas_ring"),
              "rabit_device_impl must be psum|pallas_ring, got %r",
              self._device_impl)
        min_bytes = params.get("rabit_pallas_min_bytes")
        if min_bytes is None:
            min_bytes = os.environ.get("RABIT_PALLAS_MIN_BYTES", 1 << 20)
        try:
            self._pallas_min_bytes = int(min_bytes)
        except (TypeError, ValueError):
            check(False, "rabit_pallas_min_bytes must be an integer "
                  "byte count, got %r", min_bytes)
        uri = params.get("rabit_tracker_uri") or os.environ.get(
            "RABIT_TRACKER_URI")
        port = params.get("rabit_tracker_port") or os.environ.get(
            "RABIT_TRACKER_PORT", 0)
        self._tracker_addr = (str(uri), int(port))
        # Tenant identity: must match what the INNER engine registers
        # under, or the formation barrier / jaxsvc lookups would land in
        # a different job than the rendezvous (params win over env,
        # exactly like pysocket's resolution).
        self._job_id = str(params.get("rabit_job_id")
                           or os.environ.get("RABIT_JOB_ID")
                           or "default")
        have_tracker = bool(uri)
        # Mid-job-relaunch detection: RABIT_RELAUNCH counts restarts of
        # any cause (kill-point or watchdog); rabit_num_trial alone would
        # miss watchdog restarts, whose incarnations must also come up
        # degraded.
        trial = max(int(params.get("rabit_num_trial")
                        or os.environ.get("RABIT_NUM_TRIAL", 0)),
                    int(os.environ.get("RABIT_RELAUNCH", 0)))
        if have_tracker:
            # MIXED mode (tracker + externally-initialized JAX runtime):
            # the platform fixed jax.process_index() before we ran, so
            # register with task_id = that index; with the tracker's
            # RABIT_TRACKER_PIN_RANKS=1 the control-plane rank then
            # matches the device numbering (doc/scaling.md recipe).
            # An explicit rabit_task_id always wins.
            mixed = jax.distributed.is_initialized()
            # presence test, not truthiness: an explicit task_id of 0
            # must win over the automatic registration, or the rank-0
            # worker of a user-pinned launch would collide with whichever
            # worker legitimately owns its jax.process_index()
            has_tid = (params.get("rabit_task_id") is not None
                       and str(params.get("rabit_task_id")) != "") or \
                os.environ.get("RABIT_TASK_ID", "") != ""
            if mixed and not has_tid:
                params = dict(params)
                params["rabit_task_id"] = str(jax.process_index())
            self._inner = self._make_inner(params)
            self._inner.init(params)
            self._rank = self._inner.rank
            self._world = self._inner.world_size
            # The tracker flags mid-job re-registrations too, so platform
            # restarts with a clean environment are still detected.
            if getattr(self._inner, "was_relaunched", False):
                trial = max(trial, 1)
            self._reform_enabled = str(
                params.get("rabit_device_reform")
                or os.environ.get("RABIT_DEVICE_REFORM", "1")) not in (
                    "0", "false", "no")
            try:
                self._init_timeout = max(
                    30, 2 * int(float(params.get("rabit_timeout_sec")
                                      or os.environ.get(
                                          "RABIT_TIMEOUT_SEC", 150))))
            except ValueError:
                self._init_timeout = 300
            if self._world > 1:
                if mixed:
                    # MIXED mode — set on EVERY incarnation (a relaunch
                    # must gate out of _maybe_reform and the ordered
                    # shutdown exactly like the adopted survivors do, or
                    # its host-plane protocol ops would have no partner).
                    self._adopted_jax = True
                    self._log_stderr(
                        "MIXED mode: adopting the externally-initialized "
                        "JAX runtime under a tracker control plane — host "
                        "transport stays fault-tolerant (degradation "
                        "works), but the device plane is owned by the "
                        "external runtime and can NEVER be re-formed "
                        "after a failure")
                    if trial > 0:
                        # Relaunch: whatever external device plane this
                        # incarnation re-joined, the survivors' group no
                        # longer includes the previous life — permanent
                        # host-transport mode (no reform in mixed mode).
                        self._degraded = True
                elif trial > 0:
                    # Mid-job relaunch (keepalive restart): the device mesh
                    # of the original incarnation died with this worker and
                    # the surviving processes' JAX group cannot admit a new
                    # member.  Come up degraded — all jax.Array collectives
                    # ride the fault-tolerant host transport — and resume
                    # from the checkpoint.  Full device-plane speed returns
                    # at the next checkpoint boundary, where every rank
                    # agrees to tear down the broken group and re-form a
                    # fresh one (_maybe_reform; the reference's recovered
                    # jobs likewise return to full speed,
                    # reference: src/allreduce_robust.cc:426-453).
                    #
                    # Known narrow window: a worker that completed the
                    # tracker round but died BEFORE the JAX group finished
                    # forming also arrives here, and the survivors (still
                    # inside _init_jax_distributed) then time out at
                    # initialize — surfaced as a failed formation, after
                    # which the survivors run degraded until the next
                    # checkpoint boundary re-forms the group.
                    self._degraded = True
                else:
                    with program.span("init.group"):
                        try:
                            self._init_jax_distributed(params)
                        except Exception as e:  # noqa: BLE001
                            if not _is_runtime_failure(e):
                                raise
                            self._log_stderr(
                                "device group formation failed "
                                f"({type(e).__name__}: {e}); starting "
                                "degraded")
                            self._drop_distributed_state()
                            self._degraded = True
                        if not (self._degraded or self._adopted_jax):
                            # the first touch of the chip
                            with program.span("init.group.mesh"):
                                self._build_proc_mesh()
        else:
            # No tracker: adopt whatever world JAX already lives in
            # (single process, or a pod slice launched by its own runtime).
            from rabit_tpu.engine.empty import EmptyEngine

            self._inner = EmptyEngine()
            self._inner.init(params)
            self._rank = jax.process_index()
            self._world = jax.process_count()
            self._adopted_jax = self._world > 1
            self._no_host_transport = self._world > 1
        if (self._world > 1 and not self._degraded
                and self._proc_mesh is None):
            if self._adopted_jax and not self._no_host_transport:
                self._build_proc_mesh_mixed()
            else:
                self._build_proc_mesh()

    def _make_inner(self, params: dict) -> Engine:
        name = params.get("rabit_inner_engine")
        if name is None:
            try:
                from rabit_tpu.engine.native import native_available

                if native_available():
                    name = "native"
            except ImportError:
                pass
            # No native library: the degraded/host control plane still
            # gets full cache/replay fault tolerance from the pure-
            # Python robust engine (rabit_tpu/engine/robust.py).
            if name is None:
                name = "pyrobust"
        if name in ("xla", "mpi"):
            raise ValueError(
                f"engine {name!r} cannot back the XLA data plane")
        from rabit_tpu.engine import _make_engine

        # Shared name->class registry; "native" resolves to the robust
        # variant there, which is exactly what the inner engine needs.
        return _make_engine(name, params)

    def _init_jax_distributed(self, params: dict) -> None:
        """Form the JAX process group using control-plane rank/broadcast."""
        import jax

        if jax.distributed.is_initialized():
            # Defensive only: init() routes pre-initialized runtimes to
            # the mixed-mode branch before ever calling this method.
            self._adopted_jax = True
            return
        # Only meaningful on CPU backends (tests, DCN-only hosts); inert
        # on TPU.  Must be set before backend initialization.
        jax.config.update("jax_cpu_collectives_implementation",
                          params.get("rabit_jax_cpu_collectives", "gloo"))
        # Fault tolerance lives in the host-side robust protocol, so a
        # peer death must surface as a failed collective (-> degrade to
        # host transport), NOT as the coordination service fatally
        # terminating the survivors.
        jax.config.update("jax_enable_recoverability", True)
        # Every rank resolves the SAME tracker-hosted service by key:
        # the init-time coordinator exchange runs entirely over the
        # tracker, so version-span 0 contains no engine-internal
        # collectives and a worker relaunched before the first
        # checkpoint replays a span aligned with the survivors'.
        with program.span("init.group.service"):
            # answered once every rank has asked: the wait for the
            # slowest rank (its imports, mostly) is here
            coord = self._request_tracker_service("init")
        if os.environ.get("RABIT_XLA_DIE_FORMATION", "") == str(self._rank):
            # Fault-injection hook (XLA death matrix): die INSIDE the
            # formation window — tracker round + coordinator resolution
            # complete, formation barrier not yet posted, JAX group not
            # formed.  The survivors must learn of the death on the
            # control plane (formation barrier abort), start degraded,
            # and re-form at the next checkpoint boundary.  Only
            # reachable on the first life: relaunches take the degraded
            # branch and reforms go through _maybe_reform, neither of
            # which calls this method.
            self._log_stderr(
                f"rank {self._rank} dying in the formation window "
                "(RABIT_XLA_DIE_FORMATION)")
            os._exit(254)
        with program.span("init.group.barrier"):
            # milliseconds: the service round has levelled the ranks
            formed = self._formation_barrier()
        if not formed:
            # Someone died (or the barrier timed out) before formation
            # could complete: entering the device-group registration now
            # would block unrecoverably (see protocol.CMD_FORMBAR) —
            # start degraded; the first checkpoint re-forms the plane.
            self._log_stderr(
                "formation barrier aborted — starting degraded")
            self._degraded = True
            return
        # First formation is the one spot where a member death leaves the
        # survivors blind (no host-protocol traffic to error out of), and
        # a client stuck in a doomed registration is in danger: when a
        # co-registrant dies, the coordination service's heartbeat
        # detection pushes a FATAL to the still-blocked clients
        # (client.h:80 — mid-registration deaths are not covered by the
        # recoverable-task semantics that protect formed groups).  So the
        # first-formation timeout is SHORT: survivors abandon the doomed
        # barrier, drop their clients (stopping the error-polling
        # thread), and start degraded before either the service's
        # heartbeat window or the launcher watchdog can act; the first
        # checkpoint boundary re-forms the plane.  Raise on pods where
        # honest formation needs longer.
        raw = (params.get("rabit_form_timeout_sec")
               or os.environ.get("RABIT_FORM_TIMEOUT_SEC"))
        if raw is not None:
            # explicitly configured: honored as-is (pods with slow
            # honest formation RAISE it, per doc/parameters.md)
            try:
                form_timeout = int(float(raw))
            except ValueError:
                form_timeout = 10
        else:
            form_timeout = min(10, self._init_timeout)
        with program.span("init.group.connect"):
            self._connect_distributed(coord, init_timeout=form_timeout)
        self._we_initialized_jax = True

    def _formation_barrier(self) -> bool:
        """Post the tracker's formation barrier (protocol.CMD_FORMBAR):
        the LAST act before the blocking jaxlib group registration.
        True = every worker is alive and about to register too; False =
        formation is doomed (a member died / barrier timed out) — the
        caller must start degraded instead of blocking.  Fails safe:
        any tracker-path error counts as an abort."""
        try:
            from rabit_tpu.tracker import protocol as P

            sock = pysocket.create_connection(
                self._tracker_addr, timeout=self._init_timeout + 60)
            try:
                sock.settimeout(self._init_timeout + 60)
                P.send_hello(
                    sock, P.CMD_FORMBAR,
                    os.environ.get("RABIT_TASK_ID", str(self._rank)),
                    self._world, job=self._job_id)
                return P.recv_u32(sock) == 1
            finally:
                sock.close()
        except Exception as e:  # noqa: BLE001 — fail safe to degraded
            self._log_stderr(
                f"formation barrier failed ({type(e).__name__}: {e})")
            return False

    def _request_tracker_service(self, key: str = "") -> str:
        """Ask the tracker for a JAX coordination service (cmd=jaxsvc);
        returns "host:port" or "" if it cannot.  ``key == ""`` makes a
        fresh service (one per device-plane reform); a non-empty key
        (the init-time "init") is create-or-get tracker-side, so every
        rank resolves the same service with no worker-to-worker op."""
        try:
            from rabit_tpu.tracker import protocol as P

            sock = pysocket.create_connection(self._tracker_addr, timeout=30)
            try:
                P.send_hello(sock, P.CMD_JAXSVC, key, self._world,
                             job=self._job_id)
                port = P.recv_u32(sock)
            finally:
                sock.close()
            return f"{self._tracker_addr[0]}:{port}" if port else ""
        except Exception as e:  # noqa: BLE001
            self._log_stderr(
                f"tracker jaxsvc request failed ({type(e).__name__}: {e})")
            return ""

    def _broadcast_fresh_coordinator(self) -> str:
        """Rank 0 obtains a fresh TRACKER-HOSTED coordination service —
        its lifetime is decoupled from every worker's, so any worker
        death, rank 0 included, is a recoverable peer failure — and
        everyone learns its address over the host control plane ("" if
        the tracker could not host one)."""
        payload = (self._request_tracker_service().encode()
                   if self._rank == 0 else None)
        return self._inner.broadcast(payload, root=0).decode()

    def _connect_distributed(self, coord: str,
                             init_timeout: int | None = None) -> None:
        """Join the tracker-hosted JAX coordination service at ``coord``.

        Built on the jaxlib distributed-runtime bindings directly
        because every rank here is a CLIENT — the service itself runs in
        the tracker (``jax.distributed.initialize`` would insist on
        process 0 hosting it, re-coupling the coordinator to a worker's
        lifetime).  ``recoverable=True`` keeps peer deaths non-fatal
        (they surface as failed collectives -> degrade -> re-form; the
        reference survives any single death the same way,
        reference: src/allreduce_robust.cc:426-453);
        ``shutdown_on_destruction=False`` keeps a dropped client's
        destructor from RPC-ing a dead service.  One installation: these
        are the jaxlib 0.9 signatures, called as they are."""
        from jax._src import distributed as jdist
        from jax._src.lib import _jax as jaxlib_ext

        if not coord:
            # an OSError: _is_runtime_failure degrades instead of aborting
            raise ConnectionError(
                "the tracker could not host a JAX coordination service")
        state = jdist.global_state
        check(state.client is None,
              "XLA engine: JAX distributed client already exists")
        client = jaxlib_ext.get_distributed_runtime_client(
            coord, self._rank,
            init_timeout=init_timeout or self._init_timeout,
            use_compression=True,
            shutdown_on_destruction=False,
            recoverable=True)
        client.connect()
        self._log_stderr(f"rank {self._rank} joined coordination "
                         f"service {coord}")
        state.client = client
        state.coordinator_address = coord
        state.num_processes = self._world
        state.process_id = self._rank

    def _drop_distributed_state(self) -> None:
        """Reset jax.distributed bookkeeping WITHOUT the disconnect RPC
        (for a group that never formed or whose members are gone: an
        RPC would block and, under the default callback, fatally
        terminate this process)."""
        from jax._src import distributed as jdist

        state = jdist.global_state
        state.client = None
        state.coordinator_address = None
        self._we_initialized_jax = False

    def _shutdown_distributed_ordered(self) -> None:
        """Disconnect from a LIVE coordination service with the teardown
        race closed: followers disconnect while the coordinator-owning
        rank 0 is provably alive (host barrier between the waves)."""
        import jax

        self._control_barrier()
        if self._rank != 0 and self._we_initialized_jax:
            try:
                jax.distributed.shutdown()
            except Exception as e:  # noqa: BLE001
                self._log_stderr(
                    f"distributed shutdown failed ({type(e).__name__}: "
                    f"{e}); dropping state")
                self._drop_distributed_state()
        self._control_barrier()
        if self._rank == 0 and self._we_initialized_jax:
            try:
                jax.distributed.shutdown()
            except Exception as e:  # noqa: BLE001
                self._log_stderr(
                    f"distributed shutdown failed ({type(e).__name__}: "
                    f"{e}); dropping state")
                self._drop_distributed_state()
        self._we_initialized_jax = False

    @staticmethod
    def _log_stderr(msg: str) -> None:
        import sys

        print(f"[rabit_tpu] xla engine: {msg}", file=sys.stderr, flush=True)

    # ------------------------------------------------------------------
    # device-plane re-formation
    # ------------------------------------------------------------------
    @property
    def device_epoch(self) -> int:
        """Bumped every time the device plane is re-formed.  Device
        arrays created under an older epoch are invalid — apps re-upload
        their shards when the epoch moves (the device-side analogue of
        the reference's reload-from-checkpoint after recovery)."""
        return self._device_epoch

    def _maybe_reform(self) -> None:
        """Re-form the device plane if any rank is degraded.

        Runs at the checkpoint boundary (every rank calls checkpoint()
        once per iteration, so this is a consensus point; a relaunched
        incarnation is always degraded, which drags every healthy
        survivor into the reform).  Protocol, all ranks symmetric:

        1. host-plane MAX-allreduce of per-rank state flags
           (bit0 degraded, bit1 member-of-current-JAX-group);
        2. if nobody is degraded -> done (one small host op per
           checkpoint);
        3. tear down the old group — ordered disconnect from its
           tracker-hosted coordination service (which outlives every
           worker) when anyone is still a member, raw state drop
           otherwise;
        4. destroy device backends (compiled executables and device
           arrays of the old epoch die with them);
        5. rank 0 obtains a fresh tracker-hosted coordination service
           and broadcasts it over the host plane; everyone
           re-initializes, rebuilds the process mesh, clears the
           collective cache, bumps device_epoch.

        A failed re-formation (e.g. another death mid-reform) leaves
        every reachable rank degraded; the next checkpoint retries with
        a fresh coordinator.  Matches the reference's recovered-job
        full-speed semantics (src/allreduce_robust.cc:426-453)."""
        if (self._world <= 1 or self._adopted_jax or self._inner is None
                or not self._reform_enabled):
            return
        flags = np.zeros(self._world, np.uint8)
        flags[self._rank] = (1 if self._degraded else 0) | (
            2 if self._we_initialized_jax else 0)
        with program.span("commit.reform_flags"):
            self._inner.allreduce(flags, ReduceOp.MAX)
        if not (flags & 1).any():
            return
        with program.span("commit.reform"):
            self._reform(flags)

    def _reform(self, flags) -> None:
        """Steps 3-5 of :meth:`_maybe_reform`: some rank is degraded."""
        import jax
        import jax.extend  # jax.extend is not imported by bare `import jax`

        # every rank derives these from the SHARED flags, so the branch
        # structure (and its control-plane op sequence) is identical on
        # members and relaunched incarnations alike
        members_exist = bool((flags & 2).any())
        self._log_stderr(
            f"re-forming device plane (degraded ranks: "
            f"{[int(r) for r in np.flatnonzero(flags & 1)]}, old group "
            f"{'has members' if members_exist else 'is gone'})")
        if members_exist:
            # ordered disconnect; ranks that were never members of the
            # old group (relaunched incarnations) drop their (empty)
            # state but MUST still join both barriers — every rank's
            # control-plane op sequence stays identical
            if not self._we_initialized_jax:
                self._drop_distributed_state()
            self._shutdown_distributed_ordered()
        else:
            self._drop_distributed_state()
        try:
            jax.extend.backend.clear_backends()
        except Exception as e:  # noqa: BLE001  pragma: no cover
            self._log_stderr(
                f"clear_backends failed ({type(e).__name__}: {e})")
        self._proc_mesh = None
        self._reduce_cache.clear()
        # NOTE: rank 0 must request a service even when the flags op was
        # replayed — if the old rank 0 died MID-round, the survivors are
        # still pending in this broadcast and will receive our payload
        # fresh (we then join their in-flight re-formation below); only
        # a fully-completed round serves the broadcast from cache, and
        # then the unused service is discarded (retained by the tracker,
        # one per replayed-round-on-rank-0-relaunch — rare and bounded).
        coord = self._broadcast_fresh_coordinator()
        if self._inner.last_op_replayed:
            # The coordinator payload was served from the REPLAY cache:
            # this re-formation completed before this incarnation joined
            # (its group may even contain our previous life), so the
            # address is stale — joining it would re-form a backend
            # inside an already-formed group's coordination service.
            # Consume the span's ops (done above, branch-identically)
            # and stay degraded; the next checkpoint boundary runs a
            # FRESH exchange that includes us.  clear_backends above
            # already killed this rank's device arrays — bump the epoch
            # so apps re-upload their resident shards.
            self._log_stderr(
                "re-formation round was replayed (stale group); staying "
                "degraded until the next fresh checkpoint boundary")
            self._drop_distributed_state()
            self._degraded = True
            self._device_epoch += 1
            return
        try:
            self._connect_distributed(coord)
            self._we_initialized_jax = True
            self._build_proc_mesh()
        except Exception as e:  # noqa: BLE001
            if not _is_runtime_failure(e):
                raise
            self._log_stderr(
                f"device-plane re-formation failed ({type(e).__name__}: "
                f"{e}); staying degraded until the next checkpoint")
            self._drop_distributed_state()
            self._degraded = True
            self._device_epoch += 1  # old-epoch arrays died with backends
            return
        self._degraded = False
        self._device_epoch += 1
        if self._obs_on:
            self._metrics.counter("recovery.reforms").inc()
            self._trace.emit("recovery", phase="reform", rank=self._rank,
                             epoch=self._device_epoch)
        self._log_stderr(
            f"device plane re-formed (epoch {self._device_epoch})")

    def _build_proc_mesh(self, aligned: bool = False) -> None:
        """One device per process, ordered by control-plane rank: mesh
        position ``r`` holds rank ``r``'s device, or allgather rows and
        broadcast roots would be misattributed.

        On the CPU backend ``jax.process_index()`` IS the rank (the node
        id this process joined the coordination service with).  On a TPU
        it is the chip runtime's own numbering, fixed by which chip the
        process was given and not by any task id the launcher sets (of
        the four v5e chips of one host, the process that sees chip 0 is
        process 3).  So every rank publishes its process index in the
        coordination service's key-value store — not a host-plane op, so
        version span 0 stays free of engine-internal collectives — and
        the mesh is ordered by what all of them read back.

        ``aligned`` (mixed mode) additionally requires the two
        numberings to be identical; the verdict rests on the shared
        table, so every rank reaches the same one."""
        import jax
        from jax._src import distributed as jdist
        from jax.sharding import Mesh

        check(jax.process_count() == self._world,
              "XLA engine: JAX world (%d) != tracker world (%d)",
              jax.process_count(), self._world)
        per_proc: dict[int, jax.Device] = {}
        for d in jax.devices():
            per_proc.setdefault(d.process_index, d)
        check(len(per_proc) == self._world,
              "XLA engine: %d processes own devices, expected %d",
              len(per_proc), self._world)
        kv = jdist.global_state.client
        key = "rabit_tpu/process_index_of_rank/%d"
        kv.key_value_set(key % self._rank, str(jax.process_index()),
                         allow_overwrite=True)
        proc_of_rank = [
            int(kv.blocking_key_value_get(key % r,
                                          self._init_timeout * 1000))
            for r in range(self._world)]
        check(sorted(proc_of_rank) == sorted(per_proc),
              "XLA engine: ranks report process indices %s, devices "
              "belong to %s", proc_of_rank, sorted(per_proc))
        check(not aligned or proc_of_rank == list(range(self._world)),
              "XLA engine: jax.process_index() of ranks 0..%d is %s (this "
              "rank: %d); launch so that process ids match tracker ranks",
              self._world - 1, proc_of_rank, jax.process_index())
        self._proc_mesh = Mesh(
            np.array([per_proc[p] for p in proc_of_rank]), (PROC_AXIS,))

    def _build_proc_mesh_mixed(self) -> None:
        """Mesh build for MIXED mode (tracker + adopted external JAX).

        The two rank spaces are independent here — the platform fixed
        ``jax.process_index()``, the tracker assigned the control-plane
        rank — so a mismatch is a *configuration* state, not a bug, and
        it can differ per rank (e.g. rank 1 of a reversed assignment
        matches itself).  Crashing only the mismatched ranks, or letting
        matched ranks keep the device plane while others degrade, would
        wedge the job in a split-brain collective.  So the verdict is
        agreed by consensus: if ANY rank cannot build the aligned mesh,
        ALL ranks drop it and run degraded on the fault-tolerant host
        transport (and stay there — the engine does not own the external
        runtime, so _maybe_reform is gated off).  The fix is launching
        with matching numberings: tracker-side RABIT_TRACKER_PIN_RANKS=1
        plus the engine's automatic task_id = jax.process_index()
        registration.

        The consensus rides the DEVICE plane (``process_allgather`` is
        rank-order-independent, so it works regardless of alignment),
        NOT the robust host stream: an init-time host op would sit at
        the head of version span 0 on first-life ranks only, breaking
        the span-alignment invariant that lets a worker relaunched
        before the first checkpoint replay against the survivors' cache
        (the same reason the coordinator exchange goes through the
        tracker, _init_jax_distributed).  Only first-start ranks run
        this method — mixed-mode relaunches come up degraded and never
        pair with it — and at first start the external runtime has all
        processes alive by construction (it just formed the JAX world);
        liveness inside that window is the external runtime's, not this
        engine's."""
        import jax

        # Globally-visible mismatches need no collective agreement — and
        # MUST not enter one: with a JAX world larger than the tracker's,
        # the extra processes are still blocked in tracker registration,
        # so a process_allgather would hang the N that got here instead
        # of surfacing the misconfiguration.
        per_proc = {d.process_index for d in jax.devices()}
        if jax.process_count() != self._world \
                or len(per_proc) != self._world:
            self._proc_mesh = None
            self._degraded = True
            self._log_stderr(
                f"MIXED mode: JAX world (processes={jax.process_count()}, "
                f"device-owning={len(per_proc)}) does not match the "
                f"tracker world ({self._world}) — running degraded on "
                "the host transport for the whole job; fix the launch "
                "so the two worlds agree")
            return
        err: Exception | None = None
        try:
            self._build_proc_mesh(aligned=True)
        except Exception as e:  # noqa: BLE001 — consensus decides below
            err = e
        from jax.experimental import multihost_utils

        # A peer flagged as re-registered at ITS first start comes up
        # degraded and never reaches this collective — its first-life
        # peers would then block here (the liveness window belongs to
        # the external runtime that just formed the JAX world).  Bracket
        # the collective with logs so a wedged start is diagnosable from
        # stderr.  Deliberately NOT a unilateral timeout: a rank that
        # times out and degrades while its late allgather still
        # completes on the peers would split the world between degraded
        # and device-plane modes — a permanent divergent hang, strictly
        # worse than this consistent, attributable wait.
        self._log_stderr(
            "MIXED mode: entering init consensus (process_allgather; "
            "if this is the last line, a peer never reached the "
            "collective — check for a degraded relaunch)")
        flags = multihost_utils.process_allgather(
            np.array([0 if err is None else 1], np.int32))
        self._log_stderr("MIXED mode: init consensus complete")
        if not int(np.max(flags)):
            return
        self._proc_mesh = None
        self._degraded = True
        detail = (f" (this rank: {type(err).__name__}: {err})"
                  if err is not None else " (a peer's mesh was misaligned)")
        self._log_stderr(
            "MIXED mode: control-plane ranks and jax.process_index() do "
            "not line up on every rank — running degraded on the host "
            "transport for the whole job" + detail + ".  Launch with "
            "RABIT_TRACKER_PIN_RANKS=1 on the tracker to align them")

    def _control_barrier(self) -> None:
        """Barrier over the host control plane (all ranks must call).
        A failure is logged, never swallowed silently: an unordered
        teardown is exactly the coordination-service race these
        barriers exist to prevent, so it must be diagnosable."""
        try:
            self._inner.allreduce(np.zeros(1, np.uint8), ReduceOp.SUM)
        except Exception as e:  # noqa: BLE001
            self._log_stderr(
                f"control barrier failed ({type(e).__name__}: {e}); "
                "teardown ordering is no longer guaranteed")

    def shutdown(self) -> None:
        if (self._world > 1 and self._inner is not None
                and not self._adopted_jax):
            # Coordination-service teardown is racy once any member died
            # (degradation can be *asymmetric* — a relaunched rank comes
            # up degraded while survivors that issued no device collective
            # since the death are not): a follower whose disconnect RPC
            # lands after the leader (rank 0, coordinator owner) exited is
            # fatally terminated by the error-polling thread.  So ALWAYS
            # order the teardown over our own host control plane:
            # followers disconnect while the leader is provably alive,
            # then the leader follows.  Every rank joins both barriers —
            # including a relaunched incarnation that never joined the
            # JAX group (_we_initialized_jax False).  Like the robust
            # engine's own shutdown consensus (and the reference's
            # pseudo-checkpoint shutdown, allreduce_robust.cc:37-48),
            # these barriers wait for a dead peer's relaunch — under a
            # deployment with no auto-restart, teardown blocks until the
            # link timeout, the same contract as the rest of the robust
            # protocol.
            self._shutdown_distributed_ordered()
        # Ship the device-plane telemetry while the tracker is still up
        # (the inner engine ships its own summary during its shutdown;
        # the tracker merges same-rank summaries section-wise).
        if (self._obs_on and self._world > 1 and self._inner is not None
                and not self._no_host_transport):
            obs.ship_summary(
                self._inner.tracker_print, self._obs_log, "XLAEngine",
                self._rank, self._world, self.stats(),
                [e for e in self._trace.events()
                 if e.get("name") == "recovery"],
                job=self._job_id)
        if self._inner is not None:
            self._inner.shutdown()
        # Overwrite the inner engine's per-rank event dump with the
        # merged trace (device-plane + control-plane, one timeline).
        if self._obs_on and self._obs_dir:
            obs.dump_events(self._obs_log, self._obs_dir, self._rank,
                            self.events())
        self._proc_mesh = None
        self._reduce_cache.clear()

    # ------------------------------------------------------------------
    # identity
    # ------------------------------------------------------------------
    @property
    def rank(self) -> int:
        return self._rank

    @property
    def world_size(self) -> int:
        return self._world

    def tracker_print(self, msg: str) -> None:
        self._inner.tracker_print(msg)

    def stats(self) -> dict:
        """Own (device-plane) telemetry; the inner host engine keeps its
        own registry and ships it to the tracker itself.  The raw path
        counters ride along as gauges (``path_stats`` stays available
        unconditionally for tests)."""
        if not self._obs_on or self._metrics is None:
            return {}  # disabled telemetry reports nothing (interface.py)
        self._metrics.gauge("xla.device_ops").set(
            self._path_counts["device_ops"])
        self._metrics.gauge("xla.host_ops").set(
            self._path_counts["host_ops"])
        self._metrics.gauge("xla.device_epoch").set(self._device_epoch)
        return self._metrics.snapshot()

    @property
    def path_stats(self) -> dict:
        return {**program.stats(), **self._path_counts}

    def events(self) -> list[dict]:
        own = self._trace.events() if self._trace is not None else []
        inner = self._inner.events() if self._inner is not None else []
        return sorted(own + inner, key=lambda e: e.get("ts", 0.0))

    # ------------------------------------------------------------------
    # data plane
    # ------------------------------------------------------------------
    @property
    def mesh(self):
        """The process-level mesh (None when world==1)."""
        return self._proc_mesh

    def allreduce(
        self,
        buf,
        op: ReduceOp,
        prepare_fun: Optional[Callable[[], None]] = None,
        codec: bool = True,
    ):
        import jax

        if isinstance(buf, np.ndarray):
            if self._no_host_transport and self._world > 1:
                # No host transport in adopt mode — reduce on device and
                # copy back in place (preserving the in-place contract).
                if prepare_fun is not None:
                    prepare_fun()
                out = self._device_collective(
                    jax.numpy.asarray(buf), op, kind="allreduce")
                buf[...] = np.asarray(out)
                return buf
            # Host path: fault-tolerant inner engine (result replay,
            # wire codec honored — the device plane is always exact).
            return self._inner.allreduce(buf, op, prepare_fun, codec)
        check(isinstance(buf, jax.Array),
              "XLA engine: allreduce expects numpy or jax array")
        if prepare_fun is not None:
            prepare_fun()
        if self._world == 1:
            return buf
        if self._degraded:
            return self._host_degrade("allreduce", buf, op)
        try:
            return self._device_collective(buf, op, kind="allreduce")
        except Exception as e:  # noqa: BLE001 — filtered just below
            if not _is_runtime_failure(e):
                raise  # programming error (shape/dtype), not peer failure
            return self._host_degrade("allreduce", buf, op, cause=e)

    def allgather(self, buf):
        import jax

        if isinstance(buf, np.ndarray):
            if self._no_host_transport and self._world > 1:
                out = self._device_collective(
                    jax.numpy.asarray(buf), ReduceOp.SUM, kind="allgather")
                return np.asarray(out)
            return self._inner.allgather(buf)
        if self._world == 1:
            return buf[None]
        if self._degraded:
            return self._host_degrade("allgather", buf, ReduceOp.SUM)
        try:
            return self._device_collective(buf, ReduceOp.SUM,
                                           kind="allgather")
        except Exception as e:  # noqa: BLE001 — filtered just below
            if not _is_runtime_failure(e):
                raise
            return self._host_degrade("allgather", buf, ReduceOp.SUM,
                                      cause=e)

    def allreduce_async(
        self,
        buf,
        op: ReduceOp,
        prepare_fun: Optional[Callable[[], None]] = None,
        fuse: bool = True,
        codec: bool = True,
    ) -> CollectiveHandle:
        """Async passthrough: numpy payloads ride the inner host
        engine's progress thread (overlap + bucket fusion, with the
        robust replay semantics intact); device arrays stay on the
        compiled data plane, which is already asynchronous under JAX
        dispatch, so they resolve synchronously."""
        if (isinstance(buf, np.ndarray) and self._world > 1
                and self._inner is not None
                and not self._no_host_transport and not self._degraded):
            return self._inner.allreduce_async(buf, op, prepare_fun,
                                               fuse=fuse, codec=codec)
        return CollectiveHandle.resolved(
            self.allreduce(buf, op, prepare_fun, codec))

    def allgather_async(self, buf) -> CollectiveHandle:
        if (isinstance(buf, np.ndarray) and self._world > 1
                and self._inner is not None
                and not self._no_host_transport and not self._degraded):
            return self._inner.allgather_async(buf)
        return CollectiveHandle.resolved(self.allgather(buf))

    def _host_degrade(self, kind: str, buf, op: ReduceOp,
                      cause: Exception | None = None):
        """Degraded mode: the device collective failed (typically a peer
        died mid-program, which XLA cannot recover from).  Route the
        payload through the inner fault-tolerant host engine — its
        consensus/recovery protocol re-forms the world (reference
        recovery path: src/allreduce_robust.cc:426-453) — and return a
        device array so callers keep their types.  Bulk ops ride the
        host path until the next checkpoint boundary re-forms the
        device plane (_maybe_reform; or, with rabit_device_reform=0,
        until the job is relaunched whole)."""
        import jax.numpy as jnp

        if self._inner is None or self._no_host_transport:
            raise RuntimeError(
                "XLA engine: device collective failed and no host "
                "transport is available (adopt mode)") from cause
        if not self._degraded:
            self._degraded = True
            import sys

            print("[rabit_tpu] xla engine: device collective failed "
                  f"({type(cause).__name__}: {cause}); degrading to host "
                  "transport", file=sys.stderr, flush=True)
            if self._obs_on:
                self._metrics.counter("recovery.degrades").inc()
                self._trace.emit("recovery", phase="degrade",
                                 rank=self._rank, kind=kind,
                                 epoch=self._device_epoch)
        host = np.asarray(buf)
        if kind == "allreduce":
            out = self._inner.allreduce(host.copy(), op)
        else:
            out = self._inner.allgather(host)
        self._path_counts["host_ops"] += 1
        if self._obs_on:
            self._metrics.counter("op.host_degraded.count").inc()
            self._metrics.counter("op.host_degraded.bytes").inc(host.nbytes)
        return jnp.asarray(out)

    def _device_collective(self, arr, op: ReduceOp, kind: str):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if not arr.is_fully_addressable:
            # Output of a previous engine collective: a global array
            # replicated across processes — peel off the local replica.
            check(arr.is_fully_replicated,
                  "XLA engine: global input arrays must be fully replicated")
            arr = arr.addressable_shards[0].data
        with program.span(kind + ".stage"):
            local = jax.device_put(arr, jax.local_devices()[0])[None]
            global_shape = (self._world,) + tuple(arr.shape)
            garr = jax.make_array_from_single_device_arrays(
                global_shape,
                NamedSharding(self._proc_mesh, P(PROC_AXIS)),
                [local],
            )
        fn = self._collective_fn(kind, tuple(arr.shape),
                                 np.dtype(arr.dtype).name, ReduceOp(op))
        # dispatch time only: device collectives are asynchronous and
        # blocking here to time them would serialize the data plane
        with program.span(kind + ".dispatch") as dispatch:
            out = fn(garr)
            program.enqueued(out)
        self._path_counts["device_ops"] += 1
        if self._obs_on:
            dt = dispatch.seconds
            self._metrics.counter(f"op.device_{kind}.count").inc()
            self._metrics.counter(f"op.device_{kind}.bytes").inc(arr.nbytes)
            self._metrics.histogram(
                f"op.device_{kind}.dispatch_seconds").observe(dt)
            self._trace.emit("op", kind=f"device_{kind}",
                             nbytes=int(arr.nbytes), dur=dt,
                             rank=self._rank)
        return out

    def _use_pallas_ring(self, shape, dtype_name: str, op: ReduceOp) -> bool:
        """pallas_ring serves large {SUM,MAX,MIN,PROD} allreduces; small
        payloads and other ops stay on psum (latency-bound territory —
        the ring's 2(N-1) hops only pay off once bandwidth dominates).

        Off-TPU the kernel runs in interpret mode, whose simulated
        remote DMAs live inside one process: a multi-process CPU mesh
        (the CI harness) must stay on psum or the collective wedges, so
        the ring engages only on real TPU backends or single-process
        meshes (where tests and the driver's dryrun exercise it)."""
        if self._device_impl != "pallas_ring":
            return False
        import jax

        from rabit_tpu.ops import on_tpu
        from rabit_tpu.ops.ring_allreduce import supported_ops

        if not on_tpu() and jax.process_count() > 1:
            return False
        if op not in supported_ops():
            return False
        nbytes = int(np.prod(shape, dtype=np.int64)) * \
            np.dtype(dtype_name).itemsize
        return nbytes >= self._pallas_min_bytes

    def _collective_fn(self, kind: str, shape, dtype_name: str, op: ReduceOp):
        key = (kind, shape, dtype_name, op)
        fn = self._reduce_cache.get(key)
        if fn is None:
            # a program built here compiles at its first call: inside
            # a learner's loop that is a stall
            program.count(kind + ".programs_built")
            import jax
            from jax import lax
            from jax.sharding import PartitionSpec as P

            from rabit_tpu.parallel import collectives as C

            nd = len(shape)
            check_vma = True
            if kind == "allreduce" and self._use_pallas_ring(
                    shape, dtype_name, op):
                from rabit_tpu.ops.ring_allreduce import \
                    ring_allreduce_pallas

                def body(s):
                    with jax.named_scope("engine/allreduce"):
                        return ring_allreduce_pallas(s[0], PROC_AXIS, op)

                out_spec = P(*([None] * nd))
                # pallas outputs carry no varying-across-mesh annotation;
                # the static replication check cannot see through them
                check_vma = False
            elif kind == "allreduce":
                def body(s):
                    with jax.named_scope("engine/allreduce"):
                        return C.allreduce(s[0], PROC_AXIS, op)

                out_spec = P(*([None] * nd))
            else:
                # allgather: (world, *shape) replicated everywhere.
                # Expressed as scatter-into-zeros + psum rather than
                # lax.all_gather so shard_map can statically prove the
                # output replicated (all_gather's output defeats the VMA
                # replication check).
                import jax.numpy as jnp

                world = self._world

                def body(s, world=world):
                    with jax.named_scope("engine/allgather"):
                        buf = jnp.zeros((world,) + tuple(s[0].shape),
                                        s[0].dtype)
                        buf = lax.dynamic_update_index_in_dim(
                            buf, s[0], lax.axis_index(PROC_AXIS), 0)
                        return lax.psum(buf, PROC_AXIS)

                out_spec = P(*([None] * (nd + 1)))
            # the program's name in a device trace
            body.__name__ = f"engine_{kind}_{op.name.lower()}"
            fn = C.shard_collective(
                self._proc_mesh, body,
                in_specs=(P(PROC_AXIS, *([None] * nd)),),
                out_specs=out_spec, check_vma=check_vma)
            self._reduce_cache[key] = fn
        return fn

    # ------------------------------------------------------------------
    # control plane delegation
    # ------------------------------------------------------------------
    def broadcast(self, data: Optional[bytes], root: int) -> bytes:
        if self._no_host_transport and self._world > 1:
            # No host transport in adopt mode — ship bytes over the device
            # collectives (length first, then a pow2-padded payload so the
            # compile cache stays logarithmic in payload size).
            return self._device_byte_broadcast(data, root)
        return self._inner.broadcast(data, root)

    def _device_byte_broadcast(self, data: Optional[bytes], root: int) -> bytes:
        import jax.numpy as jnp

        is_root = self._rank == root
        check(not is_root or data is not None,
              "broadcast: root rank must supply data")
        n = jnp.asarray(
            np.array([len(data) if is_root else 0], np.int32))
        total = int(np.asarray(
            self._device_collective(n, ReduceOp.SUM, "allreduce"))[0])
        padded = max(1, 1 << (total - 1).bit_length()) if total else 1
        buf = np.zeros(padded, np.uint8)
        if is_root:
            buf[:total] = np.frombuffer(data, np.uint8)
        out = self._device_collective(
            jnp.asarray(buf), ReduceOp.SUM, "allreduce")
        return np.asarray(out)[:total].tobytes()

    def load_checkpoint(self):
        out = self._inner.load_checkpoint()
        # Same consensus exchange as checkpoint(), for the same span:
        # a relaunched rank resumes at version v exactly where survivors
        # committed v, so both issue the flags op as the FIRST inner op
        # of span v and the robust replay streams stay aligned.  (At a
        # healthy start every rank does this once at version 0.)
        self._maybe_reform()
        return out

    def checkpoint(self, global_model, local_model=None, lazy_global=None):
        self._inner.checkpoint(global_model, local_model, lazy_global)
        # The committed checkpoint is the all-ranks consensus boundary:
        # heal a degraded device plane here (reference recovered jobs
        # return to full speed the same way, src/allreduce_robust.cc:
        # 426-453).  The flags exchange runs AFTER the commit — the
        # FIRST inner op of the new version span — because a relaunched
        # rank re-enters through load_checkpoint at exactly that span
        # boundary and issues the same flags op first (load_checkpoint
        # below), keeping the robust replay streams aligned.  Committing
        # first also means survivors are never blocked pre-commit by a
        # dead peer: the relaunch then resumes at the NEW version and
        # skips the iteration whose device-plane results only the
        # survivors hold.
        self._maybe_reform()

    @property
    def version_number(self) -> int:
        return self._inner.version_number
