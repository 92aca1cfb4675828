"""Rendezvous tracker — the control plane, now a multi-tenant service.

TPU-native rebuild of the reference tracker
(reference: tracker/rabit_tracker.py:124-270): assigns ranks (stable per
task_id across restarts), computes the tree+ring topology, hands every
worker its connect/accept lists, relays worker log lines, and terminates
when every job it served has completed.

Design differences from the reference, on purpose:

* Rendezvous is a **full-world barrier**: a round (start or recover)
  completes only when all ``world`` workers have registered, then everyone
  receives a complete topology in one reply.  The reference instead
  incrementally repairs links (src/allreduce_base.cc:207-261); the barrier
  is simpler, and recovery in our robust layer already requires all ranks
  to re-rendezvous (survivors cascade into recovery via link resets).
* Tracker connections are one-shot: each command (start/recover/print/
  shutdown) is a fresh TCP connection, so the tracker holds no long-lived
  per-worker socket state.  The single exception is the heartbeat
  channel (``cmd=heartbeat``): one persistent connection per worker
  carrying periodic keepalives, feeding the deadline-based failure
  detector — liveness is decided proactively on the control plane
  instead of waiting for a collective to error on a corpse
  (doc/fault_tolerance.md "Durable checkpoints & heartbeats").
* The ring is the plain rank cycle and the tree is the binary heap over
  ranks; the reference's DFS edge-sharing optimisation
  (tracker/rabit_tracker.py:167-198) minimises distinct TCP links, which
  stops mattering once bulk data rides ICI/XLA instead of host TCP.
* **Elastic membership** (``min_workers``/``max_workers``): the world
  size is no longer frozen at rendezvous.  A non-member ``cmd=start``
  registrant is admitted as a *joiner* (up to ``max_workers``), a
  heartbeat-detected death becomes a *scale-down* (never below
  ``min_workers``) instead of only a same-rank relaunch, and either
  sets a pending TARGET world.  Members learn about the pending epoch
  at checkpoint-commit boundaries (``cmd=epoch`` polls + the engines'
  K_RESCALE consensus bit) and re-register with ``cmd=rescale``; the
  round completes at the target world, ranks are reassigned
  deterministically (survivors by old rank, then joiners by task_id)
  and the epoch counter in every topology reply is bumped.
* **Restartable control plane** (``state_dir``): the tracker journals
  its state (rank map, epoch, members, committed version, formation
  barrier, liveness timeline) through the atomic
  :class:`~rabit_tpu.ckpt.CheckpointStore` machinery on every mutation.
  A crashed tracker restarted on the same port replays the journal and
  the workers' registration/connect retry bridges the gap — coordinator
  death is a stall, not a job loss (doc/fault_tolerance.md "Elastic
  membership & tracker HA").
* **Multi-tenant service** (doc/fault_tolerance.md "Multi-tenant
  tracker"): every piece of per-job state above lives in a
  :class:`JobState` keyed by the ``job`` field of the worker hello
  (protocol ``MAGIC_JOB``; the classic hello lands in the ``default``
  job, so pre-multi-tenant workers are untouched on the wire).  Jobs
  are created on their first registrant — gated by admission control
  (``--max-jobs`` / ``--max-total-workers``, over-capacity submissions
  get a typed reject reply, re-admitted as soon as a finishing job
  completes) — finish on unanimous goodbye, and an orphan sweep GCs a
  job whose last member vanished without one.  Heartbeat sweeps, EOF
  sweeps, barrier eviction, rescale epochs and journal mutations are
  all job-scoped; obs reports land under ``--obs-dir/<job>/`` and
  journals under ``--state-dir/<job>/`` (the default job keeps the
  pre-tenant root layout), so one tenant's failure storm never touches
  a co-tenant's state.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import selectors
import socket
import struct
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

from rabit_tpu import ckpt as ckpt_mod
from rabit_tpu import obs
from rabit_tpu.sched import topo as sched_topo
from rabit_tpu.sched import tuner as sched_tuner
from rabit_tpu.tracker import protocol as P
from rabit_tpu.utils.checks import log

DEFAULT_JOB = P.DEFAULT_JOB


def tree_neighbors(rank: int, world: int) -> tuple[int, list[int]]:
    """Binary-heap tree: returns (parent, [parent]+children neighbor list).

    Same shape as the reference's tree map (tracker/rabit_tracker.py:150-166).
    """
    parent = (rank - 1) // 2 if rank > 0 else P.NONE
    neighbors = []
    if rank > 0:
        neighbors.append(parent)
    for child in (2 * rank + 1, 2 * rank + 2):
        if child < world:
            neighbors.append(child)
    return parent, neighbors


def ring_neighbors(rank: int, world: int) -> tuple[int, int]:
    return ((rank - 1) % world, (rank + 1) % world)


@dataclass
class _Registrant:
    sock: socket.socket
    task_id: str
    host: str
    port: int
    cmd: str = P.CMD_START


@dataclass
class _HbPeer:
    """One worker's persistent heartbeat connection (CMD_HEARTBEAT)."""

    sock: socket.socket
    task_id: str
    period_s: float
    last: float                    # monotonic time of the last beat
    buf: bytearray = field(default_factory=bytearray)
    dead: bool = False             # declared dead by the deadline sweep
    bye: bool = False              # clean shutdown seen
    notified: float = 0.0          # last on_dead notification (rearm)
    echo: bool = False             # obs frames seen: echo beats (rtt)
    # Pending echo bytes: a non-blocking send can write PART of a u32,
    # and the worker's echo parser assumes whole-word reads — so
    # unsent tail bytes are buffered and flushed first, never dropped
    # mid-word (a short write must not misalign the echo stream).
    ebuf: bytearray = field(default_factory=bytearray)


class _AdmissionReject(Exception):
    """Internal: a registration failed admission control; the handler
    turns it into the typed wire reject reply."""

    def __init__(self, code: int, kind: str, reason: str) -> None:
        super().__init__(reason)
        self.code = code
        self.kind = kind     # counter suffix: "jobs" | "workers"
        self.reason = reason


class JobState:
    """All control-plane state of ONE job (tenant) served by the
    tracker: rank map, membership, rendezvous barrier, formation
    barrier, heartbeat peers, elastic targets, liveness timeline,
    telemetry aggregation and the durable journal.  Every mutation the
    tracker performs on behalf of a worker is scoped to the worker's
    :class:`JobState` — fault isolation between tenants is structural,
    not policed."""

    def __init__(self, tracker: "Tracker", name: str,
                 n_workers: int) -> None:
        self._tracker = tracker
        self.name = name
        self.n_workers = n_workers
        # Lifecycle: ``touched`` flips on the first admitted worker
        # command (a job exists as a service object only once a worker
        # showed up); ``done`` on unanimous goodbye or orphan GC — a
        # done incarnation holds no capacity and a re-registration
        # under the same name is a NEW job submission.
        self.touched = False
        self.done = False
        self.last_activity = time.monotonic()
        self._rank_of: dict[str, int] = {}      # task_id -> stable rank
        # Tasks that finished (cmd=shutdown).  Keyed by task_id, not
        # rank: elastic rescales reassign ranks, task identity is the
        # stable coordinate.
        self._shutdown_tasks: set[str] = set()
        # Current-epoch membership (task_ids of the last completed
        # round).  Empty until the first round; from then on the job is
        # done when every member has shut down.
        self._members: set[str] = set()
        # Telemetry aggregation (print-channel extension): workers ship
        # rank-local summaries at shutdown (obs.OBS_SUMMARY_PREFIX); the
        # tracker aggregates min/mean/max across ranks into a per-job
        # report under the job's obs dir (doc/observability.md).  The
        # default job keeps the pre-tenant root layout; named jobs nest
        # under ``<obs-dir>/<job>/``.
        self._obs_dir: str | None = None
        self._obs_reports: dict[int, dict] = {}
        self._obs_lock = threading.Lock()
        # Live telemetry plane (doc/observability.md "Live telemetry"):
        # streamed delta frames fold into a per-rank rolling view
        # (journal-free by design) and the shipped collective spans
        # merge into per-op skew + rolling straggler scores.
        self._live = obs.LiveTable()
        self._spans = obs.SpanMerger()
        # Causal trace plane (doc/observability.md "Causal tracing &
        # postmortem"): sampled per-hop records stream in with the
        # frames and assemble into skew-corrected cross-rank timelines,
        # exposed on /trace (Chrome-trace JSON) and as the per-job
        # "trace" section of /status (bound-by verdict, per-link cost
        # table).
        self._traces = obs.TraceAssembler()
        self._straggling: set[int] = set()
        self._obs_frames_bad = 0
        # The job's wire codec as reported in its streamed frames
        # (uniform across ranks): keys the controller's online tuner
        # merges (sched/tuner.py table_kind).
        self._codec = "none"
        # Adaptive control plane (obs/adapt.py, tracker --adapt): the
        # per-job controller folds the merged spans into schedule
        # decisions; its directive (payload bucket -> schedule) and
        # straggler-demotion set ride every topology reply and are
        # journaled, so a restarted tracker keeps the job on its
        # learned schedule (the controller's rolling windows rebuild
        # from the live stream).
        self._controller: obs.AdaptiveController | None = None
        self._active_sched: dict[int, str] = {}
        self._demoted: set[int] = set()
        # A controller push pending: the next rendezvous round bumps
        # the epoch at the UNCHANGED world so the whole world adopts
        # the new directive together at a commit boundary.
        self._sched_switch_pending = False
        # True between a controller push and the first tick after its
        # epoch landed — lets the tick re-baseline the probe budget at
        # adoption time, not decision time.
        self._adapt_pushed = False
        self._last_groups: list[int] = []
        # task_ids that completed at least one rendezvous round: a fresh
        # cmd=start from one of these is a mid-job relaunch, flagged in
        # its topology reply (works even when the restarting platform
        # passes a clean environment).
        self._started_tasks: set[str] = set()
        self._pending: list[_Registrant] = []
        self._round_started: float | None = None  # first registrant time
        self._pending_lock = threading.Lock()
        # Keyed coordinator-service ports (cmd=jaxsvc): every worker of
        # THIS job asking for the same key gets the same port; the
        # service objects themselves are tracker-owned (retained until
        # the tracker closes).
        self._jaxsvc_keyed: dict[str, int] = {}
        # Formation barrier (cmd=formbar), one-shot per job: "open" ->
        # "done" (everyone posted) | "aborted" (a relaunch registered, a
        # recover round started, or the barrier timed out).
        self._formbar_state = "open"
        self._formbar_socks: list[socket.socket] = []
        self._formbar_posted: set[str] = set()
        self._formbar_timer: threading.Thread | None = None
        self._formbar_lock = threading.Lock()
        # Heartbeat failure detector state (protocol CMD_HEARTBEAT),
        # job-scoped: task ids are only unique within a job.
        self._hb_peers: dict[str, _HbPeer] = {}
        self._hb_seen: set[str] = set()  # tasks that ever heartbeat —
        # a SECOND channel for the same task is its relaunched life
        self._hb_lock = threading.Lock()
        # Job-scoped liveness/restart timeline (merged into the
        # obs_report recovery timeline next to the workers' events).
        self._events: collections.deque = collections.deque(maxlen=2048)
        # -- elastic membership state ----------------------------------
        self._epoch = 0
        # Pending rescale: the next rendezvous round completes at this
        # world instead of n_workers (None = no rescale pending).
        self._target_world: int | None = None
        self._dead_tasks: set[str] = set()   # members seen dead, unresolved
        self._joiners: set[str] = set()      # parked non-member starts
        # Every task with an unresolved death/loss verdict of ANY kind
        # (heartbeat EOF or deadline, registrant sweep, supervisor
        # note_dead) — cleared by re-registration / a fresh heartbeat
        # channel.  The orphan GC's evidence that the job's members
        # vanished rather than went quiet.
        self._lost_tasks: set[str] = set()
        self._scale_lock = threading.Lock()
        # One thread runs _finish_round at a time (the accept loop on
        # round fill, the heartbeat monitor on a target change).
        self._round_lock = threading.Lock()
        self._committed_version = 0          # max version cmd=epoch reported
        # -- durable control-plane journal (state_dir) -----------------
        self._state_store: ckpt_mod.CheckpointStore | None = None
        self._state_seq = 0
        self._journal_lock = threading.Lock()

    # -- config (tracker-wide knobs, getattr-safe for bare objects) ----
    @property
    def _registrant_timeout(self) -> float:
        return getattr(self._tracker, "_registrant_timeout", 600.0)

    @property
    def _elastic(self) -> bool:
        return getattr(self._tracker, "_elastic", False)

    @property
    def _min_workers(self) -> int | None:
        return getattr(self._tracker, "_min_workers", None)

    @property
    def _max_workers(self) -> int | None:
        return getattr(self._tracker, "_max_workers", None)

    def _tag(self) -> str:
        """Log prefix: the default job keeps the pre-tenant wording."""
        return "" if self.name == DEFAULT_JOB else f" [job {self.name}]"

    # -- lifecycle -----------------------------------------------------
    def job_done(self) -> bool:
        """Job completion.  Before the first round completes the only
        coordinate is the launch count; after it, the job is done when
        every CURRENT member shut down (leavers dropped by a rescale
        owe no goodbye)."""
        if self._members:
            return self._members <= self._shutdown_tasks
        return len(self._shutdown_tasks) >= self.n_workers

    def orphaned(self, now: float) -> str | None:
        """GC predicate for a job whose last member vanished without a
        unanimous goodbye: returns the reason, or None while the job is
        (possibly) alive.  Evidence-based — a job with live heartbeat
        channels, parked registrants, or recent control-plane activity
        is never a candidate, and a job that never armed heartbeats is
        only collected once every member holds an explicit death
        verdict (heartbeat EOF, registrant sweep, supervisor
        note_dead)."""
        if self.done or not self.touched:
            return None
        gc_sec = getattr(self._tracker, "_job_gc_sec", 30.0)
        if now - self.last_activity < gc_sec:
            return None
        with self._pending_lock:
            if self._pending:
                return None
        with self._hb_lock:
            if any(not p.dead for p in self._hb_peers.values()):
                return None
            hb_seen = bool(self._hb_seen)
        if not self._members:
            # Died before the first round ever completed: the only
            # evidence a worker existed at all is a loss verdict (the
            # registrant sweep reaped its parked socket) or a heartbeat
            # life that ended.  Without either, keep waiting — workers
            # may simply not have arrived yet.
            if self._lost_tasks or hb_seen:
                return ("every registrant lost before the first round "
                        "completed")
            return None
        accounted = (self._shutdown_tasks | self._lost_tasks
                     | self._dead_tasks)
        if self._members <= accounted:
            return "every member lost without a unanimous goodbye"
        if hb_seen:
            return (f"heartbeat channels gone and the job idle "
                    f"past {gc_sec:g}s")
        return None

    def close(self) -> None:
        """Drop this job's sockets (pending registrants, heartbeat
        channels) and release its formation barrier."""
        self._abort_formbar("job closing")
        with self._pending_lock:
            for reg in self._pending:
                try:
                    reg.sock.close()
                except OSError:
                    pass
            self._pending.clear()
            self._round_started = None
        with self._hb_lock:
            peers, self._hb_peers = dict(self._hb_peers), {}
        for peer in peers.values():
            try:
                peer.sock.close()
            except OSError:
                pass

    # -- elastic membership + durable journal --------------------------
    def _round_size(self) -> int:
        """How many registrants complete the current rendezvous round:
        the pending rescale target when one is set, else the world."""
        return (self._target_world if self._target_world is not None
                else self.n_workers)

    def _recompute_target(self) -> None:
        """(Re)derive the pending rescale target from membership deltas
        (joiners parked, members dead).  Scale-up needs ``max_workers``,
        scale-down needs ``min_workers`` and never undershoots it; a
        death the floor cannot absorb is left to the supervisor's
        same-rank relaunch path (target cleared).  A changed target
        re-checks round fullness — survivors may already be parked in a
        recover round that the new, smaller target completes."""
        if not self._elastic or not self._members:
            return
        with self._scale_lock:
            alive = self._members - self._dead_tasks
            target = len(alive)
            admitted = 0
            if self._max_workers is not None and self._joiners:
                admitted = min(len(self._joiners),
                               max(self._max_workers - target, 0))
                target += admitted
            if self._dead_tasks:
                if (self._min_workers is None or not alive
                        or target < self._min_workers):
                    target = None  # deaths the elastic floor can't absorb
            elif (target == self.n_workers and not admitted
                    and not self._sched_switch_pending):
                # Nothing changed — unless a controller push is
                # pending, which needs the same-world epoch to land.
                target = None
            changed = target != self._target_world
            self._target_world = target
        if not changed:
            return
        if target is not None:
            log("tracker:%s rescale pending -> world %d (epoch %d -> %d; "
                "%d alive, %d dead, %d joiner(s))", self._tag(), target,
                self._epoch, self._epoch + 1, len(alive),
                len(self._dead_tasks), len(self._joiners))
            self._events.append({
                "ts": time.time(), "name": "epoch", "phase": "pending",
                "epoch": self._epoch + 1, "from_world": self.n_workers,
                "to_world": target})
        self._journal()
        self._maybe_finish_round()

    def _maybe_finish_round(self) -> None:
        """Complete the rendezvous round if the (possibly just-changed)
        target makes the parked registrants a full house."""
        with self._pending_lock:
            full = 0 < self._round_size() <= len(self._pending)
        if full:
            self._finish_round()

    def _journal(self) -> None:
        """Persist the control-plane state through the atomic ckpt-store
        machinery (tmp+fsync+rename, CRC-stamped, bounded retention) so
        a restarted tracker resumes exactly here.  Best-effort: a full
        disk degrades HA, it never kills the running job."""
        if self._state_store is None:
            return
        with self._journal_lock:
            # Snapshot with a bounded retry: the accept, heartbeat and
            # round threads mutate these containers without one global
            # state lock, and iterating a deque/set mid-mutation raises
            # RuntimeError — which must never escape into the serve
            # loop.  A lost race only skips THIS write; the mutation
            # that raced re-journals right behind it.
            for _ in range(3):
                try:
                    state = {
                        "job": self.name,
                        "done": self.done,
                        "epoch": self._epoch,
                        "world": self.n_workers,
                        "rank_of": dict(self._rank_of),
                        "started": sorted(self._started_tasks),
                        "shutdown": sorted(self._shutdown_tasks),
                        "members": sorted(self._members),
                        # Deaths already detected must survive a crash:
                        # a dead worker never reconnects to re-earn its
                        # verdict, so a restart that forgot these would
                        # recompute the target from "everyone alive"
                        # and stall the round on corpses.  _joiners are
                        # deliberately NOT journaled — a parked joiner's
                        # socket died with the old tracker and its
                        # retry re-admits it; a phantom restored joiner
                        # would hold a target slot nothing can fill.
                        "dead": sorted(self._dead_tasks),
                        "lost": sorted(self._lost_tasks),
                        "target_world": self._target_world,
                        "committed_version": self._committed_version,
                        "formbar_state": self._formbar_state,
                        "formbar_posted": sorted(self._formbar_posted),
                        # Adaptive plane: what the controller learned
                        # must survive a tracker crash — a restarted
                        # tracker keeps handing out the learned
                        # directive (its rolling evidence rebuilds
                        # from the live stream).
                        "active_sched": {str(b): s for b, s
                                         in self._active_sched.items()},
                        "demoted": sorted(self._demoted),
                        "events": list(self._events)[-512:],
                    }
                    blob = json.dumps(state, sort_keys=True).encode()
                    break
                except RuntimeError:
                    continue
            else:
                log("tracker:%s state journal snapshot kept racing "
                    "mutations; skipping this write", self._tag())
                return
            self._state_seq += 1
            seq = self._state_seq
            try:
                self._state_store.persist(seq, state["world"], blob)
            except OSError as e:
                log("tracker:%s state journal write failed (seq %d): %s",
                    self._tag(), seq, e)

    def attach_store(self, store: ckpt_mod.CheckpointStore) -> None:
        """Wire this job's journal store; the sequence continues above
        whatever a previous incarnation left on disk."""
        self._state_store = store
        self._state_seq = store.newest_version() or 0

    def restore_journal(self) -> bool:
        """Replay the newest valid journal entry (tracker restart on the
        same port): rank map, epoch, membership, committed version and
        the formation barrier resume where the dead incarnation left
        them; the liveness timeline survives into the next obs report.
        Returns True when a journal was replayed."""
        dc = self._state_store.load_latest()
        if dc is None:
            return False
        try:
            state = json.loads(dc.global_blob.decode())
        except (ValueError, UnicodeDecodeError) as e:
            log("tracker:%s state journal unreadable (%s); starting "
                "fresh", self._tag(), e)
            return False
        self._state_seq = dc.version
        self.done = bool(state.get("done", False))
        self.n_workers = int(state.get("world", self.n_workers))
        self._epoch = int(state.get("epoch", 0))
        self._rank_of = {str(t): int(r)
                         for t, r in state.get("rank_of", {}).items()}
        self._started_tasks = set(state.get("started", []))
        self._shutdown_tasks = set(state.get("shutdown", []))
        self._members = set(state.get("members", []))
        self._dead_tasks = set(state.get("dead", []))
        self._lost_tasks = set(state.get("lost", []))
        tw = state.get("target_world")
        self._target_world = int(tw) if tw is not None else None
        self._committed_version = int(state.get("committed_version", 0))
        self._active_sched = {
            int(b): str(s)
            for b, s in (state.get("active_sched") or {}).items()
            if str(b).lstrip("-").isdigit() and int(b) > 0}
        self._demoted = {int(r) for r in state.get("demoted", [])}
        self._formbar_state = state.get("formbar_state", "open")
        self._formbar_posted = set(state.get("formbar_posted", []))
        if (self._formbar_state == "open"
                and len(self._formbar_posted) >= self.n_workers):
            self._formbar_state = "done"  # resolved mid-crash
        for ev in state.get("events", []):
            self._events.append(ev)
        self._events.append({"ts": time.time(), "name": "tracker",
                             "phase": "restart", "epoch": self._epoch,
                             "world": self.n_workers})
        log("tracker:%s journal replayed (seq %d): world=%d epoch=%d "
            "members=%d committed_version=%d formbar=%s", self._tag(),
            dc.version, self.n_workers, self._epoch, len(self._members),
            self._committed_version, self._formbar_state)
        return True

    # -- formation barrier ---------------------------------------------
    def _formbar_post(self, sock: socket.socket, task_id: str) -> None:
        """See protocol.CMD_FORMBAR.  Parks the socket until the barrier
        resolves; posts after resolution get the resolved answer."""
        with self._formbar_lock:
            if self._formbar_state != "open":
                self._formbar_reply(sock, self._formbar_state == "done")
                return
            self._formbar_socks.append(sock)
            self._formbar_posted.add(task_id)
            if len(self._formbar_posted) >= self.n_workers:
                self._resolve_formbar_locked("done")
                self._journal()
                return
            if self._formbar_timer is None:
                self._formbar_timer = threading.Thread(
                    target=self._formbar_timeout, daemon=True)
                self._formbar_timer.start()
        # Journal each post: a tracker crash mid-barrier must not lose
        # who already arrived — the restarted tracker resumes the round
        # and the (re-)posts of the parked workers complete it.
        self._journal()

    @staticmethod
    def _formbar_reply(sock: socket.socket, proceed: bool) -> None:
        try:
            P.send_u32(sock, 1 if proceed else 0)
        except OSError:
            pass
        try:
            sock.close()
        except OSError:
            pass

    def _resolve_formbar_locked(self, state: str) -> None:
        self._formbar_state = state
        socks, self._formbar_socks = self._formbar_socks, []
        for s in socks:
            self._formbar_reply(s, state == "done")

    def _abort_formbar(self, why: str) -> None:
        with self._formbar_lock:
            if self._formbar_state == "open" and (
                    self._formbar_socks or self._formbar_posted):
                log("tracker:%s aborting formation barrier (%s)",
                    self._tag(), why)
            if self._formbar_state == "open":
                self._resolve_formbar_locked("aborted")

    def _formbar_timeout(self) -> None:
        deadline = time.monotonic() + self._registrant_timeout
        while time.monotonic() < deadline:
            time.sleep(0.5)
            with self._formbar_lock:
                if self._formbar_state != "open":
                    return
        with self._formbar_lock:
            if self._formbar_state == "open":
                log("tracker:%s formation barrier timed out "
                    "(%d/%d posted); aborting formation", self._tag(),
                    len(self._formbar_posted), self.n_workers)
                self._resolve_formbar_locked("aborted")

    def keyed_jax_service(self, key: str) -> int:
        """Coordinator-service lookup for workers (cmd=jaxsvc).

        ``key == ""``: always a fresh service (device-plane reform needs
        a new incarnation per epoch).  Non-empty key (the engines send
        "init" at job start): create-or-get under one lock — every
        worker of THIS job asks for the same key and receives the SAME
        port, so the init-time coordinator exchange involves no
        worker-to-worker collective at all.  That keeps version-span 0
        free of engine-internal ops: a worker relaunched before the
        first checkpoint replays a span containing only application
        ops, exactly like the survivors'."""
        tr = self._tracker
        with tr._jaxsvc_lock:
            if key and key in self._jaxsvc_keyed:
                return self._jaxsvc_keyed[key]
            port = tr._fresh_jax_service_locked(self.n_workers)
            if key and port:
                self._jaxsvc_keyed[key] = port
            return port

    # -- live telemetry plane ------------------------------------------
    def _obs_frame_ingest(self, task_id: str, raw: bytes) -> None:
        """One streamed obs frame arriving on the heartbeat channel:
        fold the delta metrics into the live table, merge the spans,
        and re-check the straggler verdicts.  Malformed frames are
        counted and dropped — they arrive from the network."""
        try:
            payload = json.loads(raw.decode())
            rank = int(payload["rank"])
        except (ValueError, KeyError, TypeError, UnicodeDecodeError) as e:
            self._obs_frames_bad += 1
            log("tracker:%s malformed obs frame from task %r dropped: %s",
                self._tag(), task_id, e)
            return
        self.last_activity = time.monotonic()
        # The wire codec label (uniform across ranks — replicated
        # config) scopes the controller's online tuner merges: winners
        # measured over a quantized wire never answer a full-width job.
        codec = payload.get("codec")
        if isinstance(codec, str) and codec:
            self._codec = codec
        now = time.time()
        self._live.ingest(rank, now, payload)
        # Clock-skew calibration for the trace plane: the frame carries
        # the sender's wall clock, and its hb-RTT estimate (echoed
        # beats, read time) bounds the flight time — half of it is the
        # classic NTP-style one-way correction.  Folded as a rolling
        # median per rank, so hop timelines from skewed hosts still
        # order causally.
        sent_ts = payload.get("ts")
        if isinstance(sent_ts, (int, float)) and sent_ts > 0:
            rtt = (payload.get("gauges") or {}).get("hb.rtt.seconds.p50")
            rtt = rtt if isinstance(rtt, (int, float)) and rtt > 0 else 0.0
            self._traces.note_offset(rank, now - float(sent_ts) - rtt / 2.0)
        hops = payload.get("hops")
        if hops:
            self._traces.add(rank, hops, self.n_workers)
        spans = payload.get("spans")
        if spans:
            self._spans.add(rank, spans, self.n_workers)
            self._check_stragglers()

    def _check_stragglers(self) -> None:
        """Emit a liveness-style ``straggler`` event when a rank's
        rolling score crosses ``rabit_straggler_factor`` (and a
        recovery event when it falls back under half of it — the
        hysteresis keeps a borderline rank from flapping the
        timeline)."""
        tracker = self._tracker
        factor = getattr(tracker, "_straggler_factor", 3.0)
        min_sec = getattr(tracker, "_straggler_min_sec", 0.05)
        verdicts = self._spans.straggler_verdicts(factor, min_sec)
        current = {r for r, _s, _l in verdicts}
        for rank, score, late in verdicts:
            if rank in self._straggling:
                continue
            self._straggling.add(rank)
            log("tracker:%s rank %d is STRAGGLING: mean lateness "
                "%.1f ms = %.1fx the op cost (factor %g)", self._tag(),
                rank, late * 1e3, score, factor)
            self._events.append({
                "ts": time.time(), "name": "straggler",
                "phase": "straggler", "rank": rank,
                "score": round(score, 2),
                "lateness_sec": round(late, 4), "factor": factor})
            tracker._count("job.stragglers")
        for rank in sorted(self._straggling - current):
            if self._spans.score(rank) < factor / 2:
                self._straggling.discard(rank)
                log("tracker:%s rank %d recovered from straggling",
                    self._tag(), rank)
                self._events.append({
                    "ts": time.time(), "name": "straggler",
                    "phase": "recovered", "rank": rank})

    # -- adaptive control plane (obs/adapt.py) -------------------------
    def _adapt_tick(self) -> None:
        """One controller pass for this job (tracker --adapt sweep):
        fold the merged spans into a schedule/demotion verdict and push
        any decision as a schedule-switch epoch.  Skipped while a
        rescale is already in flight — one pending epoch at a time
        keeps the round bookkeeping trivial."""
        tracker = self._tracker
        if not self._members or self.n_workers < 2:
            return
        ctl = self._controller
        if ctl is None or ctl.world != self.n_workers \
                or ctl.groups != self._last_groups:
            # (Re)built on first use and after every membership change:
            # the candidate set and demotion streaks belong to ONE
            # (world, topology); learned directives persist in
            # _active_sched and the TuningCache.
            if ctl is not None:
                # An actual world/groups CHANGE: timings, lateness and
                # straggler evidence measured at the old world (old
                # rank numbering!) must not feed the new one's
                # decisions or cache merges.
                self._spans.reset_windows()
                self._straggling.clear()
            ctl = self._controller = obs.AdaptiveController(
                self.n_workers, self._last_groups,
                straggler_factor=getattr(tracker, "_straggler_factor",
                                         3.0))
            # Demotions outside the new rank space are meaningless (a
            # shrink renumbered the world); in-range ones carry over
            # and self-heal via the controller's no-signal
            # reinstatement if the rank's straggling didn't.
            self._demoted = {r for r in self._demoted
                             if r < self.n_workers}
            ctl.demoted = set(self._demoted)
            ctl.active = dict(self._active_sched)
            # settled holds PLAIN schedule names (the scorer's
            # incumbent domain); a journaled slashed ``sched/codec``
            # directive value seeds only its schedule half — the codec
            # suffix is re-derived from live evidence each tick.
            ctl.settled = {b: s.split("/", 1)[0]
                           for b, s in self._active_sched.items()}
        with self._scale_lock:
            if self._target_world is not None:
                return  # an epoch is already pending; decide after it
        if self._adapt_pushed:
            # The pushed epoch completed since the last tick (target is
            # clear again): the workers adopted the directive only NOW,
            # so the probe's abandonment budget starts here.
            self._adapt_pushed = False
            ctl.note_epoch_landed(self._spans.merged_ops)
        # wire=self._codec: schedule evidence is scoped to spans that
        # actually rode the job's codec wire — full-width opt-out ops
        # never steer the verdicts merged under codec-keyed rows.
        actions = ctl.tick(self._spans, self._spans.scores(),
                           wire=getattr(self, "_codec", "none"))
        if not actions:
            return
        for act in actions:
            self._apply_controller_action(ctl, act)
        self._active_sched = dict(ctl.active)
        self._demoted = set(ctl.demoted)
        if any(a.kind in ("probe", "switch", "settle", "demote",
                          "reinstate", "codec") for a in actions):
            self._adapt_pushed = True
            self._push_sched_epoch()
        self._journal()

    def _apply_controller_action(self, ctl, act) -> None:
        """Record one controller decision: timeline event (with the
        evidence), service counter, structured log — and, for final
        schedule verdicts, the online TuningCache merge that makes the
        next job start warm."""
        tracker = self._tracker
        # Liveness-style past-tense phases on the timeline (the
        # decision KIND keeps the imperative form for counters/soak).
        phase = {"demote": "demoted",
                 "reinstate": "reinstated"}.get(act.kind, act.kind)
        ev = {"ts": act.ts, "name": "controller", "phase": phase}
        if act.bucket is not None:
            ev["bucket"] = act.bucket
        if act.sched is not None:
            ev["sched"] = act.sched
        if act.rank is not None:
            ev["rank"] = act.rank
        evd = act.evidence or {}
        for k in ("incumbent", "incumbent_sec", "challenger_sec",
                  "score", "factor", "why",
                  # codec-override decisions (RABIT_ADAPT_CODEC)
                  "base_sec", "codec_sec", "codec"):
            if k in evd:
                ev[k] = evd[k]
        self._events.append(ev)
        tracker._count(f"controller.decisions.{act.kind}")
        if act.kind == "switch":
            log("tracker:%s controller SWITCH %dB -> %s (incumbent %s "
                "%.3fms vs challenger %.3fms over %s samples)",
                self._tag(), act.bucket or 0, act.sched,
                evd.get("incumbent"),
                float(evd.get("incumbent_sec", 0)) * 1e3,
                float(evd.get("challenger_sec", 0)) * 1e3,
                evd.get("samples"))
        elif act.kind == "demote":
            log("tracker:%s controller DEMOTED rank %d from leader "
                "roles (straggler score %s > factor %s)", self._tag(),
                act.rank, evd.get("score"), evd.get("factor"))
        elif act.kind == "reinstate":
            log("tracker:%s controller REINSTATED rank %d (score %s)",
                self._tag(), act.rank, evd.get("score"))
        else:
            log("tracker:%s controller %s %s", self._tag(), act.kind,
                act.sched or act.rank)
        if act.kind in ("switch", "settle") and act.bucket is not None:
            merge = getattr(tracker, "_tune_merge", None)
            if merge is not None:  # bare test objects lack the cache
                merge("allreduce", self.n_workers, act.bucket, act.sched,
                      getattr(self, "_codec", "none"))

    def _push_sched_epoch(self) -> None:
        """Arm a schedule-switch epoch: the next rendezvous round
        completes at the UNCHANGED world with a bumped epoch, so every
        member adopts the new directive/demotion set together at its
        next commit boundary (the K_RESCALE consensus — PR 6's rescale
        choreography reused verbatim)."""
        with self._scale_lock:
            self._sched_switch_pending = True
            if self._target_world is None:
                self._target_world = len(self._members) or self.n_workers
        # No journal here: the only caller (_adapt_tick) journals right
        # after applying the whole action batch — one atomic write per
        # decision, not two back-to-back.
        self._maybe_finish_round()

    # -- telemetry aggregation -----------------------------------------
    def _obs_ingest(self, raw: str) -> None:
        """One rank's shutdown summary arriving on the print channel.
        Summaries for the same rank merge section-wise: a layered engine
        ships two (the XLA engine's device-plane instruments plus its
        host inner's — disjoint metric names), and within one section
        the newest shipment wins per name (a relaunched worker's final
        life supersedes; only lives that reach shutdown ship at all)."""
        try:
            payload = json.loads(raw)
            rank = int(payload["rank"])
        except (ValueError, KeyError, TypeError) as e:
            log("tracker:%s malformed obs summary dropped: %s",
                self._tag(), e)
            return
        with self._obs_lock:
            have = self._obs_reports.get(rank)
            if have is None:
                self._obs_reports[rank] = payload
                return
            for section, vals in payload.get("metrics", {}).items():
                have.setdefault("metrics", {}).setdefault(
                    section, {}).update(vals)
            have.setdefault("recovery", []).extend(
                payload.get("recovery", []))
            have["engine"] = payload.get("engine", have.get("engine"))

    def _write_obs_report(self) -> None:
        """Aggregate the shipped rank summaries into the per-job report
        (min/mean/max across ranks + a merged recovery timeline; the
        tracker's own liveness/restart transitions land on the same
        timeline, ts-sorted next to the recovery phases they caused).
        Lands under this JOB's obs dir — co-tenant reports never
        collide."""
        with self._obs_lock:
            reports = dict(self._obs_reports)
        tracker_events = list(self._events)
        if not self._obs_dir or not (reports or tracker_events):
            return
        timeline = list(tracker_events)
        for rank, rep in reports.items():
            for ev in rep.get("recovery", []):
                ev = dict(ev)
                ev.setdefault("rank", rank)
                timeline.append(ev)
        timeline.sort(key=lambda e: e.get("ts", 0.0))
        report = {
            "job": self.name,
            "world": self.n_workers,
            "ranks_reported": sorted(reports),
            "ranks": {str(r): rep for r, rep in sorted(reports.items())},
            "aggregate": obs.aggregate_snapshots(
                [rep.get("metrics", {}) for rep in reports.values()]),
            "recovery_timeline": timeline,
            "service": self._tracker._service_report(),
        }
        # Sharded control plane: a job hosted by a ShardServer stamps
        # its shard index so a fleet-collected report stays attributable
        # after the files leave the shard's obs dir.
        shard = getattr(self._tracker, "_shard_index", None)
        if shard is not None:
            report["shard"] = shard
        # Live-plane sections (streaming export + merged spans): the
        # straggler table and per-schedule latency/skew breakdown the
        # obs_report renderer turns into tables.
        span_rep = self._spans.report()
        if span_rep["merged_ops"]:
            report["straggler"] = {
                "ranks": span_rep["ranks"],
                "straggling": sorted(self._straggling),
                "factor": getattr(self._tracker,
                                  "_straggler_factor", 3.0),
            }
            report["sched_latency"] = span_rep["sched"]
        # Adaptive-controller section: the decisions with their
        # evidence, the directive the job converged on and the
        # demotion set (rendered by obs_report as the decision table).
        if self._controller is not None or self._active_sched \
                or self._demoted:
            ctl = self._controller
            report["controller"] = {
                "active_sched": {str(b): s for b, s
                                 in sorted(self._active_sched.items())},
                "demoted": sorted(self._demoted),
                "decisions": ([d.as_dict() for d in ctl.decisions]
                              if ctl is not None else []),
                "counters": (dict(ctl.counters)
                             if ctl is not None else {}),
            }
        live = self._live.report()
        if live:
            report["live"] = {"ranks": live,
                              "frames_bad": self._obs_frames_bad}
        try:
            os.makedirs(self._obs_dir, exist_ok=True)
            path = os.path.join(self._obs_dir, "obs_report.json")
            with open(path, "w") as f:
                json.dump(report, f, indent=2, sort_keys=True)
            log("tracker:%s wrote obs report (%d ranks) to %s",
                self._tag(), len(reports), path)
        except OSError as e:
            log("tracker:%s obs report write failed: %s", self._tag(), e)

    # -- liveness / heartbeat ------------------------------------------
    def _emit_liveness(self, phase: str, task_id: str, **fields) -> None:
        """One control-plane liveness transition (alive / dead / lost /
        relaunch) for the merged obs timeline."""
        ev = {"ts": time.time(), "name": "liveness", "phase": phase,
              "task": task_id}
        rank = self._rank_of.get(task_id)
        if rank is not None:
            ev["rank"] = rank
        for k, v in fields.items():
            if v is not None:
                ev[k] = v
        self._events.append(ev)

    def note_dead(self, task_id: str) -> None:
        """Supervisor-facing death notice: the launcher's keepalive saw
        the worker process exit and will not relaunch it (elastic
        leave).  Redundant when the heartbeat channel is armed — its
        EOF verdict fires first and ``_note_dead`` dedups — but the
        ONLY death signal the tracker gets in elastic mode without
        heartbeats.  Liveness first, so the timeline orders the loss
        ahead of the scale-down it triggers."""
        self._lost_tasks.add(task_id)
        if not self._elastic or task_id in self._dead_tasks:
            return
        self._emit_liveness("lost", task_id, supervisor=1)
        self._evict_registrant(task_id, "supervisor reported it dead")
        self._note_dead(task_id)

    def _note_dead(self, task_id: str) -> None:
        """Elastic-mode death bookkeeping: a member the heartbeat layer
        saw die (EOF without the goodbye, or a missed-beat verdict) is
        marked dead and the rescale target recomputed — scale-down
        instead of waiting for a same-rank relaunch.  Callers emit the
        liveness transition FIRST, so the timeline orders the death
        ahead of the epoch move it causes."""
        if not self._elastic or task_id not in self._members:
            return
        if task_id in self._dead_tasks:
            return
        self._dead_tasks.add(task_id)
        self._recompute_target()

    def _hb_register(self, sock: socket.socket, task_id: str,
                     period_ms: int) -> None:
        """A worker opened its persistent heartbeat channel; a fresh
        connection for a known task is its relaunched life."""
        sock.setblocking(False)
        peer = _HbPeer(sock, task_id, max(int(period_ms), 1) / 1000.0,
                       time.monotonic())
        with self._hb_lock:
            old = self._hb_peers.pop(task_id, None)
            relaunched = old is not None or task_id in self._hb_seen
            self._hb_seen.add(task_id)
            self._hb_peers[task_id] = peer
        if old is not None:
            try:
                old.sock.close()
            except OSError:
                pass
        log("tracker:%s heartbeat channel open for task %r "
            "(period %d ms%s)", self._tag(), task_id, period_ms,
            ", relaunched" if relaunched else "")
        self._emit_liveness("alive", task_id,
                            relaunched=1 if relaunched else None)
        self._lost_tasks.discard(task_id)
        if self._elastic and task_id in self._dead_tasks:
            # Back from the dead (relaunch beat the scale-down): the
            # pending target stops counting it out.
            self._dead_tasks.discard(task_id)
            self._recompute_target()

    def _hb_forget(self, peer: _HbPeer) -> None:
        with self._hb_lock:
            if self._hb_peers.get(peer.task_id) is peer:
                del self._hb_peers[peer.task_id]
        try:
            peer.sock.close()
        except OSError:
            pass

    def _hb_drain(self, peer: _HbPeer, now: float) -> None:
        """Consume whatever beats arrived on one heartbeat socket."""
        tracker = self._tracker
        try:
            data = peer.sock.recv(4096)
        except (BlockingIOError, InterruptedError):
            return
        except OSError:
            data = b""
        if not data:
            # EOF/RST without the bye: the process died.  The launcher
            # watches the process directly, so no on_dead escalation —
            # but the parked registrant (if any) must still go, and the
            # transition belongs in the timeline.
            # No registrant eviction here: the dead process's parked
            # rendezvous socket EOFs too and the registrant sweep reaps
            # it, while a late-drained EOF must never close a freshly
            # relaunched life's registrant parked under the same task.
            self._hb_forget(peer)
            if not peer.bye and not peer.dead and not tracker._stopped:
                log("tracker:%s heartbeat channel for task %r lost (EOF)",
                    self._tag(), peer.task_id)
                self._emit_liveness("lost", peer.task_id)
                self._lost_tasks.add(peer.task_id)
                # Elastic mode: a SIGKILL'd/preempted worker EOFs its
                # channel instantly and never earns a deadline verdict —
                # this IS the death signal that triggers scale-down.
                self._note_dead(peer.task_id)
            return
        peer.buf += data
        while len(peer.buf) >= 4:
            (beat,) = struct.unpack_from("<I", peer.buf)
            if beat == P.HEARTBEAT_OBS:
                # Telemetry frame multiplexed onto the beat stream:
                # sentinel, u32 length, JSON payload.  Incomplete
                # frames wait in peer.buf for the next drain.
                if len(peer.buf) < 8:
                    break
                (ln,) = struct.unpack_from("<I", peer.buf, 4)
                if ln > P.MAX_PRINT_LEN:
                    log("tracker:%s oversized obs frame (%d bytes) from "
                        "task %r; dropping the heartbeat channel",
                        self._tag(), ln, peer.task_id)
                    self._hb_forget(peer)
                    return
                if len(peer.buf) < 8 + ln:
                    break
                raw = bytes(peer.buf[8:8 + ln])
                del peer.buf[:8 + ln]
                peer.last = now   # a frame proves liveness like a beat
                peer.echo = True  # an obs worker reads echoes (hb.rtt)
                self._obs_frame_ingest(peer.task_id, raw)
                continue
            del peer.buf[:4]
            if beat == P.HEARTBEAT_BYE:
                peer.bye = True
                self._hb_forget(peer)
                self._emit_liveness("shutdown", peer.task_id)
                return
            peer.last = now
            if peer.echo:
                # Echo the beat back so the worker can measure its
                # heartbeat round trip (hb.rtt.seconds).  Best-effort:
                # a backed-up socket drops WHOLE echoes (bounded
                # pending buffer), while a short write keeps its tail
                # buffered so the worker's u32 parser never misaligns.
                if len(peer.ebuf) <= 60:  # cap: 16 pending echoes
                    peer.ebuf += struct.pack("<I", beat)
                try:
                    sent = peer.sock.send(peer.ebuf)
                    del peer.ebuf[:sent]
                except (BlockingIOError, InterruptedError):
                    pass
                except OSError:
                    peer.ebuf.clear()  # channel dying; EOF path owns it
            if peer.dead:
                # Beats resumed after a dead verdict (a SIGCONT'd rank
                # the supervisor has not reaped yet): record the flap;
                # the supervisor's kill remains in flight.
                peer.dead = False
                log("tracker:%s task %r resumed heartbeats after a dead "
                    "verdict", self._tag(), peer.task_id)
                self._emit_liveness("alive", peer.task_id, resumed=1)
                self._lost_tasks.discard(peer.task_id)
                if self._elastic and peer.task_id in self._dead_tasks:
                    # The scale-down verdict is withdrawn: the rank is
                    # demonstrably alive on the SAME channel (no
                    # relaunch happened), so it keeps its membership
                    # instead of staying permanently counted out.
                    self._dead_tasks.discard(peer.task_id)
                    self._recompute_target()

    def _hb_mark_dead(self, peer: _HbPeer, phase: str, why: str) -> None:
        """Deadline verdict: evict the corpse from the barrier and tell
        the supervisor.  Re-notifies every miss budget while the verdict
        stands, so a supervisor that skipped a kill (restart grace) gets
        another chance instead of the job wedging."""
        tracker = self._tracker
        renotify = max(peer.period_s * tracker._hb_miss, 0.5)
        now = time.monotonic()
        if peer.dead and now - peer.notified < renotify:
            return
        first = not peer.dead
        peer.dead = True
        peer.notified = now
        if first:
            log("tracker:%s task %r declared dead by the heartbeat sweep "
                "(%s)", self._tag(), peer.task_id, why)
            self._emit_liveness(phase, peer.task_id, why=why)
            self._lost_tasks.add(peer.task_id)
            # Evict only on the FIRST verdict: no EOF means the hung
            # process is still alive holding its sockets, so a parked
            # registrant is provably the hung life's own.  A re-notify
            # runs after the supervisor's kill — by then the task's
            # NEXT life may already be parked, and closing its socket
            # would abort the very relaunch the kill arranged.
            self._evict_registrant(peer.task_id, why)
            # Elastic mode: the liveness verdict above precedes this —
            # scale-down is its consequence on the timeline.
            self._note_dead(peer.task_id)
        if tracker._on_dead is not None:
            try:
                tracker._on_dead(peer.task_id)
            except Exception as e:  # noqa: BLE001 — detector must survive
                log("tracker:%s on_dead callback failed: %s",
                    self._tag(), e)

    def _evict_registrant(self, task_id: str, why: str) -> None:
        """Drop a dead task's PARKED rendezvous registrant so the round
        re-opens (the hung-but-connected sibling of the EOF-based
        registrant sweep: a SIGSTOP'd rank keeps its sockets open, so
        only the heartbeat verdict can evict it)."""
        with self._pending_lock:
            if len(self._pending) >= self._round_size():
                return  # full round: the reply loop owns these sockets
            lost = [r for r in self._pending if r.task_id == task_id]
            if not lost:
                return
            self._pending = [r for r in self._pending
                             if r.task_id != task_id]
            if not self._pending:
                self._round_started = None
        for reg in lost:
            log("tracker:%s evicted registrant task %r from the "
                "rendezvous barrier (%s); the round re-opens for its "
                "relaunch", self._tag(), reg.task_id, why)
            try:
                reg.sock.close()
            except OSError:
                pass

    def sweep_registrants_once(self) -> None:
        """One pass of the dead-registrant sweep: drop EOF'd parked
        registrants so a partially-filled round re-opens instead of
        wedging the survivors (see Tracker._sweep_registrants)."""
        with self._pending_lock:
            if (not self._pending
                    or len(self._pending) >= self._round_size()):
                return
            socks = [r.sock for r in self._pending]
        # selectors (epoll/poll), not select.select: fds above
        # FD_SETSIZE would make select raise on every pass and
        # silently disable the sweep for big/long-lived jobs.
        sel = selectors.DefaultSelector()
        try:
            for s in socks:
                try:
                    sel.register(s, selectors.EVENT_READ)
                except (OSError, ValueError):
                    continue  # closed under us; next sweep re-checks
            ready = [key.fileobj for key, _ in sel.select(0)]
        finally:
            sel.close()
        dead = set()
        for s in ready:
            try:
                if s.recv(1, socket.MSG_PEEK) == b"":
                    dead.add(s)
            except OSError:
                dead.add(s)
        if not dead:
            return
        with self._pending_lock:
            if len(self._pending) >= self._round_size():
                return  # round filled meanwhile: let it reply
            lost = [r for r in self._pending if r.sock in dead]
            self._pending = [r for r in self._pending
                             if r.sock not in dead]
            if not self._pending:
                self._round_started = None
        for reg in lost:
            log("tracker:%s registrant task %r (cmd=%s) lost during "
                "the rendezvous barrier; dropping it and re-opening "
                "the round (its restart will re-register)",
                self._tag(), reg.task_id, reg.cmd)
            # Liveness BEFORE any membership/topology consequence:
            # the obs timeline must order the loss causally ahead of
            # the rescale/round it triggers.
            self._emit_liveness("lost", reg.task_id, barrier=1)
            self._lost_tasks.add(reg.task_id)
            try:
                reg.sock.close()
            except OSError:
                pass
            if self._elastic:
                if reg.task_id in self._joiners:
                    # A joiner that died while parked stops holding
                    # a slot in the pending target.
                    self._joiners.discard(reg.task_id)
                    self._recompute_target()
                elif reg.task_id in self._members:
                    self._note_dead(reg.task_id)

    # -- rendezvous ----------------------------------------------------
    def register(self, sock: socket.socket, cmd: str, task_id: str,
                 host: str, port: int) -> None:
        """Park one start/recover/rescale registrant in this job's
        rendezvous barrier (and complete the round if it fills)."""
        self.last_activity = time.monotonic()
        self._lost_tasks.discard(task_id)
        # Any recover/rescale round, or a fresh start from a task
        # that already ran, means the membership moved: an open
        # formation barrier can never complete — release it as
        # aborted so no survivor walks into the doomed device-group
        # registration.
        if cmd != P.CMD_START or task_id in self._started_tasks:
            self._abort_formbar("task %r re-registered (cmd=%s)"
                                % (task_id, cmd))
            if cmd == P.CMD_START:
                # A mid-job relaunch re-registering: a restart event
                # for the merged liveness timeline.
                self._emit_liveness("relaunch", task_id)
        # Registered: the socket now waits on the barrier, not on a
        # half-read message — lift the handshake timeout.
        sock.settimeout(self._registrant_timeout)
        # A re-registration from the same task replaces its stale entry
        # (e.g. worker crashed after registering, restarted mid-round).
        with self._pending_lock:
            stale = [r for r in self._pending if r.task_id == task_id]
            for r in stale:
                try:
                    r.sock.close()
                except OSError:
                    pass
            self._pending = [r for r in self._pending
                             if r.task_id != task_id]
            if not self._pending:
                self._round_started = time.monotonic()
            self._pending.append(
                _Registrant(sock, task_id, host, port, cmd))
        if self._elastic:
            if task_id in self._dead_tasks:
                # A presumed-dead member registered — ANY cmd proves
                # life (a supervisor relaunch's fresh start, or a
                # live member whose abandoned registration socket
                # the sweep mistook for a death retrying its
                # recover/rescale) — so it must not stay counted
                # out of the pending target.
                self._dead_tasks.discard(task_id)
                self._recompute_target()
            elif (cmd == P.CMD_START
                    and self._members and task_id not in self._members
                    and self._max_workers is not None):
                # Late joiner: parks until a rescale round admits it.
                if task_id not in self._joiners:
                    self._joiners.add(task_id)
                    self._emit_liveness("join_request", task_id)
                    self._recompute_target()
        self._maybe_finish_round()

    def _assign_ranks(self, regs: list[_Registrant] | None = None) -> None:
        # Shuffle the free-rank pool before handing ranks to NEW task
        # ids (the reference shuffles its todo_nodes for load balance,
        # tracker/rabit_tracker.py:242): arrival order otherwise
        # correlates host startup speed with tree position, piling the
        # root's traffic onto whatever machine booted first.  Restarted
        # tasks keep their old rank regardless (stable-rank contract).
        # RABIT_TRACKER_SHUFFLE=0 restores plain arrival order
        # (deterministic rank <-> arrival mapping for debugging).
        #
        # RABIT_TRACKER_PIN_RANKS=1: a task_id that is a decimal integer
        # in [0, n_workers) CLAIMS that rank.  This is the mixed-mode
        # alignment knob (doc/scaling.md): when an external runtime
        # already fixed each process's jax.process_index(), the engine
        # registers with task_id = that index, and pinning makes the
        # control-plane rank equal to it — the XLA engine requires the
        # two numberings to agree before it will use the device plane.
        import random

        if regs is None:
            regs = self._pending
        used = set(self._rank_of.values())
        if os.environ.get("RABIT_TRACKER_PIN_RANKS", "0") in (
                "1", "true", "yes"):
            for reg in regs:
                tid = reg.task_id
                if tid not in self._rank_of and tid.isdecimal():
                    r = int(tid)
                    if r < self.n_workers and r not in used:
                        self._rank_of[tid] = r
                        used.add(r)
        free = [r for r in range(self.n_workers) if r not in used]
        if os.environ.get("RABIT_TRACKER_SHUFFLE", "1") not in (
                "0", "false", "no"):
            random.shuffle(free)
        it = iter(free)
        for reg in regs:
            if reg.task_id not in self._rank_of:
                self._rank_of[reg.task_id] = next(it)

    def _assign_ranks_rescale(self, regs: list[_Registrant],
                              world: int) -> None:
        """Deterministic rank reassignment for a rescale round:
        surviving members keep their relative (old-rank) order — a pure
        scale-up moves nobody — and joiners follow, sorted by task_id,
        compacting the rank space to exactly ``[0, world)``."""
        old = sorted((r for r in regs if r.task_id in self._rank_of),
                     key=lambda r: self._rank_of[r.task_id])
        new = sorted((r for r in regs if r.task_id not in self._rank_of),
                     key=lambda r: r.task_id)
        self._rank_of = {reg.task_id: i for i, reg in enumerate(old + new)}
        assert len(self._rank_of) == world

    def _select_round_locked(self, world: int
                             ) -> tuple[list[_Registrant],
                                        list[_Registrant]]:
        """Pick which parked registrants form this round (caller holds
        ``_pending_lock``).  Normally everyone; when MORE are parked
        than the round admits (joiners beyond ``max_workers``), members
        and already-ranked tasks go first, then joiners by task_id —
        the extras stay parked for a later epoch."""
        pending = list(self._pending)
        if len(pending) <= world:
            return pending, []
        core = [r for r in pending
                if not self._members or r.task_id in self._members
                or r.task_id in self._rank_of]
        rest = sorted((r for r in pending if r not in core),
                      key=lambda r: r.task_id)
        chosen = (core + rest)[:world]
        chosen_ids = {id(r) for r in chosen}
        extras = [r for r in pending if id(r) not in chosen_ids]
        return chosen, extras

    def _topo_groups(self, by_rank: dict, world: int) -> list[int]:
        """Host-group handout for the topology-aware schedules: one
        group id per rank.  Ranks whose registrants advertised the same
        host share an id (the ``launch_pod`` shape the hierarchical
        schedule keys off); ``RABIT_TRACKER_GROUPS`` ("0,0,1,1" by
        rank) overrides for tests and explicit pinning.  Ids are dense
        in first-seen rank order, so the handout is deterministic for a
        given rank map — a recover round reproduces it exactly."""
        raw = os.environ.get("RABIT_TRACKER_GROUPS", "").strip()
        if raw:
            try:
                ids = [int(x) for x in raw.replace(";", ",").split(",")
                       if x.strip() != ""]
            except ValueError:
                ids = []
            # Ids travel as wire u32s: range-check here so a bad
            # override is ignored with a log line instead of a
            # struct.error mid-handout (which would strand the ranks
            # not yet replied to).
            if len(ids) == world and all(0 <= g < (1 << 32)
                                         for g in ids):
                return ids
            log("tracker: RABIT_TRACKER_GROUPS %r invalid for world %d "
                "(need %d comma-separated u32 ids); ignoring",
                raw, world, world)
        seen: dict[str, int] = {}
        return [seen.setdefault(by_rank[rank].host, len(seen))
                for rank in range(world)]

    def _finish_round(self) -> None:
        """All workers registered: compute topology, reply to everyone.

        A worker dying between registering and its reply must not wedge the
        tracker: its send failure drops only that registrant (it will
        re-register on restart) while every other socket is still replied
        to and closed.  Survivors that already got a topology naming the
        dead worker will fail link setup and come back with cmd=recover.

        When a rescale target is pending the round IS the rescale: it
        completed at the target world, so membership, ranks and the
        epoch move here — liveness events for the deaths/joins that
        caused it were already emitted by the heartbeat sweep and the
        admission path, so the timeline orders cause before effect.
        """
        with self._round_lock:
            # One consistent read of the pending target decides BOTH
            # the round size and whether this round is a rescale: a
            # concurrent _recompute_target (e.g. a presumed-dead member
            # re-registering) must not make them disagree and ship a
            # topology whose world and rank space come from different
            # targets.  A target that changes after this read simply
            # opens the next round (_recompute_target re-derives it
            # from the completed round's membership below).
            with self._scale_lock:
                target = self._target_world
            rescale = target is not None
            world = target if rescale else self.n_workers
            with self._pending_lock:
                if not 0 < world <= len(self._pending):
                    return  # raced: another thread already served it
                regs, extras = self._select_round_locked(world)
                self._pending = extras
                self._round_started = (time.monotonic() if extras
                                       else None)
            if rescale:
                old_world, old_epoch = self.n_workers, self._epoch
                self._assign_ranks_rescale(regs, world)
                self.n_workers = world
                self._epoch += 1
                members = {r.task_id for r in regs}
                with self._scale_lock:
                    self._target_world = None
                    self._sched_switch_pending = False
                    self._dead_tasks &= members
                    self._lost_tasks &= members
                    self._joiners -= members
                log("tracker:%s rescale complete — world %d -> %d, epoch "
                    "%d -> %d (%d member(s))", self._tag(), old_world,
                    world, old_epoch, self._epoch, len(members))
                self._events.append({
                    "ts": time.time(), "name": "epoch", "phase": "rescale",
                    "epoch": self._epoch, "from_world": old_world,
                    "to_world": world})
            else:
                self._assign_ranks(regs)
                members = {r.task_id for r in regs}
            by_rank = {self._rank_of[r.task_id]: r for r in regs}
            addr = {rk: (reg.host, reg.port) for rk, reg in by_rank.items()}
            groups = self._topo_groups(by_rank, world)
            self._last_groups = groups  # the controller's topology view
            # Adaptive handout: demotions only make sense inside the
            # current rank space; the directive string rides verbatim.
            demoted = sorted(r for r in self._demoted if r < world)
            directive = sched_tuner.encode_directive(self._active_sched)
            for rank, reg in sorted(by_rank.items()):
                parent, neighbors = tree_neighbors(rank, world)
                rp, rn = ring_neighbors(rank, world)
                # Beyond the tree/ring links, wire every peer the
                # topology-aware schedules can ask for (halving/doubling
                # XOR partners, Swing hops, hierarchical leader links) —
                # O(log world) extras per rank, computed from the SAME
                # functions the engine-side applies() checks consult
                # (rabit_tpu/sched/topo.py), so a schedule never meets a
                # missing link at dispatch time.
                extra = sched_topo.extra_link_peers(rank, world, groups,
                                                    demoted)
                linkset = sorted(set(neighbors + list(extra)
                                     + ([rp, rn] if world > 1 else [])))
                linkset = [r for r in linkset if r != rank]
                # Deterministic direction: connect to lower ranks,
                # accept higher.
                connect = [(r, addr[r][0], addr[r][1])
                           for r in linkset if r < rank]
                naccept = sum(1 for r in linkset if r > rank)
                relaunched = int(reg.cmd == P.CMD_START
                                 and reg.task_id in self._started_tasks)
                reply = P.TopologyReply(
                    rank=rank, world=world, parent=parent,
                    neighbors=neighbors, ring_prev=rp, ring_next=rn,
                    connect=connect, naccept=naccept,
                    relaunched=relaunched, epoch=self._epoch,
                    groups=groups, sched=directive, demoted=demoted)
                try:
                    reply.send(reg.sock)
                    # Mark "completed a round" only on a delivered
                    # reply: a worker that died before receiving its
                    # first topology never ran with it, so its restart
                    # is a fresh start, not a mid-job relaunch.
                    self._started_tasks.add(reg.task_id)
                except OSError as e:
                    log("tracker:%s worker rank %d died before its "
                        "reply: %s", self._tag(), rank, e)
                try:
                    reg.sock.close()
                except OSError:
                    pass
            self._members = members
            self._journal()
        # Registrants still parked after ANY completed round open the
        # next epoch's target: joiners beyond max_workers, joiners that
        # arrived before the FIRST round completed (membership was
        # empty, so the admission branch could not see them), and
        # members a concurrent target change dropped from this round.
        self._admit_parked()

    def _admit_parked(self) -> None:
        """Sweep the still-parked registrants into the joiner set and
        re-derive the pending rescale target.  Runs after every
        completed round — without it, a cmd=start that raced the round
        it missed would sit parked until its registration socket times
        out instead of being admitted at the next commit boundary."""
        if not self._elastic:
            return
        if self._max_workers is not None:
            # cmd=start: ordinary late joiners.  cmd=rescale from a
            # NON-member: a worker a concurrent target change dropped
            # from the round it re-registered for — it rejoins at the
            # next epoch rather than stalling out its parked socket.
            with self._pending_lock:
                parked = [r.task_id for r in self._pending
                          if r.cmd in (P.CMD_START, P.CMD_RESCALE)
                          and r.task_id not in self._members]
            fresh = [t for t in parked if t not in self._joiners]
            for tid in fresh:
                self._joiners.add(tid)
                self._emit_liveness("join_request", tid)
        self._recompute_target()


class Tracker:
    """Accepts worker connections and serves rendezvous rounds — for
    one job (the embedded launcher shape) or many concurrent jobs (the
    standalone multi-tenant service)."""

    def __init__(self, n_workers: int, host: str = "127.0.0.1", port: int = 0,
                 watchdog_sec: float | None = None,
                 on_stall: Optional[Callable[[set, set], None]] = None,
                 registrant_timeout_sec: float | None = None,
                 obs_dir: str | None = None,
                 heartbeat_miss: float | None = None,
                 on_dead: Optional[Callable[[str], None]] = None,
                 min_workers: int | None = None,
                 max_workers: int | None = None,
                 state_dir: str | None = None,
                 max_jobs: int | None = None,
                 max_total_workers: int | None = None,
                 job_gc_sec: float | None = None,
                 obs_port: int | None = None,
                 straggler_factor: float | None = None,
                 adapt: bool = False,
                 tune_dir: str | None = None,
                 trace_dir: str | None = None):
        """``n_workers`` is the DEFAULT job's world size (and the world
        assumed for a named job whose first registrant sent no world
        hint).

        ``watchdog_sec``: if a rendezvous round stays *partially*
        registered this long, the tracker calls ``on_stall(present_task_
        ids, finished_task_ids)`` so the launcher can kill/restart the
        silent workers — a hung (SIGSTOP'd, wedged) rank is then replaced
        in seconds instead of holding the barrier for the full link
        timeout (reference analogue: the tracker-side liveness the
        reference delegates to its job manager).

        ``heartbeat_miss`` / ``on_dead``: the proactive heartbeat
        failure detector.  Workers launched with ``rabit_heartbeat_sec``
        keep one persistent CMD_HEARTBEAT connection each; a worker
        whose beats stop for ``heartbeat_miss`` periods (default 3, env
        ``RABIT_HEARTBEAT_MISS``) is declared dead: its parked
        rendezvous registrant (if any) is evicted so the round
        re-opens, the liveness transition lands in the obs timeline,
        and ``on_dead(task_id)`` tells the supervisor to kill/relaunch
        it — all without any collective op having to touch the corpse
        first.

        ``min_workers`` / ``max_workers``: enable **elastic
        membership** (per job).  With ``max_workers`` set, late
        ``cmd=start`` registrants beyond a job's current membership are
        admitted as joiners (pending rescale epoch at the next commit
        boundary); with ``min_workers`` set, a worker whose death the
        heartbeat channel reveals triggers a scale-*down* rescale
        instead of waiting for a same-rank relaunch — never below the
        floor.  Leaving both ``None`` freezes each job's world at its
        registration size exactly as before.

        ``state_dir``: journal the control-plane state through the
        atomic CheckpointStore tier so a restarted tracker (same port)
        resumes every in-flight job.  The default job journals at the
        ``state_dir`` root (the pre-multi-tenant layout); named jobs
        journal under ``state_dir/<job>/``, and a restart replays ALL
        of them.

        ``max_jobs`` / ``max_total_workers``: **admission control** for
        the multi-tenant service.  A registration that would create a
        job past either bound gets a typed reject reply (protocol
        ``REJECT_MAX_JOBS`` / ``REJECT_MAX_WORKERS``) instead of
        parking forever; capacity is released the moment a job
        finishes (or is orphan-GC'd), so a rejected submission's
        backoff retry is admitted as soon as a finishing job drains —
        not held off for its whole retry budget.  ``None`` = unbounded.

        ``job_gc_sec`` (env ``RABIT_JOB_GC_SEC``, default 30): how long
        a job must sit idle — no parked registrants, no live heartbeat
        channels, every member holding a death verdict or goodbye —
        before the orphan sweep garbage-collects it.

        ``obs_port``: serve the **live telemetry plane** over HTTP on
        this port (0 = ephemeral; the bound port lands in
        ``self.obs_port``): ``GET /metrics`` is the Prometheus text
        exposition (labels ``job``/``rank``/``sched``), ``GET /status``
        the per-job JSON state — members, epoch, committed version,
        liveness, straggler scores (doc/observability.md "Live
        telemetry"; ``tools/rabit_top.py`` polls it).  None disables.

        ``straggler_factor`` (env ``RABIT_STRAGGLER_FACTOR``, default
        3): a rank whose rolling mean lateness across merged collective
        spans exceeds this many op-times (and the
        ``RABIT_STRAGGLER_MIN_SEC`` absolute floor, default 0.05 s)
        gets a ``straggler`` event on the job timeline.

        ``adapt``: arm the **adaptive controller** (doc/performance.md
        "Online adaptation"): per job, the merged-span fold is
        re-scored online and schedule switches / straggler demotions
        are pushed as schedule-switch epochs at the workers' commit
        boundaries (workers must run ``rabit_adapt=1`` to poll for
        them).  ``tune_dir``: load-or-create a :class:`TuningCache`
        there and atomically re-persist what the controller learns, so
        the next ``rabit_sched=auto`` job starts warm."""
        self._default_world = n_workers
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind((host, port))
        self._listener.listen(256)
        self.host, self.port = self._listener.getsockname()
        self._obs_base = obs_dir if obs_dir is not None \
            else os.environ.get("RABIT_OBS_DIR") or None
        self._thread: threading.Thread | None = None
        self._stopped = False
        self._watchdog_sec = watchdog_sec
        self._on_stall = on_stall
        # socket timeout applied to registered rendezvous sockets: it
        # bounds the tracker's blocking SENDS when a round completes (a
        # wedged worker cannot hold _finish_round's reply loop), not the
        # barrier wait itself — a partially-filled round is bounded by
        # the stall watchdog (watchdog_sec), and the workers' own link
        # timeouts bound their side.  Defaults to the job's configured
        # RABIT_TIMEOUT_SEC instead of a hardcoded 600 s.
        if registrant_timeout_sec is None:
            try:
                registrant_timeout_sec = float(
                    os.environ.get("RABIT_TIMEOUT_SEC", 600))
            except ValueError:
                registrant_timeout_sec = 600.0
        self._registrant_timeout = max(float(registrant_timeout_sec), 1.0)
        # tracker-hosted JAX coordination services (cmd=jaxsvc).  Old
        # epochs' services are RETAINED until the tracker closes: a
        # degraded member whose disconnect RPC failed can still have an
        # error-polling thread attached to an old service, and killing
        # that service fatally terminates the member (client.h:80's
        # default callback).  One retained service per re-formation,
        # bounded by the job's failure count.  The service objects are
        # tracker-owned; the keyed create-or-get maps are per job.
        self._jaxsvcs: list = []
        self._jaxsvc_lock = threading.Lock()
        # Heartbeat failure detector config (protocol CMD_HEARTBEAT).
        if heartbeat_miss is None:
            try:
                heartbeat_miss = float(
                    os.environ.get("RABIT_HEARTBEAT_MISS", 3))
            except ValueError:
                heartbeat_miss = 3.0
        self._hb_miss = max(float(heartbeat_miss), 1.0)
        self._on_dead = on_dead
        # -- elastic membership config (applies to every job) ----------
        self._min_workers = min_workers
        self._max_workers = max_workers
        self._elastic = min_workers is not None or max_workers is not None
        # -- multi-tenant service state --------------------------------
        self._max_jobs = max_jobs
        self._max_total_workers = max_total_workers
        if job_gc_sec is None:
            try:
                job_gc_sec = float(os.environ.get("RABIT_JOB_GC_SEC", 30))
            except ValueError:
                job_gc_sec = 30.0
        self._job_gc_sec = max(float(job_gc_sec), 0.5)
        self._svc_lock = threading.Lock()
        self._svc_counters: collections.Counter = collections.Counter()
        self._jobs_touched = 0     # jobs that ever admitted a worker
        # Admission linger: a submission rejected at capacity is
        # re-polling with backoff right now — the service must not shut
        # down between the finishing job that freed the slot and the
        # rejected worker's next retry, or "admitted once the finishing
        # job completes" silently becomes "connection refused".
        self._last_reject: float | None = None
        # The jobs dict may already exist: the legacy-alias path
        # (attribute access on a bare object) lazily creates it.
        self.__dict__.setdefault("_jobs", {})
        self.__dict__.setdefault("_jobs_lock", threading.Lock())
        # -- durable control-plane journal (state_dir) -----------------
        self._state_base = str(state_dir) if state_dir else None
        default = self._default_job()
        default.n_workers = n_workers
        default._obs_dir = self._obs_base
        if self._state_base:
            default.attach_store(ckpt_mod.CheckpointStore(
                self._state_base, rank=0, keep=3))
            if default.restore_journal():
                self._mark_restored(default)
            self._restore_named_jobs()
        # -- live telemetry exposition (obs_port) ----------------------
        if straggler_factor is None:
            try:
                straggler_factor = float(
                    os.environ.get("RABIT_STRAGGLER_FACTOR", 3.0))
            except ValueError:
                straggler_factor = 3.0
        self._straggler_factor = max(float(straggler_factor), 1.0)
        try:
            self._straggler_min_sec = float(
                os.environ.get("RABIT_STRAGGLER_MIN_SEC", 0.05))
        except ValueError:
            self._straggler_min_sec = 0.05
        # Serving SLO target for the burn-rate exposition rows
        # (doc/observability.md "Serving SLO").
        try:
            self._serve_slo_target = float(
                os.environ.get("RABIT_SERVE_SLO_TARGET", 0.99))
        except ValueError:
            self._serve_slo_target = 0.99
        # Postmortem directory (--trace-dir): the tracker dumps each
        # job's control-plane journal (liveness/recovery timeline +
        # assembled trace summary) there at teardown, next to the
        # workers' flight records (workers persist theirs via
        # RABIT_TRACE_DIR — launch_local --trace-dir sets both).
        self._trace_dir = str(trace_dir) if trace_dir else None
        self._obs_server = None
        self.obs_port: int | None = None
        if obs_port is not None:
            self._start_obs_server(obs_port)
        # -- adaptive controller (obs/adapt.py) ------------------------
        self._adapt = bool(adapt)
        self._tune_dir = str(tune_dir) if tune_dir else None
        self._tune_lock = threading.Lock()
        self._tuning_cache: sched_tuner.TuningCache | None = None
        if self._tune_dir:
            self._tuning_cache = (
                sched_tuner.TuningCache.load(self._tune_dir)
                or sched_tuner.TuningCache({}, {"host": self.host,
                                               "source": "online"}))
        if self._adapt:
            if not self._tune_dir:
                log("tracker: --adapt without --tune-dir: decisions "
                    "apply live but are not persisted for future jobs")
            threading.Thread(target=self._adapt_loop,
                             daemon=True).start()
        if watchdog_sec is not None and on_stall is not None:
            threading.Thread(target=self._watchdog, daemon=True).start()
        # Registrant-loss sweep: a worker that dies while PARKED in the
        # rendezvous barrier must not keep holding a slot (see
        # JobState.sweep_registrants_once); the same cadence runs job
        # completion/orphan GC.
        threading.Thread(target=self._sweep_registrants,
                         daemon=True).start()
        threading.Thread(target=self._hb_monitor, daemon=True).start()

    # -- job registry --------------------------------------------------
    def _default_job(self) -> JobState:
        """The default tenant's JobState, created lazily so the legacy
        single-job attribute surface (``tracker._pending`` & co, used
        by tests and tools) keeps working — including on bare
        ``Tracker.__new__`` objects that unit tests assemble by hand."""
        jobs = self.__dict__.get("_jobs")
        if jobs is None:
            jobs = {}
            self.__dict__["_jobs"] = jobs
            self.__dict__.setdefault("_jobs_lock", threading.Lock())
        job = jobs.get(DEFAULT_JOB)
        if job is None:
            job = JobState(self, DEFAULT_JOB,
                           self.__dict__.get("_default_world", 0))
            jobs[DEFAULT_JOB] = job
        return job

    def _job_list(self) -> list[JobState]:
        with self._jobs_lock:
            return list(self._jobs.values())

    def _active_jobs(self) -> list[JobState]:
        with self._jobs_lock:
            return [j for j in self._jobs.values()
                    if j.touched and not j.done]

    def _live_jobs(self) -> list[JobState]:
        """Jobs the background sweeps must watch: everything not done.
        Deliberately wider than :meth:`_active_jobs` — a heartbeat
        channel (or a parked registrant) can exist before the job's
        first registration is admitted."""
        with self._jobs_lock:
            return [j for j in self._jobs.values() if not j.done]

    def _job_get(self, name: str) -> JobState | None:
        """The current live incarnation of a job, or None (unknown or
        already finished)."""
        with self._jobs_lock:
            job = self._jobs.get(name)
        return None if job is None or job.done else job

    def _mark_restored(self, job: JobState) -> None:
        """A journal replayed at startup: the job is mid-flight (it
        only has a journal because workers registered) and holds
        capacity again."""
        if not job.touched:
            job.touched = True
            self._jobs_touched += 1
        self._count("job.restored")

    def _restore_named_jobs(self) -> None:
        """Replay every named job's journal under ``state_dir/<job>/``
        (the default job's lives at the root).  Finished jobs are left
        on disk but not resurrected."""
        try:
            names = sorted(os.listdir(self._state_base))
        except OSError:
            return
        for name in names:
            sub = os.path.join(self._state_base, name)
            if (name == DEFAULT_JOB or not P.valid_job_id(name)
                    or not os.path.isdir(sub)):
                continue
            job = JobState(self, name, self._default_world)
            if self._obs_base:
                job._obs_dir = os.path.join(self._obs_base, name)
            try:
                job.attach_store(ckpt_mod.CheckpointStore(
                    sub, rank=0, keep=3))
            except OSError as e:
                log("tracker: cannot open job %r journal under %s: %s",
                    name, sub, e)
                continue
            if job.restore_journal() and not job.done:
                with self._jobs_lock:
                    self._jobs[name] = job
                self._mark_restored(job)

    def _check_capacity_locked(self, name: str, world: int) -> None:
        """Admission bounds for one NEW job of ``world`` ranks (caller
        holds ``_jobs_lock``).  Raises :class:`_AdmissionReject` — and
        by contract no state may have been created for the job yet, so
        a rejected submission leaves nothing behind (no JobState to
        sweep forever, no state_dir/<job>/ on disk)."""
        active = [j for j in self._jobs.values()
                  if j.touched and not j.done]
        if self._max_jobs is not None and len(active) >= self._max_jobs:
            raise _AdmissionReject(
                P.REJECT_MAX_JOBS, "jobs",
                f"job {name!r} refused: {len(active)} active "
                f"job(s) at the --max-jobs={self._max_jobs} "
                "capacity; retry after one finishes")
        if self._max_total_workers is not None:
            total = sum(j.n_workers for j in active)
            if total + world > self._max_total_workers:
                raise _AdmissionReject(
                    P.REJECT_MAX_WORKERS, "workers",
                    f"job {name!r} refused: {total} worker(s) "
                    f"active + {world} requested exceeds "
                    f"--max-total-workers={self._max_total_workers}"
                    "; retry after one finishes")

    def _admitted_locked(self, job: JobState) -> None:
        """Capacity charged: lifecycle bookkeeping for a job that just
        admitted its first worker (caller holds ``_jobs_lock``)."""
        job.touched = True
        self._jobs_touched += 1
        self._count("job.created")
        job._events.append({
            "ts": time.time(), "name": "job", "phase": "created",
            "job": job.name, "world": job.n_workers})
        log("tracker: job %r admitted (world %d; %d job(s) active)",
            job.name, job.n_workers,
            sum(1 for j in self._jobs.values()
                if j.touched and not j.done))

    def _admit(self, name: str, world_hint: int) -> JobState:
        """Resolve a registration's job, creating (and admission-
        checking) a fresh incarnation when none is live.  Capacity is
        charged when a job first admits a worker and released the
        moment it finishes, so a rejected submission's backoff retry
        lands as soon as a finishing job drains.  Raises
        :class:`_AdmissionReject` for the typed wire reply — BEFORE any
        job state is created, so rejects cannot accumulate zombie
        JobStates or journal directories."""
        fresh = False
        with self._jobs_lock:
            job = self._jobs.get(name)
            if job is not None and job.done:
                job = None
            if job is not None:
                if not job.touched:
                    # The pre-created default job (legacy alias
                    # surface): charge admission on its first worker.
                    self._check_capacity_locked(name, job.n_workers)
                    self._admitted_locked(job)
                return job
            # A named job's world comes from its first registrant's
            # hint; the default job (and hint-less registrants) use
            # the tracker's configured world.  Admission runs before
            # the JobState exists.
            world = (world_hint if world_hint > 0
                     and name != DEFAULT_JOB else self._default_world)
            self._check_capacity_locked(name, world)
            job = JobState(self, name, world)
            if self._obs_base:
                job._obs_dir = (self._obs_base if name == DEFAULT_JOB
                                else os.path.join(self._obs_base, name))
            self._jobs[name] = job
            self._admitted_locked(job)
            fresh = True
        if fresh and self._state_base:
            # Journal store creation does disk I/O (makedirs, stale-tmp
            # sweep): done OUTSIDE _jobs_lock so one tenant's slow
            # storage cannot stall every co-tenant's command dispatch
            # and heartbeat sweep.  Only _handle's accept thread admits
            # jobs, so nobody races the late attach; journal writes
            # before it simply skip (best-effort by contract).
            sub = (self._state_base if name == DEFAULT_JOB
                   else os.path.join(self._state_base, name))
            try:
                job.attach_store(ckpt_mod.CheckpointStore(
                    sub, rank=0, keep=3))
            except OSError as e:
                log("tracker: job %r journal unavailable (%s); "
                    "running without HA for it", name, e)
        return job

    def _finish_job(self, job: JobState, phase: str) -> None:
        """Complete a job's lifecycle (unanimous goodbye or orphan GC):
        release its capacity, drop its sockets, write its obs report,
        journal the terminal state, and wake the serve loop if it was
        the last one."""
        with self._jobs_lock:
            if job.done:
                return
            job.done = True
        log("tracker:%s job %s (%d member(s), %d shutdown)",
            job._tag() or " [job default]", phase, len(job._members),
            len(job._shutdown_tasks))
        job._events.append({"ts": time.time(), "name": "job",
                            "phase": phase, "job": job.name,
                            "world": job.n_workers})
        self._count("job.finished" if phase == "finished"
                    else "job.orphan_gc")
        job.close()
        job._write_obs_report()
        job._journal()
        if self._service_done():
            self._wake_accept()

    def _count(self, name: str, n: int = 1) -> None:
        """Service-level ``job.*`` counters (admissions, completions,
        GCs, dropped strays) — stamped into every per-job obs report's
        ``service`` section."""
        with self._svc_lock:
            self._svc_counters[name] += n

    def _service_report(self) -> dict:
        with self._jobs_lock:
            active = sorted(j.name for j in self._jobs.values()
                            if j.touched and not j.done)
        with self._svc_lock:
            counters = dict(self._svc_counters)
        return {"jobs_active": active, "counters": counters}

    # How long the service outlives its last job while a rejected
    # submission may still be re-polling admission (see _last_reject).
    # Must cover one worker-side backoff step after the LAST reject:
    # pysocket caps the step at 32 x rabit_backoff_base_ms, so the
    # default covers bases up to ~900 ms; deployments with slower
    # backoff bases raise it via RABIT_ADMISSION_LINGER_SEC.
    ADMISSION_LINGER_SEC = 30.0

    def _service_done(self) -> bool:
        """Serve-loop exit condition: at least one job ever admitted a
        worker, every admitted job has finished, and no capacity-
        rejected submission is plausibly still re-polling.  (A tracker
        that never saw a worker keeps waiting — same as before.)"""
        with self._jobs_lock:
            if self._jobs_touched == 0:
                return False
            if not all(j.done for j in self._jobs.values() if j.touched):
                return False
        try:
            linger = float(os.environ.get("RABIT_ADMISSION_LINGER_SEC",
                                          self.ADMISSION_LINGER_SEC))
        except ValueError:
            linger = self.ADMISSION_LINGER_SEC
        return (self._last_reject is None
                or time.monotonic() - self._last_reject >= linger)

    def _wake_accept(self) -> None:
        """Nudge the accept loop so it re-checks the exit condition —
        job completion can happen on a sweep thread while run() is
        blocked in accept()."""
        host = self.host if self.host not in ("0.0.0.0", "::") \
            else "127.0.0.1"
        try:
            socket.create_connection((host, self.port), timeout=2).close()
        except OSError:
            pass

    # -- public --------------------------------------------------------
    @property
    def uri(self) -> str:
        return self.host

    def worker_env(self, task_id: str,
                   job: str | None = None) -> dict[str, str]:
        """Environment for a worker process launched under this tracker.
        ``job`` names the tenant (default: the default job — byte-
        compatible with pre-multi-tenant workers)."""
        world = self.n_workers
        env = {
            "RABIT_TRACKER_URI": self.host,
            "RABIT_TRACKER_PORT": str(self.port),
            "RABIT_TASK_ID": str(task_id),
        }
        if job and job != DEFAULT_JOB:
            env["RABIT_JOB_ID"] = str(job)
            j = self._job_get(str(job))
            if j is not None:
                world = j.n_workers
        env["RABIT_WORLD_SIZE"] = str(world)
        return env

    def start(self) -> None:
        self._thread = threading.Thread(target=self.run, daemon=True)
        self._thread.start()

    def join(self, timeout: float | None = None) -> None:
        assert self._thread is not None
        self._thread.join(timeout)

    def run(self) -> None:
        """Serve until every admitted job has completed (or stop() is
        called)."""
        while not self._service_done() and not self._stopped:
            try:
                sock, _addr = self._listener.accept()
            except OSError:
                break
            # Bound the handshake so one silent client can't stall the
            # whole control plane; barrier waits happen after _handle.
            sock.settimeout(30)
            try:
                self._handle(sock)
            except (ConnectionError, OSError) as e:
                # A worker dying mid-handshake is survivable: drop it from
                # the pending barrier; it will re-register on restart.
                log("tracker: dropped connection during handshake: %s", e)
                for job in self._job_list():
                    with job._pending_lock:
                        job._pending = [r for r in job._pending
                                        if r.sock is not sock]
                try:
                    sock.close()
                except OSError:
                    pass
        self._close_all()

    def stop(self) -> None:
        """Abort the tracker (e.g. the launcher saw a permanent worker
        failure).  Pending workers get connection resets and fail fast
        instead of sitting in the rendezvous barrier."""
        self._stopped = True
        try:
            # Unblock accept() by closing the listener.
            self._listener.close()
        except OSError:
            pass

    # -- legacy single-job surface (the default tenant) ----------------
    @property
    def epoch(self) -> int:
        """Default job's membership epoch (bumped per completed rescale
        round)."""
        return self._default_job()._epoch

    @property
    def committed_version(self) -> int:
        """Max checkpoint version any default-job worker reported via
        cmd=epoch."""
        return self._default_job()._committed_version

    def _job_done(self) -> bool:
        return self._default_job().job_done()

    def note_dead(self, task_id: str, job: str | None = None) -> None:
        """Supervisor-facing death notice (see JobState.note_dead).
        ``job`` names the tenant (None = the default job)."""
        j = self._job_get(job or DEFAULT_JOB)
        if j is not None:
            j.note_dead(task_id)

    def _obs_ingest(self, raw: str) -> None:
        self._default_job()._obs_ingest(raw)

    def _write_obs_report(self) -> None:
        self._default_job()._write_obs_report()

    def _assign_ranks(self, regs: list[_Registrant] | None = None) -> None:
        self._default_job()._assign_ranks(regs)

    def _assign_ranks_rescale(self, regs: list[_Registrant],
                              world: int) -> None:
        self._default_job()._assign_ranks_rescale(regs, world)

    # -- service internals ---------------------------------------------
    def _fresh_jax_service_locked(self, world: int) -> int:
        """Host a fresh JAX coordination service for one job's world;
        returns its port (0 if jaxlib isn't importable or no port could
        be bound).  Caller holds ``_jaxsvc_lock``.

        The jaxlib service object has no port accessor, so binding it to
        port 0 is useless — a free port is probed first.  The probe binds
        the SAME wildcard namespace the service will use (IPv6 any,
        falling back to IPv4 any on IPv6-less hosts), and the residual
        probe-close -> service-bind race is handled by retrying with a
        fresh port.  Importing jaxlib here initialises no backend: the
        tracker (and the launcher that hosts it) never touches a chip,
        which its children need whole."""
        try:
            from jax._src.lib import _jax as jaxlib_ext
        except ImportError as e:  # a tracker-only host without jax
            log("tracker: cannot host jax coordination service: %s", e)
            return 0
        last: Exception | None = None
        for _ in range(5):
            try:
                probe = socket.socket(socket.AF_INET6,
                                      socket.SOCK_STREAM)
                try:
                    probe.bind(("::", 0))
                except OSError:
                    probe.close()
                    raise
                bind_host = "[::]"
            except OSError:
                probe = socket.socket(socket.AF_INET,
                                      socket.SOCK_STREAM)
                probe.bind(("0.0.0.0", 0))
                bind_host = "0.0.0.0"
            port = probe.getsockname()[1]
            probe.close()
            try:
                # cluster_register_timeout far beyond any client's
                # init_timeout: a member dying inside group formation
                # must surface as each surviving client's LOCAL
                # connect timeout (a catchable exception -> degraded
                # start), never as the service's barrier deadline,
                # which is pushed to registered clients as a FATAL
                # error (client.h:80 terminates them).
                svc = jaxlib_ext.get_distributed_runtime_service(
                    f"{bind_host}:{port}", world,
                    cluster_register_timeout=24 * 3600)
            except Exception as e:  # noqa: BLE001 — port race: retry
                last = e
                continue
            self._jaxsvcs.append(svc)
            log("tracker: hosting jax coordination service #%d on "
                "port %d", len(self._jaxsvcs), port)
            return port
        log("tracker: cannot host jax coordination service "
            "(5 attempts): %s", last)
        return 0

    # -- live telemetry exposition (GET /metrics, GET /status) ---------
    def _start_obs_server(self, port: int) -> None:
        """Serve the live telemetry plane on a tiny stdlib HTTP server
        (its own daemon threads — a slow scraper never touches the
        accept loop or the sweeps).  A bind failure degrades to "no
        exposition" with a log line, never a dead tracker."""
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        tracker = self

        class _Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 — stdlib naming
                try:
                    if self.path.split("?")[0] in ("/metrics",):
                        body = tracker._render_metrics()
                        ctype = "text/plain; version=0.0.4"
                    elif self.path.split("?")[0] in ("/status",):
                        body = json.dumps(tracker._render_status(),
                                          sort_keys=True)
                        ctype = "application/json"
                    elif self.path.split("?")[0] in ("/trace",):
                        body = json.dumps(
                            tracker._render_trace(self.path),
                            sort_keys=True)
                        ctype = "application/json"
                    elif self.path.split("?")[0] in ("/", "/healthz"):
                        body, ctype = "ok\n", "text/plain"
                    else:
                        extra = tracker._render_http_extra(
                            self.path.split("?")[0])
                        if extra is None:
                            self.send_error(404)
                            return
                        body, ctype = extra
                except Exception as e:  # noqa: BLE001 — scrape survives
                    log("tracker: obs scrape failed: %s: %s",
                        type(e).__name__, e)
                    self.send_error(500, type(e).__name__)
                    return
                data = body.encode()
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_POST(self):  # noqa: N802 — stdlib naming
                try:
                    n = int(self.headers.get("Content-Length") or 0)
                    body = json.loads(self.rfile.read(n) or b"{}")
                    doc = tracker._handle_http_post(
                        self.path.split("?")[0], body)
                except Exception as e:  # noqa: BLE001 — serve thread
                    log("tracker: obs POST %s failed: %s: %s",
                        self.path, type(e).__name__, e)
                    self.send_error(500, type(e).__name__)
                    return
                if doc is None:
                    self.send_error(404)
                    return
                data = json.dumps(doc, sort_keys=True).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def log_message(self, *_a):  # silence per-request stderr
                pass

        host = self.host if self.host not in ("::",) else "0.0.0.0"
        try:
            srv = ThreadingHTTPServer((host, port), _Handler)
        except OSError as e:
            log("tracker: cannot bind the obs exposition port %d on "
                "%s: %s (scrape endpoint disabled)", port, host, e)
            return
        srv.daemon_threads = True
        self._obs_server = srv
        self.obs_port = srv.server_address[1]
        threading.Thread(target=srv.serve_forever, name="rabit-obs-http",
                         daemon=True).start()
        log("tracker: obs exposition on http://%s:%d (/metrics, /status)",
            host, self.obs_port)

    def _render_http_extra(self, path: str) -> tuple[str, str] | None:
        """Subclass hook for extra obs-server GET paths — ``(body,
        content_type)`` or None for a 404.  ShardServer mirrors the
        directory snapshot here (``GET /directory``)."""
        return None

    def _handle_http_post(self, path: str, body: dict) -> dict | None:
        """Subclass hook for obs-server POST paths — a JSON-able reply
        dict or None for a 404.  ShardServer serves the shard-to-shard
        migration offer (``POST /migrate``) and the forwarded goodbye
        (``POST /goodbye``) here."""
        return None

    def _render_trace(self, path: str) -> dict:
        """``GET /trace``: per-job assembled-timeline summaries;
        ``GET /trace?job=NAME[&op=E,V,S,KIND]`` exports one job's
        newest (or named) op as a Perfetto-loadable Chrome-trace JSON
        object — the doc ``tools/trace_report.py`` analyzes."""
        from urllib.parse import parse_qs, urlsplit
        q = parse_qs(urlsplit(path).query)
        want = (q.get("job") or [None])[0]
        if want is None:
            jobs = {}
            for job in self._job_list():
                if job.touched:
                    try:
                        jobs[job.name] = job._traces.report()
                    except Exception as e:  # noqa: BLE001 — scrape survives
                        jobs[job.name] = {"error": type(e).__name__, "detail": str(e)}
            return {"jobs": jobs}
        job = self._job_get(want)
        if job is None:
            return {"error": "no such job", "job": want}
        key = None
        raw = (q.get("op") or [None])[0]
        if raw:
            try:
                e, v, s, kind = raw.split(",", 3)
                key = (int(e), int(v), int(s), kind)
            except ValueError:
                return {"error": "bad op key (want E,V,S,KIND)", "op": raw}
        doc = job._traces.chrome(key)
        doc["job"] = want
        return doc

    def _render_metrics(self) -> str:
        """The Prometheus text exposition: service counters plus every
        job's live per-rank fold, heartbeat freshness, straggler scores
        and per-schedule span latency (labels job/rank/sched).  Each
        job renders inside its own guard so one tenant's racing
        mutation can only drop its OWN series from one scrape."""
        samples: list[tuple[str, dict, float]] = []
        types: dict[str, str] = {"rabit_jobs_active": "gauge",
                                 "rabit_job_world": "gauge",
                                 "rabit_job_epoch": "gauge",
                                 "rabit_job_committed_version": "gauge",
                                 "rabit_job_members": "gauge",
                                 "rabit_hb_last_seen_seconds": "gauge",
                                 "rabit_straggler_score": "gauge",
                                 "rabit_sched_op_count": "counter",
                                 "rabit_sched_op_seconds_sum": "counter",
                                 "rabit_sched_skew_seconds_max": "gauge",
                                 "rabit_sched_active": "gauge",
                                 "rabit_rank_demoted": "gauge",
                                 "rabit_controller_decisions_total":
                                     "counter",
                                 "rabit_serve_requests_total": "counter",
                                 "rabit_serve_qos_requests_total":
                                     "counter",
                                 "rabit_serve_slo_burn_rate": "gauge",
                                 "rabit_serve_slo_budget_remaining":
                                     "gauge",
                                 "rabit_trace_ops_assembled_total":
                                     "counter",
                                 "rabit_trace_records_total": "counter",
                                 "rabit_trace_link_seconds_mean": "gauge",
                                 "rabit_trace_link_hops_total": "counter"}
        svc = self._service_report()
        samples.append(("rabit_jobs_active", {},
                        len(svc["jobs_active"])))
        for name, v in sorted(svc["counters"].items()):
            pname = obs.prom_name(name)
            types[pname] = "counter"
            samples.append((pname, {}, v))
        now = time.monotonic()
        for job in self._job_list():
            if not job.touched:
                continue
            try:
                base = {"job": job.name}
                samples += [
                    ("rabit_job_world", base, job.n_workers),
                    ("rabit_job_epoch", base, job._epoch),
                    ("rabit_job_committed_version", base,
                     job._committed_version),
                    ("rabit_job_members", base, len(job._members)),
                ]
                with job._hb_lock:
                    peers = dict(job._hb_peers)
                for task, p in sorted(peers.items()):
                    rank = job._rank_of.get(task)
                    lbl = {**base, "rank": str(rank)
                           if rank is not None else task}
                    samples.append(("rabit_hb_last_seen_seconds", lbl,
                                    max(now - p.last, 0.0)))
                for rank, row in job._live.rows():
                    lbl = {**base, "rank": str(rank)}
                    for name, v in sorted(row["counters"].items()):
                        # Serving-plane SLO counters render as ONE
                        # labeled series (doc/serving.md "SLOs"):
                        # serve.requests.<status> →
                        # rabit_serve_requests_total{status=...}, the
                        # shape dashboards sum/rate over.
                        if name.startswith("serve.requests."):
                            status = name[len("serve.requests."):]
                            if status and "." not in status:
                                samples.append(
                                    ("rabit_serve_requests_total",
                                     {**lbl, "status": status}, v))
                                continue
                        # Per-class serving books render the same way:
                        # serve.qos.<class>.<status> → one labeled
                        # rabit_serve_qos_requests_total{qos,status}
                        # series dashboards can sum by either label.
                        if name.startswith("serve.qos."):
                            cls, _, status = \
                                name[len("serve.qos."):].partition(".")
                            if cls and status and "." not in status:
                                samples.append(
                                    ("rabit_serve_qos_requests_total",
                                     {**lbl, "qos": cls,
                                      "status": status}, v))
                                continue
                        pname = obs.prom_name(name)
                        types.setdefault(pname, "counter")
                        samples.append((pname, lbl, v))
                    for name, v in sorted(row["gauges"].items()):
                        pname = obs.prom_name(name)
                        types.setdefault(pname, "gauge")
                        samples.append((pname, lbl, v))
                # ONE report() per job per scrape: every sub-section
                # below reads the same snapshot (the merger lock sits
                # on the frame-ingest hot path).
                span_rep = job._spans.report()
                # Straggler scores max-merge the training-plane span
                # fold with the serving-plane batch-service fold
                # (serve.svc_ewma_ms over the fleet median): a rank
                # slow on EITHER plane scores high, and serve-only
                # jobs (no spans at all) still get a series the
                # loadgen router can route away from.
                serve_scores = {str(r): s for r, s in
                                obs.serve_straggler_scores(
                                    job._live.rows()).items()}
                for rank, row in span_rep["ranks"].items():
                    samples.append(("rabit_straggler_score",
                                    {**base, "rank": rank},
                                    max(row["score"],
                                        serve_scores.pop(str(rank),
                                                         0.0))))
                for rank, score in sorted(serve_scores.items()):
                    samples.append(("rabit_straggler_score",
                                    {**base, "rank": rank}, score))
                for sched, st in span_rep["sched"].items():
                    lbl = {**base, "sched": sched}
                    samples += [
                        ("rabit_sched_op_count", lbl, st["count"]),
                        ("rabit_sched_op_seconds_sum", lbl,
                         st["count"] * st["mean_sec"]),
                        ("rabit_sched_skew_seconds_max", lbl,
                         st["max_skew_sec"]),
                    ]
                # Adaptive controller: the currently-active directive
                # (one series per payload bucket), demotions and the
                # decision counters.
                for bucket, sname in sorted(job._active_sched.items()):
                    samples.append(("rabit_sched_active",
                                    {**base, "sched": sname,
                                     "bucket": str(bucket)}, 1))
                for rank in sorted(job._demoted):
                    samples.append(("rabit_rank_demoted",
                                    {**base, "rank": str(rank)}, 1))
                if job._controller is not None:
                    for kind, n in sorted(
                            job._controller.counters.items()):
                        samples.append(
                            ("rabit_controller_decisions_total",
                             {**base, "kind": kind}, n))
                # Serving SLO burn rows (doc/observability.md "Serving
                # SLO"): derived from the per-rank shed/timeout/error
                # counters the live fold already holds.  Per-job labels
                # keep the shard-level page merge exact (jobs are
                # disjoint across shards).
                slo = obs.serve_slo(job._live.rows(),
                                    self._serve_slo_target)
                if slo is not None:
                    samples += [
                        ("rabit_serve_slo_burn_rate", base,
                         slo["burn_rate"]),
                        ("rabit_serve_slo_budget_remaining", base,
                         slo["budget_remaining"]),
                    ]
                # Causal trace plane: assembly totals plus the folded
                # per-link cost table (mean hop seconds + hop counts per
                # directed link) — the same evidence /trace exports.
                if job._traces.records:
                    samples += [
                        ("rabit_trace_ops_assembled_total", base,
                         job._traces.assembled),
                        ("rabit_trace_records_total", base,
                         job._traces.records),
                    ]
                    for link, row in job._traces.link_costs().items():
                        lbl = {**base, "link": link}
                        samples += [
                            ("rabit_trace_link_seconds_mean", lbl,
                             row["mean_sec"]),
                            ("rabit_trace_link_hops_total", lbl,
                             row["n"]),
                        ]
            except Exception as e:  # noqa: BLE001 — one tenant's racing
                log("tracker:%s metrics render skipped this scrape: %s",
                    job._tag(), e)  # mutation must not 500 the scrape
        return obs.prometheus_text(samples, types)

    def _render_status(self) -> dict:
        """The ``GET /status`` JSON: the facts soak.py derives from the
        outside (members, epoch, committed version, liveness verdicts,
        admission counters), queryable live per job."""
        out = {"ts": time.time(), "service": self._service_report(),
               "elastic": self._elastic, "jobs": {}}
        now = time.monotonic()
        for job in self._job_list():
            if not job.touched:
                continue
            try:
                with job._hb_lock:
                    peers = dict(job._hb_peers)
                liveness = {}
                for task, p in sorted(peers.items()):
                    liveness[task] = {
                        "rank": job._rank_of.get(task),
                        "last_seen_sec": round(max(now - p.last, 0.0), 3),
                        "dead": p.dead,
                    }
                span_rep = job._spans.report()
                scores = {r: round(row["score"], 3)
                          for r, row in span_rep["ranks"].items()}
                for r, s in obs.serve_straggler_scores(
                        job._live.rows()).items():
                    r = str(r)
                    scores[r] = round(max(scores.get(r, 0.0), s), 3)
                flagged = {str(r) for r in job._straggling}
                out["jobs"][job.name] = {
                    "world": job.n_workers,
                    "epoch": job._epoch,
                    "committed_version": job._committed_version,
                    "done": job.done,
                    "members": sorted(job._members),
                    "shutdown": sorted(job._shutdown_tasks),
                    "lost": sorted(job._lost_tasks),
                    "liveness": liveness,
                    "live": job._live.report(),
                    "stragglers": {r: s for r, s in scores.items()
                                   if r in flagged},
                    "straggler_scores": scores,
                    "merged_ops": span_rep["merged_ops"],
                    "sched_latency": span_rep["sched"],
                }
                # Causal trace plane: bound-by verdict, per-link cost
                # table and the newest assembled timeline — what
                # rabit_top's bound-by column and --trace read, and
                # what merge_status_docs folds shard-level (the section
                # rides the per-job row; jobs are disjoint).
                if job._traces.records:
                    out["jobs"][job.name]["trace"] = job._traces.report()
                slo = obs.serve_slo(job._live.rows(),
                                    self._serve_slo_target)
                if slo is not None:
                    out["jobs"][job.name]["serve_slo"] = slo
                # Adaptive controller: active directive, demotions and
                # the recent decision records with their evidence — the
                # facts soak.py's --adapt gate (and rabit_top's "active
                # sched / last decision" display) derive from outside.
                ctl = job._controller
                if ctl is not None or job._active_sched or job._demoted:
                    out["jobs"][job.name]["controller"] = {
                        "active_sched": {
                            str(b): s for b, s
                            in sorted(job._active_sched.items())},
                        "demoted": sorted(job._demoted),
                        "decisions": ([d.as_dict()
                                       for d in list(ctl.decisions)[-8:]]
                                      if ctl is not None else []),
                        "counters": (dict(ctl.counters)
                                     if ctl is not None else {}),
                    }
            except Exception as e:  # noqa: BLE001 — see _render_metrics
                out["jobs"][job.name] = {"error": type(e).__name__}
        return out

    def _dump_trace_journal(self, job: "JobState") -> None:
        """One job's control-plane side of the postmortem record
        (``--trace-dir``): the liveness/recovery timeline plus the
        assembled trace summary, written atomically next to the
        workers' flight records for ``tools/postmortem.py`` to merge.
        Best effort — teardown never dies in its own forensics."""
        if not self._trace_dir:
            return
        doc = {"job": job.name, "ts": round(time.time(), 6),
               "world": job.n_workers, "epoch": job._epoch,
               "committed_version": job._committed_version,
               "members": sorted(job._members),
               "lost": sorted(job._lost_tasks),
               "events": list(job._events)[-512:]}
        try:
            doc["trace"] = job._traces.report()
        except Exception as e:  # noqa: BLE001 — forensics stay best effort
            doc["trace"] = {"error": type(e).__name__, "detail": str(e)}
        name = job.name if job.name != "default" else "default"
        path = os.path.join(self._trace_dir, f"tracker.{name}.json")
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            os.makedirs(self._trace_dir, exist_ok=True)
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, path)
        except OSError as e:
            log("tracker: trace journal dump failed: %s", e)

    def _close_all(self) -> None:
        # Jobs interrupted mid-flight (stop() / permanent failure)
        # still get their telemetry written; finished jobs already
        # wrote theirs at completion.
        if getattr(self, "_trace_dir", None):
            for job in self._job_list():
                if job.touched:
                    self._dump_trace_journal(job)
        srv = getattr(self, "_obs_server", None)
        if srv is not None:
            try:
                srv.shutdown()
                srv.server_close()
            except OSError:
                pass
            self._obs_server = None
        for job in self._job_list():
            if job.touched and not job.done:
                job._write_obs_report()
        try:
            self._listener.close()
        except OSError:
            pass
        with self._jaxsvc_lock:
            svcs, self._jaxsvcs = self._jaxsvcs, []
            for svc in svcs:
                try:
                    svc.shutdown()
                except Exception as e:  # noqa: BLE001 — best-effort stop
                    log("tracker: jax service shutdown failed: %s", e)
        for job in self._job_list():
            job.close()

    def _watchdog(self) -> None:
        """Fires on_stall when a rendezvous round sits partially filled
        longer than watchdog_sec.  Restarting a merely-slow worker is
        wasteful but safe (it reloads from its checkpoint), so the
        launcher may use an aggressive bound in test/dev jobs."""
        while not self._stopped:
            time.sleep(min(0.2, self._watchdog_sec / 5))
            for job in self._live_jobs():
                with job._pending_lock:
                    stalled = (
                        job._round_started is not None
                        and 0 < len(job._pending) < job._round_size()
                        and time.monotonic() - job._round_started
                        > self._watchdog_sec)
                    if not stalled:
                        continue
                    present = {r.task_id for r in job._pending}
                    finished = set(job._shutdown_tasks)
                    # rearm: fire again only after another full period
                    job._round_started = time.monotonic()
                log("tracker:%s rendezvous stalled (%d/%d registered); "
                    "notifying launcher", job._tag(), len(present),
                    job._round_size())
                try:
                    self._on_stall(present, finished)
                except Exception as e:  # noqa: BLE001 — must survive
                    log("tracker: on_stall callback failed: %s", e)

    # How often the adaptive controller re-scores each job's schedule
    # choice from the live span fold (tracker --adapt).
    ADAPT_SWEEP_SEC = 0.5

    def _adapt_loop(self) -> None:
        """The adaptive controller's sweep: one `_adapt_tick` per live
        job per period, each inside its own guard — one tenant's racing
        mutation must never stall a co-tenant's adaptation."""
        while not self._stopped:
            time.sleep(self.ADAPT_SWEEP_SEC)
            for job in self._active_jobs():
                try:
                    job._adapt_tick()
                except Exception as e:  # noqa: BLE001 — sweep survives
                    log("tracker:%s adapt tick failed: %s: %s",
                        job._tag(), type(e).__name__, e)

    def _tune_merge(self, kind: str, world: int, nbytes: int,
                    name: str, codec: str = "none") -> None:
        """Fold one controller verdict into the shared TuningCache and
        atomically re-persist it (tracker --tune-dir), so the NEXT
        ``rabit_sched=auto`` job starts on the learned schedule.
        ``codec`` (from the job's streamed frames) keys the rows — an
        int8-wire winner never answers a full-width job.
        Best-effort: a full disk degrades warm starts, never the
        running job."""
        if self._tuning_cache is None:
            return
        with self._tune_lock:
            self._tuning_cache.merge_online(kind, world, nbytes, name,
                                            codec=codec)
            if self._tune_dir:
                try:
                    self._tuning_cache.save(self._tune_dir)
                except OSError as e:
                    log("tracker: tuning cache persist failed: %s", e)
        self._count("controller.tune_merges")

    # How often parked rendezvous sockets are polled for death (and
    # job completion / orphan GC is re-checked).
    REGISTRANT_SWEEP_SEC = 0.5

    def _sweep_registrants(self) -> None:
        """Per-job dead-registrant sweep + the job lifecycle sweep
        (completion backstop and the idle-orphan GC)."""
        while not self._stopped:
            time.sleep(self.REGISTRANT_SWEEP_SEC)
            now = time.monotonic()
            for job in self._live_jobs():
                # One tenant's corrupt state must never kill the sweep
                # for its co-tenants (fault isolation): failures are
                # logged per job and the pass moves on.
                try:
                    job.sweep_registrants_once()
                    if not job.touched:
                        continue  # lifecycle starts at first admission
                    if job.job_done():
                        self._finish_job(job, "finished")
                        continue
                    why = job.orphaned(now)
                    if why is not None:
                        log("tracker:%s orphan GC: %s", job._tag(), why)
                        self._finish_job(job, "orphan_gc")
                except Exception as e:  # noqa: BLE001 — sweep survives
                    log("tracker:%s registrant/lifecycle sweep failed: "
                        "%s", job._tag(), e)
            # Exit-condition backstop: job completion and linger expiry
            # can both happen while run() is blocked in accept().
            if self._service_done():
                self._wake_accept()

    # -- heartbeat failure detector ------------------------------------
    # How often the heartbeat sweep wakes to drain beats and check
    # deadlines; detection latency adds at most one sweep period on top
    # of the miss budget.
    HB_SWEEP_SEC = 0.1

    def _hb_monitor(self) -> None:
        """Drain beats and run the deadline-based suspicion sweep,
        across every job's heartbeat channels."""
        while not self._stopped:
            pairs: list[tuple[JobState, _HbPeer]] = []
            for job in self._live_jobs():
                with job._hb_lock:
                    pairs.extend((job, p)
                                 for p in job._hb_peers.values())
            if not pairs:
                time.sleep(self.HB_SWEEP_SEC)
                continue
            sel = selectors.DefaultSelector()
            try:
                for job, p in pairs:
                    try:
                        sel.register(p.sock, selectors.EVENT_READ,
                                     (job, p))
                    except (OSError, ValueError):
                        continue  # closed under us; deadline still runs
                try:
                    ready = [key.data
                             for key, _ in sel.select(self.HB_SWEEP_SEC)]
                except OSError:
                    # a registered fd closed mid-select (tracker
                    # teardown race): the detector must outlive it
                    ready = []
            finally:
                sel.close()
            if self._stopped:
                return  # teardown: sockets are closing under us; any
                # drain from here would just log spurious EOFs
            now = time.monotonic()
            for job, p in ready:
                try:
                    job._hb_drain(p, now)
                except Exception as e:  # noqa: BLE001 — see sweep note
                    log("tracker:%s heartbeat drain failed for task %r: "
                        "%s", job._tag(), p.task_id, e)
            for job, p in pairs:
                with job._hb_lock:
                    if job._hb_peers.get(p.task_id) is not p:
                        continue  # replaced (relaunch) or forgotten
                if now - p.last > p.period_s * self._hb_miss:
                    try:
                        job._hb_mark_dead(
                            p, "dead",
                            f"no beat for {now - p.last:.2f}s (budget "
                            f"{self._hb_miss:g} x {p.period_s:g}s)")
                    except Exception as e:  # noqa: BLE001
                        log("tracker:%s heartbeat verdict failed for "
                            "task %r: %s", job._tag(), p.task_id, e)

    # -- command dispatch ----------------------------------------------
    def _handle(self, sock: socket.socket) -> None:
        try:
            job_name, cmd, task_id, world_hint = P.recv_hello(sock)
        except P.HandshakeError as e:
            # Stray client on the tracker port (port scanner, HTTP
            # probe, corrupt worker): log + drop; a client that spoke
            # the magic gets the typed reject so a confused worker
            # fails loudly instead of waiting on a closed socket.
            self._count("job.handshake.dropped")
            log("tracker: dropped stray client on the tracker port (%s)",
                e)
            if e.parsed_magic:
                try:
                    P.RejectReply(P.REJECT_BAD_HANDSHAKE, str(e)).send(sock)
                except OSError:
                    pass
            try:
                sock.close()
            except OSError:
                pass
            return
        try:
            self._dispatch(sock, job_name, cmd, task_id, world_hint)
        except P.HandshakeError as e:
            # Post-magic garbage (oversized host string, corrupt print
            # payload length): same typed-reject treatment as a hello
            # that went wrong after the magic — the client is told
            # loudly instead of timing out its whole retry budget on a
            # silent close, and the stray is counted.
            self._count("job.handshake.dropped")
            log("tracker: dropped malformed %s from task %r (%s)",
                cmd, task_id, e)
            try:
                P.RejectReply(P.REJECT_BAD_HANDSHAKE, str(e)).send(sock)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass

    def _dispatch(self, sock: socket.socket, job_name: str, cmd: str,
                  task_id: str, world_hint: int) -> None:
        if cmd == P.CMD_PRINT:
            # Print payloads (incl. multi-KB obs summaries) get a
            # generous but finite cap — a stray length prefix must not
            # become an unbounded buffering recv.
            msg = P.recv_str(sock, max_len=P.MAX_PRINT_LEN)
            job = self._job_get(job_name)
            if msg.startswith(obs.OBS_SUMMARY_PREFIX):
                if job is not None:
                    job.last_activity = time.monotonic()
                    job._obs_ingest(msg[len(obs.OBS_SUMMARY_PREFIX):])
            else:
                sys.stdout.write(msg if msg.endswith("\n")
                                 else msg + "\n")
                sys.stdout.flush()
            sock.close()
            return
        if cmd == P.CMD_SHUTDOWN:
            job = self._job_get(job_name)
            if job is not None:
                job.last_activity = time.monotonic()
                if task_id in job._rank_of:
                    job._shutdown_tasks.add(task_id)
                if job.job_done():
                    # _finish_job journals the terminal (done=True)
                    # state — no point fsyncing an immediately
                    # superseded snapshot first.
                    self._finish_job(job, "finished")
                elif task_id in job._rank_of:
                    job._journal()
            sock.close()
            return
        if cmd == P.CMD_EPOCH:
            # Membership poll (one-shot): record the worker's committed
            # version (journaled job progress), reply the current and
            # pending epoch so commit boundaries learn about rescales.
            version = P.recv_u32(sock)
            job = self._job_get(job_name)
            if job is None:
                try:  # unknown/finished job: "no change"
                    P.send_u32(sock, 0)
                    P.send_u32(sock, 0)
                    P.send_u32(sock, 0)
                except OSError:
                    pass
                sock.close()
                return
            job.last_activity = time.monotonic()
            bump = version > job._committed_version
            if bump:
                job._committed_version = version
            with job._scale_lock:
                pending = job._target_world is not None
                target_epoch = job._epoch + (1 if pending else 0)
                target_world = (job._target_world if pending
                                else job.n_workers)
            try:
                P.send_u32(sock, job._epoch)
                P.send_u32(sock, target_epoch)
                P.send_u32(sock, target_world)
            except OSError:
                pass  # poller gone; it treats that as "no change"
            sock.close()
            if bump:
                job._journal()
            return
        if cmd == P.CMD_JAXSVC:
            job = self._job_get(job_name)
            P.send_u32(sock, job.keyed_jax_service(task_id)
                       if job is not None else 0)
            sock.close()
            return
        if cmd == P.CMD_FORMBAR:
            job = self._job_get(job_name)
            if job is None:
                JobState._formbar_reply(sock, False)
                return
            job.last_activity = time.monotonic()
            job._formbar_post(sock, task_id)
            return
        if cmd == P.CMD_HEARTBEAT:
            period_ms = P.recv_u32(sock)
            job = self._job_get(job_name)
            if job is None:
                sock.close()
                return
            job.last_activity = time.monotonic()
            job._hb_register(sock, task_id, period_ms)
            return  # the connection stays open for the beat stream
        if cmd in (P.CMD_START, P.CMD_RECOVER, P.CMD_RESCALE):
            host = P.recv_str(sock, max_len=P.MAX_HELLO_STR)
            port = P.recv_u32(sock)
            try:
                job = self._admit(job_name, world_hint)
            except _AdmissionReject as rej:
                self._last_reject = time.monotonic()
                self._count("job.admission.rejected")
                self._count(f"job.admission.rejected.{rej.kind}")
                log("tracker: admission rejected %s of task %r: %s",
                    cmd, task_id, rej.reason)
                try:
                    P.RejectReply(rej.code, rej.reason).send(sock)
                except OSError:
                    pass
                sock.close()
                return
            job.register(sock, cmd, task_id, host, port)
            return
        log("tracker: unknown command %r from task %r", cmd, task_id)
        sock.close()


def _job_alias(attr: str):
    """Legacy single-job attribute surface: ``tracker.<attr>`` reads and
    writes the DEFAULT job's state (tests, tools and the embedded
    launchers predate multi-tenancy and address the tracker as if it
    served exactly one job — for them it still does)."""
    return property(
        lambda self: getattr(self._default_job(), attr),
        lambda self, value: setattr(self._default_job(), attr, value),
        doc=f"default job's ``{attr}`` (legacy single-job surface)")


for _attr in ("n_workers", "_rank_of", "_shutdown_tasks", "_members",
              "_started_tasks", "_pending", "_round_started",
              "_pending_lock", "_formbar_state", "_formbar_socks",
              "_formbar_posted", "_formbar_timer", "_formbar_lock",
              "_hb_peers", "_hb_seen", "_hb_lock", "_events",
              "_target_world", "_dead_tasks", "_joiners", "_lost_tasks",
              "_scale_lock", "_round_lock", "_committed_version",
              "_state_store", "_state_seq", "_journal_lock",
              "_obs_reports", "_obs_lock", "_jaxsvc_keyed",
              "_live", "_spans", "_straggling", "_controller",
              "_active_sched", "_demoted", "_sched_switch_pending",
              "_last_groups"):
    setattr(Tracker, _attr, _job_alias(_attr))
del _attr


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description="rabit_tpu rendezvous tracker")
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--obs-dir", default=None,
                    help="write the aggregated per-job telemetry report "
                         "(obs_report.json; named jobs nest under "
                         "<obs-dir>/<job>/) here; defaults to "
                         "RABIT_OBS_DIR when set")
    ap.add_argument("--min-workers", type=int, default=None,
                    help="elastic floor (per job): heartbeat-detected "
                         "deaths scale the world DOWN (never below "
                         "this) instead of waiting for a same-rank "
                         "relaunch")
    ap.add_argument("--max-workers", type=int, default=None,
                    help="elastic ceiling (per job): late cmd=start "
                         "registrants are admitted as joiners at the "
                         "next checkpoint-commit rescale, up to this "
                         "world")
    ap.add_argument("--state-dir", default=None,
                    help="journal the tracker state (rank map, epoch, "
                         "members, barriers; one journal per job) "
                         "through the atomic checkpoint-store tier; a "
                         "restarted tracker on the same port replays "
                         "every in-flight job and the workers' connect "
                         "retry bridges the outage")
    ap.add_argument("--max-jobs", type=int, default=None,
                    help="admission control: maximum concurrently "
                         "active jobs; an over-capacity submission "
                         "gets a typed reject reply (workers surface "
                         "it as AdmissionError after their retry "
                         "budget) and is re-admitted as soon as a "
                         "finishing job drains")
    ap.add_argument("--max-total-workers", type=int, default=None,
                    help="admission control: cap on the sum of all "
                         "active jobs' world sizes")
    ap.add_argument("--job-gc-sec", type=float, default=None,
                    help="orphan sweep: GC a job whose last member "
                         "vanished (no live heartbeat channels, every "
                         "member holding a death verdict) after this "
                         "long idle (default 30, env RABIT_JOB_GC_SEC)")
    ap.add_argument("--obs-port", type=int, default=None,
                    help="serve the live telemetry plane on this port "
                         "(0 = ephemeral): GET /metrics is the "
                         "Prometheus text exposition (labels "
                         "job/rank/sched), GET /status the per-job "
                         "JSON state; tools/rabit_top.py polls it "
                         "(doc/observability.md 'Live telemetry')")
    ap.add_argument("--straggler-factor", type=float, default=None,
                    help="straggler verdict threshold: a rank whose "
                         "rolling mean lateness across merged "
                         "collective spans exceeds this many op-times "
                         "gets a straggler event (default 3, env "
                         "RABIT_STRAGGLER_FACTOR)")
    ap.add_argument("--adapt", action="store_true",
                    help="arm the adaptive controller: re-score each "
                         "job's schedule choice online from the merged "
                         "collective spans, push schedule-switch "
                         "epochs at commit boundaries (workers need "
                         "rabit_adapt=1) and demote persistent "
                         "stragglers out of hierarchical leader roles "
                         "(doc/performance.md 'Online adaptation')")
    ap.add_argument("--tune-dir", default=None,
                    help="load-or-create the schedule TuningCache here "
                         "and atomically re-persist what the adaptive "
                         "controller learns, so the next "
                         "rabit_sched=auto job starts warm (same "
                         "format as bench.py --tune-dir)")
    ap.add_argument("--trace-dir", default=None,
                    help="postmortem directory: dump each job's "
                         "control-plane journal (liveness/recovery "
                         "timeline + assembled trace summary) here at "
                         "teardown, next to the workers' flight "
                         "records (RABIT_TRACE_DIR), for tools/"
                         "postmortem.py (doc/observability.md 'Causal "
                         "tracing & postmortem')")
    ap.add_argument("--directory", default=None,
                    help="base URL of the job directory service "
                         "(python -m rabit_tpu.tracker.directory): run "
                         "as ONE SHARD of the partitioned control "
                         "plane instead of a lone tracker — host only "
                         "the jobs the consistent-hash ring assigns "
                         "here, redirect the rest with typed "
                         "REJECT_SHARD_MOVED replies, and adopt a dead "
                         "peer's journals from the shared --state-dir "
                         "(doc/fault_tolerance.md 'Sharded tracker')")
    ap.add_argument("--shard-index", type=int, default=None,
                    help="this shard's stable index on the ring "
                         "(required with --directory; survives "
                         "restarts so a supervised shard relaunch "
                         "reclaims its own arc)")
    ap.add_argument("--migrate-after-sec", type=float, default=None,
                    help="live-migration threshold (shards only): a "
                         "RUNNING job whose ring owner has been "
                         "another shard for this long is handed to it "
                         "at a commit boundary (journal shipped, "
                         "workers redirected).  Unset = jobs stay "
                         "sticky until they finish (the default)")
    ap.add_argument("--migrate-max", type=int, default=2,
                    help="max live migrations per poll tick (bounds "
                         "the drain-and-move pass after a cold "
                         "restart or scale-up)")
    args = ap.parse_args(argv)
    common = dict(obs_dir=args.obs_dir, min_workers=args.min_workers,
                  max_workers=args.max_workers, state_dir=args.state_dir,
                  max_jobs=args.max_jobs,
                  max_total_workers=args.max_total_workers,
                  job_gc_sec=args.job_gc_sec, obs_port=args.obs_port,
                  straggler_factor=args.straggler_factor,
                  adapt=args.adapt, tune_dir=args.tune_dir,
                  trace_dir=args.trace_dir)
    if args.directory is not None:
        if args.shard_index is None:
            ap.error("--directory requires --shard-index")
        from rabit_tpu.tracker.shard import ShardServer
        tr: Tracker = ShardServer(args.num_workers, args.host,
                                  args.port,
                                  shard_index=args.shard_index,
                                  directory=args.directory,
                                  migrate_after_sec=args.migrate_after_sec,
                                  migrate_max=args.migrate_max,
                                  **common)
        sys.stdout.write(
            f"shard {args.shard_index} listening on "
            f"{tr.host}:{tr.port}"
            + (f" (obs on :{tr.obs_port})" if tr.obs_port else "")
            + f" [directory {args.directory}]\n")
    else:
        if args.shard_index is not None:
            ap.error("--shard-index requires --directory")
        tr = Tracker(args.num_workers, args.host, args.port, **common)
        sys.stdout.write(
            f"tracker listening on {tr.host}:{tr.port}"
            + (f" (obs on :{tr.obs_port})" if tr.obs_port else "")
            + "\n")
    sys.stdout.flush()
    tr.run()


if __name__ == "__main__":
    main()
