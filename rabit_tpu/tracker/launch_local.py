"""Local multi-process launcher with keepalive restart.

TPU-native equivalent of the reference's demo launcher
(reference: tracker/rabit_demo.py:28-64): starts a tracker plus N worker
processes, and — the fault-tolerance test harness — restarts any worker
that exits with the kill-point code (254), passing an incremented
``rabit_num_trial`` so deterministic mock kill-points fire once per life.

Usage:
    python -m rabit_tpu.tracker.launch_local -n 4 python guide/basic.py
"""
from __future__ import annotations

import argparse
import glob
import os
import random
import signal
import subprocess
import sys
import threading
import time

from rabit_tpu.tracker.tracker import Tracker

# Exit code meaning "killed at a mock kill-point; restart me".  The
# reference uses exit(-2) == 254 (src/allreduce_mock.h:165-171,
# tracker/rabit_demo.py:28-40); we keep the same convention.
RESTART_EXIT_CODE = 254


# One process per chip on ONE host: libtpu's process bounds by world
# size, the layout jax's own multi-process TPU harness uses.  Only the
# shape this repository has run on chips is listed (four v5e, PR 21).
_PROCESS_BOUNDS = {4: "2,2,1"}


def local_chips() -> int:
    """TPU chips this host exposes to its processes, counted from the
    device nodes — no JAX, no libtpu: a launcher that opened the chip
    would hold what its children need."""
    return len(glob.glob("/dev/vfio/[0-9]*")
               + glob.glob("/dev/accel[0-9]*"))


def chip_envs(n_workers: int) -> list[dict[str, str]]:
    """Per-child environment that gives each of ``n_workers`` local
    children exactly ONE chip (doc/scaling.md "One process per chip").

    A chip belongs to one process at a time, and a child that inherits
    the host's whole-slice environment tries to open every chip, so N
    children collide.  libtpu's per-process visibility and
    process-bounds variables split the host instead: child ``i`` sees
    chip ``i`` as task ``i`` of an N-process slice, and the runtimes
    find each other on the listed local ports.  Which
    ``jax.process_index()`` a child ends up with is the chip runtime's
    business (by chip position, not by task id); the XLA engine orders
    its process mesh by tracker rank whatever it is.

    Returns empty dicts where the host has no chips to give (CPU test
    runs) — and says so on stderr when it has chips but not one per
    worker, because those children then share or miss the chip."""
    chips = local_chips()
    if chips == 0:
        return [{}] * n_workers
    if n_workers > chips or n_workers not in _PROCESS_BOUNDS:
        sys.stderr.write(
            f"[launch] host exposes {chips} TPU chip(s); {n_workers} "
            "workers cannot each own one (supported: "
            f"{sorted(_PROCESS_BOUNDS)} <= chips) — children are NOT "
            "pinned to chips\n")
        return [{}] * n_workers
    from rabit_tpu.utils.net import free_port

    ports = [free_port() for _ in range(n_workers)]
    addrs = ",".join(f"localhost:{p}" for p in ports)
    bounds = _PROCESS_BOUNDS[n_workers]
    return [{
        "TPU_VISIBLE_CHIPS": str(i),
        "CLOUD_TPU_TASK_ID": str(i),
        "TPU_PROCESS_BOUNDS": bounds,
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": addrs,
        "TPU_PROCESS_PORT": str(ports[i]),
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
        # the older aliases of the same settings, kept consistent so a
        # host image that exports them whole-slice cannot contradict
        "TPU_WORKER_ID": str(i),
        "TPU_HOST_BOUNDS": bounds,
        "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
        "TPU_WORKER_HOSTNAMES": ",".join(["localhost"] * n_workers),
    } for i in range(n_workers)]


def is_dead_exit(code: int, remote: bool = False) -> bool:
    """Did the worker die of a signal (crash/kill/preemption) rather
    than exiting on its own?  The supervisor's restart budget
    (``max_restarts``) covers exactly these: a SIGKILL'd/preempted rank
    is relaunched, while a deliberate non-zero exit (assertion, typed
    error) still aborts the job.  On the ssh leg a remote group kill
    surfaces as 255 (dropped connection) or 128+9."""
    if code < 0:
        return True
    return remote and code in (255, 128 + signal.SIGKILL)


def is_watchdog_exit(code: int, remote: bool = False) -> bool:
    """True when an exit status is one the watchdog's kill can produce.

    The stall killer marks a worker as watchdog-killed *before* the kill
    lands; a worker that crashes on its own in that window must not be
    classified as watchdog-killed, or a genuinely failing worker gets
    silently restarted until the restart budget runs out.  The local
    kill is always SIGKILL (``Popen.kill``); on the ssh leg the local
    client may instead die from the remote group kill reaching it first
    (ssh exits 255 on a dropped connection, or 128+9 when the remote
    shell reports the signal)."""
    if code == -signal.SIGKILL:
        return True
    return remote and code in (255, 128 + signal.SIGKILL)


def restart_delay_ms(nth_restart: int, base_ms: float) -> float:
    """Supervisor relaunch pacing: capped exponential backoff (32x the
    base) with jitter, shared by both launchers."""
    return min(base_ms * (1 << (nth_restart - 1)),
               32.0 * base_ms) * random.uniform(0.5, 1.0)


def make_dead_killer(live: dict, started: dict, lock: threading.Lock,
                     watchdog_killed: set, heartbeat_sec: float | None,
                     label: str, kill_fn=None):
    """Shared heartbeat-verdict policy for the launchers (tracker
    ``on_dead``): kill the declared-dead worker so its keepalive
    restarts it, riding the watchdog-kill bookkeeping (a free restart —
    the launcher caused the death).

    The grace window keeps a stale verdict (the tracker re-notifies
    while a corpse's socket lingers) from killing the freshly
    relaunched life; the tracker re-notifies past it.  ``kill_fn(wid,
    proc)`` overrides the kill transport (the pod launcher kills remote
    workers over ssh) and must guarantee the local ``proc`` dies even
    when the remote leg fails."""
    dead_grace = max(2.0, 3.0 * float(heartbeat_sec or 0.0))

    def on_dead(task_id: str) -> None:
        try:
            wid = int(task_id)
        except (TypeError, ValueError):
            return
        with lock:
            proc = live.get(wid)
            if proc is None or proc.poll() is not None:
                return  # already dead; the keepalive is on it
            if time.monotonic() - started.get(wid, 0.0) < dead_grace:
                return  # freshly (re)started life: not the corpse
            watchdog_killed.add(wid)
        sys.stderr.write(f"[{label}] heartbeat: worker {wid} declared "
                         "dead; killing for restart\n")
        sys.stderr.flush()
        try:
            (kill_fn or (lambda _w, p: p.kill()))(wid, proc)
        except Exception as e:  # noqa: BLE001 — kill transport gone
            sys.stderr.write(f"[{label}] kill of worker {wid} "
                             f"failed: {e}\n")
            sys.stderr.flush()
            proc.kill()  # at minimum the local process must die

    return on_dead


def make_stall_killer(n_workers: int, live: dict, started: dict,
                      lock: threading.Lock, watchdog_killed: set,
                      watchdog_sec: float | None, label: str,
                      kill_fn=None):
    """Shared hung-worker policy for the launchers (tracker ``on_stall``).

    Kills AT MOST ONE hung worker per stall event.  Workers blocked
    inside a device collective (Gloo has no timeout) are unblocked by
    their *peer's* death — killing one sends RSTs that error the others
    out into host-path recovery with their in-memory checkpoint replicas
    intact.  Killing every silent worker at once would destroy all
    replicas and silently restart the job from version 0; if more than
    one is truly wedged, the next stall event (one watchdog period
    later) takes the next one.

    ``kill_fn(wid, proc)`` overrides the kill transport (the pod
    launcher kills remote workers over ssh); it runs OUTSIDE the lock —
    a slow remote kill must not freeze exit bookkeeping — and must
    guarantee the local ``proc`` dies even when the remote leg fails.
    """

    def on_stall(present: set, finished: set) -> None:
        all_ids = {str(i) for i in range(n_workers)}
        for tid in sorted(all_ids - present - finished):
            wid = int(tid)
            with lock:
                proc = live.get(wid)
                if proc is None or proc.poll() is not None:
                    continue  # already dead; keepalive is restarting it
                if (watchdog_sec is not None
                        and time.monotonic() - started.get(wid, 0.0)
                        < watchdog_sec):
                    continue  # freshly (re)started: give it a full period
                watchdog_killed.add(wid)
            sys.stderr.write(f"[{label}] watchdog: worker {wid} is "
                             "hung; killing for restart\n")
            sys.stderr.flush()
            try:
                (kill_fn or (lambda _w, p: p.kill()))(wid, proc)
            except Exception as e:  # noqa: BLE001 — kill transport gone
                sys.stderr.write(f"[{label}] kill of worker {wid} "
                                 f"failed: {e}\n")
                sys.stderr.flush()
                proc.kill()  # at minimum the local process must die
            return

    return on_stall


def launch(n_workers: int, cmd: list[str], max_trials: int = 10,
           verbose: bool = False,
           extra_env: dict[str, str] | None = None,
           watchdog_sec: float | None = None,
           obs_dir: str | None = None,
           max_restarts: int = 0,
           ckpt_dir: str | None = None,
           heartbeat_sec: float | None = None,
           restart_backoff_ms: float = 250.0,
           min_workers: int | None = None,
           max_workers: int | None = None,
           state_dir: str | None = None,
           job: str | None = None,
           obs_port: int | None = None,
           trace_dir: str | None = None) -> int:
    """Run ``cmd`` as n worker processes under a fresh tracker.

    ``job``: name the tenant (``rabit_job_id`` / ``RABIT_JOB_ID``) —
    workers register under this job on the tracker, their structured-
    log lines and obs summaries carry it, and their journal/obs state
    nests under the job's directory.  Mostly useful when several
    launches share one obs/state tree; the in-process tracker here
    serves whatever job its workers bring.

    ``watchdog_sec``: kill + restart workers the tracker reports as hung
    (registered peers are waiting on the rendezvous barrier, this worker
    stayed silent that long).  Detects SIGSTOP'd/wedged workers in
    seconds; safe — a restarted worker reloads from its checkpoint.

    ``obs_dir``: enable the telemetry subsystem — workers dump event
    traces and ship metric summaries there, and the tracker writes the
    aggregated ``obs_report.json`` (doc/observability.md).

    ``obs_port``: serve the live telemetry plane (``GET /metrics``
    Prometheus exposition + ``GET /status`` JSON; ``rabit_top.py``
    polls it) on this port while the job runs — 0 picks an ephemeral
    port (doc/observability.md "Live telemetry").

    ``max_restarts``: the supervisor budget — a worker that dies of a
    signal (SIGKILL, crash, preemption; NOT a deliberate non-zero exit)
    is relaunched up to this many times, paced by capped-exponential
    backoff (``restart_backoff_ms`` base, full jitter).  Combined with
    ``ckpt_dir`` this is the cold-restart path: even killing EVERY rank
    at once resumes the job from the last durably committed version.

    ``ckpt_dir`` / ``heartbeat_sec``: exported to workers as
    ``RABIT_CKPT_DIR`` / ``RABIT_HEARTBEAT_SEC``; a heartbeat period
    also arms the tracker's proactive failure detector, whose dead
    verdicts are handled like watchdog kills (kill + free restart).

    ``min_workers`` / ``max_workers``: **elastic membership**
    (doc/fault_tolerance.md "Elastic membership & tracker HA") — the
    tracker admits late ``cmd=start`` joiners up to the ceiling and
    turns heartbeat-detected deaths into a scale-*down* (never below
    the floor) instead of insisting on a same-rank relaunch; workers
    get ``RABIT_ELASTIC=1`` so the robust engine polls for rescale
    epochs at checkpoint-commit boundaries.  A signal-killed worker
    whose restart budget is spent *leaves* the job (the world shrinks)
    rather than failing it.

    ``state_dir``: journal the tracker's control-plane state through
    the atomic checkpoint-store tier so a restarted tracker on the same
    port resumes the job (the launcher's in-process tracker cannot
    crash alone, but the journal makes the job resumable by a fresh
    launcher pointed at the same state/ckpt dirs, and the standalone
    ``python -m rabit_tpu.tracker.tracker --state-dir`` path is what a
    production supervisor restarts).

    ``trace_dir``: causal-trace/postmortem directory — exported to
    workers as ``RABIT_TRACE_DIR`` so each rank persists its bounded
    flight record there on fault paths (link errors, aborts, SIGTERM),
    and the tracker dumps a control-plane journal at teardown;
    ``tools/postmortem.py`` merges them to reconstruct a dead job's
    last seconds (doc/observability.md "Causal tracing & postmortem").

    Returns 0 if every worker finished cleanly, else the first non-restart
    non-zero exit code.
    """
    if job is not None:
        from rabit_tpu.tracker import protocol as P

        P.require_valid_job_id(job)
    elastic = min_workers is not None or max_workers is not None
    extra_env = dict(extra_env or {})
    if obs_dir is not None:
        extra_env.setdefault("RABIT_OBS_DIR", obs_dir)
    if trace_dir is not None:
        # Workers persist flight records here on fault paths; the
        # tracker writes its control-plane journal at teardown.
        extra_env.setdefault("RABIT_TRACE_DIR", str(trace_dir))
    if ckpt_dir is not None:
        extra_env.setdefault("RABIT_CKPT_DIR", str(ckpt_dir))
    if heartbeat_sec:
        extra_env.setdefault("RABIT_HEARTBEAT_SEC", str(heartbeat_sec))
    if elastic:
        extra_env.setdefault("RABIT_ELASTIC", "1")
    failures: list[int] = []
    live: dict[int, subprocess.Popen] = {}
    lock = threading.Lock()
    aborting = threading.Event()
    watchdog_killed: set[int] = set()

    started: dict[int, float] = {}

    on_stall = make_stall_killer(n_workers, live, started, lock,
                                 watchdog_killed, watchdog_sec,
                                 "launch_local")

    on_dead = make_dead_killer(live, started, lock, watchdog_killed,
                               heartbeat_sec, "launch_local")

    chips = chip_envs(n_workers)
    tracker = Tracker(n_workers, watchdog_sec=watchdog_sec,
                      on_stall=on_stall if watchdog_sec else None,
                      obs_dir=obs_dir,
                      on_dead=on_dead if heartbeat_sec else None,
                      min_workers=min_workers, max_workers=max_workers,
                      state_dir=state_dir, obs_port=obs_port,
                      trace_dir=trace_dir)
    tracker.start()

    def keepalive(worker_id: int) -> None:
        trial = 0
        wd_restarts = 0
        sup_restarts = 0
        while not aborting.is_set():
            env = dict(os.environ)
            env.update(extra_env or {})
            env.update(tracker.worker_env(task_id=str(worker_id),
                                          job=job))
            env.update(chips[worker_id])
            env["RABIT_NUM_TRIAL"] = str(trial)
            # Total restarts of any cause.  Distinct from RABIT_NUM_TRIAL,
            # which counts only kill-point deaths so deterministic mock
            # scenarios stay reproducible under watchdog restarts; the
            # XLA engine keys its mid-job-relaunch (degraded) path on
            # this one.
            env["RABIT_RELAUNCH"] = str(trial + wd_restarts + sup_restarts)
            proc = subprocess.Popen(cmd, env=env)
            with lock:
                live[worker_id] = proc
                started[worker_id] = time.monotonic()
            code = proc.wait()
            with lock:
                live.pop(worker_id, None)
                was_watchdog = worker_id in watchdog_killed
                watchdog_killed.discard(worker_id)
            if (was_watchdog and is_watchdog_exit(code)
                    and wd_restarts < max_trials):
                # same trial number: the worker never reached its
                # kill-point, it was stopped from outside
                wd_restarts += 1
                continue
            if code == RESTART_EXIT_CODE and trial < max_trials:
                trial += 1
                if verbose:
                    sys.stderr.write(
                        f"[launch_local] worker {worker_id} hit a "
                        f"kill-point; restart #{trial}\n")
                continue
            if (is_dead_exit(code) and sup_restarts < max_restarts
                    and not aborting.is_set()):
                # Supervisor path: the worker was killed from outside
                # (preemption, crash, kill-all) — relaunch it under the
                # bounded, backoff-paced restart budget.  Its checkpoint
                # comes back from live replicas or the durable tier.
                sup_restarts += 1
                delay_ms = restart_delay_ms(sup_restarts,
                                            restart_backoff_ms)
                sys.stderr.write(
                    f"[launch_local] supervisor: worker {worker_id} "
                    f"died (exit {code}); relaunch "
                    f"#{sup_restarts}/{max_restarts} in "
                    f"{delay_ms:.0f} ms\n")
                sys.stderr.flush()
                time.sleep(delay_ms / 1000.0)
                continue
            if (elastic and is_dead_exit(code) and not aborting.is_set()):
                # Elastic leave: the restart budget (if any) is spent —
                # a preempted/killed worker departs instead of failing
                # the job.  Tell the tracker directly: with heartbeats
                # armed this is redundant (the EOF verdict fired first),
                # without them it is the ONLY signal that turns the
                # death into a scale-down at the next commit boundary
                # (never below min_workers); if the floor cannot absorb
                # it, the survivors' stall watchdog / link timeouts
                # still bound the job.
                sys.stderr.write(
                    f"[launch_local] elastic: worker {worker_id} left "
                    f"the job (exit {code}); world scales down\n")
                sys.stderr.flush()
                tracker.note_dead(str(worker_id), job=job)
                return
            if code != 0 and not aborting.is_set():
                failures.append(code)
                # A permanent failure means the rendezvous barrier can
                # never complete: kill the job instead of letting peers
                # sit in their (up to 600 s) control-plane timeouts.
                aborting.set()
                tracker.stop()
                with lock:
                    for p in live.values():
                        p.terminate()
            return

    threads = [threading.Thread(target=keepalive, args=(i,))
               for i in range(n_workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if not aborting.is_set():
        tracker.join(timeout=10)
    tracker.stop()
    return failures[0] if failures else 0


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(
        description="run N rabit_tpu workers locally under a tracker")
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--max-trials", type=int, default=10,
                    help="max restarts per worker on kill-point exit (254)")
    ap.add_argument("--watchdog", type=float, default=None, metavar="SEC",
                    help="kill+restart workers that stall a rendezvous "
                         "round this long (hung-worker detection)")
    ap.add_argument("--obs-dir", default=None,
                    help="enable telemetry: per-rank event traces + the "
                         "tracker-aggregated obs_report.json land here")
    ap.add_argument("--obs-port", type=int, default=None,
                    help="serve the live telemetry plane while the job "
                         "runs: GET /metrics (Prometheus) + GET /status "
                         "(JSON) on this port; 0 = ephemeral "
                         "(doc/observability.md 'Live telemetry')")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="supervisor budget: relaunch a signal-killed "
                         "worker (crash/preemption/kill-all) up to this "
                         "many times, backoff-paced; 0 disables")
    ap.add_argument("--ckpt-dir", default=None,
                    help="durable checkpoint tier: exported to workers "
                         "as RABIT_CKPT_DIR so writer ranks persist "
                         "committed versions and a cold restart resumes "
                         "from disk (doc/fault_tolerance.md)")
    ap.add_argument("--heartbeat", type=float, default=None, metavar="SEC",
                    help="worker keepalive period (RABIT_HEARTBEAT_SEC); "
                         "arms the tracker's proactive failure detector "
                         "— hung ranks are killed+relaunched without a "
                         "collective op having to touch them")
    ap.add_argument("--min-workers", type=int, default=None,
                    help="elastic floor: heartbeat-detected deaths "
                         "scale the world DOWN at the next checkpoint-"
                         "commit boundary (never below this) instead of "
                         "waiting for a same-rank relaunch; enables "
                         "elastic membership (RABIT_ELASTIC=1)")
    ap.add_argument("--max-workers", type=int, default=None,
                    help="elastic ceiling: late cmd=start registrants "
                         "are admitted as joiners at the next rescale "
                         "epoch, up to this world size; enables elastic "
                         "membership (RABIT_ELASTIC=1)")
    ap.add_argument("--state-dir", default=None,
                    help="journal the tracker's control-plane state "
                         "(rank map, epoch, members, barriers) through "
                         "the atomic checkpoint-store tier so a "
                         "restarted tracker resumes the job")
    ap.add_argument("--trace-dir", default=None,
                    help="causal-trace/postmortem directory: exported to "
                         "workers as RABIT_TRACE_DIR so each rank "
                         "persists its crash flight record there on "
                         "fault paths, and the tracker dumps its "
                         "control-plane journal at teardown "
                         "(doc/observability.md 'Causal tracing & "
                         "postmortem')")
    ap.add_argument("--job", default=None, metavar="ID",
                    help="tenant name (rabit_job_id / RABIT_JOB_ID): "
                         "workers register under this job, their log "
                         "lines and obs summaries carry it, and the "
                         "journal/obs state nests per job "
                         "(doc/fault_tolerance.md 'Multi-tenant "
                         "tracker')")
    ap.add_argument("-v", "--verbose", action="store_true")
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="worker command and its arguments")
    args = ap.parse_args(argv)
    if args.cmd and args.cmd[0] == "--":  # REMAINDER keeps the separator
        args.cmd = args.cmd[1:]
    if not args.cmd:
        ap.error("missing worker command")
    sys.exit(launch(args.num_workers, args.cmd, args.max_trials, args.verbose,
                    watchdog_sec=args.watchdog, obs_dir=args.obs_dir,
                    max_restarts=args.max_restarts, ckpt_dir=args.ckpt_dir,
                    heartbeat_sec=args.heartbeat,
                    min_workers=args.min_workers,
                    max_workers=args.max_workers,
                    state_dir=args.state_dir, job=args.job,
                    obs_port=args.obs_port, trace_dir=args.trace_dir))


if __name__ == "__main__":
    main()
