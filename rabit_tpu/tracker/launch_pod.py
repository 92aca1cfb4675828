"""Multi-host launcher: one worker per TPU-VM host (or hostfile entry).

Equivalent of the reference's cluster launchers
(reference: tracker/rabit_mpi.py:25-40 — mpirun submission;
tracker/rabit_hadoop.py:96-160 — workers as Hadoop streaming mappers).
The TPU-native deployment unit is a pod slice: one worker process per
host, each owning that host's chips, with the tracker reachable over
DCN.  Submission is pluggable the same way the reference's
``fun_submit`` is (reference: tracker/rabit_tracker.py:264-270):

* ``ssh``  — start workers over ssh to each host in a hostfile (the
  classic cluster path; TPU VMs expose plain ssh).
* ``local``— subprocesses on this machine (testing / single host).

The tracker assigns ranks in connect order keyed by task id, so restarts
keep their rank (reference: tracker/rabit_tracker.py:60-65).

Usage:
    python -m rabit_tpu.tracker.launch_pod --hostfile hosts.txt -- \
        python train.py
    python -m rabit_tpu.tracker.launch_pod --local -n 4 -- python train.py
"""
from __future__ import annotations

import argparse
import shlex
import subprocess
import sys
import threading

from rabit_tpu.tracker.tracker import Tracker


def _read_hostfile(path: str) -> list[str]:
    hosts = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line and not line.startswith("#"):
                hosts.append(line.split()[0])
    return hosts


def launch_pod(cmd: list[str], hosts: list[str] | None = None,
               n_local: int = 0, tracker_host: str | None = None,
               ssh_opts: str = "", verbose: bool = False,
               watchdog_sec: float | None = None,
               max_wd_restarts: int = 10,
               pidfile_dir: str = "/tmp",
               max_restarts: int = 0,
               ckpt_dir: str | None = None,
               heartbeat_sec: float | None = None,
               restart_backoff_ms: float = 250.0,
               min_workers: int | None = None,
               max_workers: int | None = None,
               state_dir: str | None = None,
               job: str | None = None,
               obs_port: int | None = None) -> int:
    """Run ``cmd`` once per host (or n_local subprocesses).

    Returns 0 when every worker exits cleanly.  Unlike the keepalive
    demo launcher, kill-point restarts are the platform's job (the
    reference makes the same split: rabit_demo restarts, mpi/hadoop
    delegate, reference: guide/README.md "Fault Tolerance").

    ``watchdog_sec``: hung-worker detection, same contract as
    ``launch_local`` — when a rendezvous round stalls that long, the
    tracker reports the silent workers and the launcher kills AT MOST
    ONE per stall event (killing one unblocks its Gloo peers into
    recovery with their checkpoint replicas intact) and restarts it
    with an incremented ``RABIT_RELAUNCH``.  Remote workers are killed
    over ssh via the pidfile each one writes at startup (the launcher
    owns watchdog restarts even though kill-point restarts are
    delegated: the launcher caused the death).

    Durability knobs, same contract as ``launch_local`` so pod launches
    get the full stack: ``ckpt_dir``/``heartbeat_sec`` export
    ``RABIT_CKPT_DIR``/``RABIT_HEARTBEAT_SEC`` to every worker (the
    heartbeat also arms the tracker's proactive failure detector, whose
    dead verdicts kill the hung remote over ssh and restart it), and
    ``max_restarts`` is the supervisor budget — a signal-killed worker
    (preemption, crash, kill-all) is relaunched with capped-exponential
    backoff instead of aborting the job; with a durable tier configured
    even whole-pod loss resumes from the last committed version.

    ``min_workers`` / ``max_workers`` / ``state_dir``: elastic
    membership + tracker HA, same contract as ``launch_local`` — the
    tracker admits late joiners up to the ceiling, heartbeat deaths
    scale the world down to the floor at checkpoint-commit boundaries
    (a signal-killed worker past its restart budget *leaves* instead
    of failing the job), workers get ``RABIT_ELASTIC=1``, and the
    control-plane state is journaled to ``state_dir`` so a restarted
    tracker resumes the job (doc/fault_tolerance.md "Elastic
    membership & tracker HA").
    """
    import os
    import time
    import uuid

    from rabit_tpu.tracker.launch_local import (chip_envs, is_dead_exit,
                                                is_watchdog_exit,
                                                make_dead_killer,
                                                make_stall_killer,
                                                restart_delay_ms)

    world = len(hosts) if hosts else n_local
    assert world > 0, "no hosts / workers requested"
    if job is not None:
        from rabit_tpu.tracker import protocol as P

        P.require_valid_job_id(job)
    # remote workers need a routable tracker address; local ones loopback
    from rabit_tpu.utils.net import routable_ip

    job_tag = uuid.uuid4().hex[:10]
    live: dict[int, subprocess.Popen] = {}
    started: dict[int, float] = {}
    watchdog_killed: set[int] = set()
    lock = threading.Lock()
    aborting = threading.Event()

    def _remote_pidfile(i: int) -> str:
        return f"{pidfile_dir}/rabit_pod_{job_tag}_{i}.pid"

    def _kill_worker(i: int, proc: subprocess.Popen) -> None:
        if hosts:
            # the local Popen is the ssh client; kill the REMOTE process
            # GROUP (the worker runs under setsid, so the pidfile pid is
            # its pgid — children die with it).  Best-effort: whatever
            # happens to the ssh leg, the local client must still die so
            # the keepalive loop can restart the worker.
            pidfile = _remote_pidfile(i)
            try:
                subprocess.run(
                    ["ssh"] + shlex.split(ssh_opts) + [
                        hosts[i],
                        f"kill -9 -$(cat {shlex.quote(pidfile)}) "
                        "2>/dev/null"],
                    timeout=30, check=False)
            finally:
                proc.kill()
        else:
            proc.kill()

    on_stall = make_stall_killer(world, live, started, lock,
                                 watchdog_killed, watchdog_sec,
                                 "launch_pod", kill_fn=_kill_worker)

    # Heartbeat dead verdicts use the same kill transport as the stall
    # watchdog (remote workers die over ssh via their pidfile) and the
    # same restart bookkeeping.
    on_dead = make_dead_killer(live, started, lock, watchdog_killed,
                               heartbeat_sec, "launch_pod",
                               kill_fn=_kill_worker)

    elastic = min_workers is not None or max_workers is not None
    # --local children share this host's chips, one each; over ssh each
    # worker owns its whole host and the platform's own environment
    chips = chip_envs(world) if not hosts else [{}] * world
    tracker = Tracker(world, host=tracker_host
                      or (routable_ip() if hosts else "127.0.0.1"),
                      watchdog_sec=watchdog_sec,
                      on_stall=on_stall if watchdog_sec else None,
                      on_dead=on_dead if heartbeat_sec else None,
                      min_workers=min_workers, max_workers=max_workers,
                      state_dir=state_dir, obs_port=obs_port)
    tracker.start()
    codes: list[int] = [0] * world

    def spawn(i: int, relaunch: int) -> subprocess.Popen:
        env = tracker.worker_env(task_id=str(i), job=job)
        env["RABIT_RELAUNCH"] = str(relaunch)
        if ckpt_dir is not None:
            env.setdefault("RABIT_CKPT_DIR", str(ckpt_dir))
        if heartbeat_sec:
            env.setdefault("RABIT_HEARTBEAT_SEC", str(heartbeat_sec))
        if elastic:
            env.setdefault("RABIT_ELASTIC", "1")
        if hosts:
            env_prefix = " ".join(
                f"{k}={shlex.quote(v)}" for k, v in env.items())
            # remote workers mirror the launch cwd (TPU-VM images keep
            # homogeneous paths across a slice).  setsid + `echo $$;
            # exec` makes the pidfile pid both the worker AND its
            # process-group id, so the watchdog's group kill takes the
            # worker's children down with it.
            worker = " ".join(shlex.quote(c) for c in cmd)
            inner = (f"echo $$ > {shlex.quote(_remote_pidfile(i))} && "
                     f"exec env {env_prefix} {worker}")
            remote = (f"cd {shlex.quote(os.getcwd())} && "
                      f"exec setsid sh -c {shlex.quote(inner)}")
            full = ["ssh"] + shlex.split(ssh_opts) + [hosts[i], remote]
            if verbose:
                sys.stderr.write(f"[launch_pod] {full}\n")
            return subprocess.Popen(full)
        penv = dict(os.environ)
        penv.update(env)
        penv.update(chips[i])
        return subprocess.Popen(cmd, env=penv)

    def run_one(i: int) -> None:
        wd_restarts = 0
        sup_restarts = 0
        while not aborting.is_set():
            try:
                proc = spawn(i, wd_restarts + sup_restarts)
            except Exception as e:  # ssh/worker binary missing
                sys.stderr.write(
                    f"[launch_pod] worker {i} failed to start: {e}\n")
                codes[i] = 1
                break
            with lock:
                live[i] = proc
                started[i] = time.monotonic()
            code = proc.wait()
            with lock:
                live.pop(i, None)
                was_watchdog = i in watchdog_killed
                watchdog_killed.discard(i)
            if (was_watchdog
                    and is_watchdog_exit(code, remote=bool(hosts))
                    and wd_restarts < max_wd_restarts):
                wd_restarts += 1
                continue
            if (is_dead_exit(code, remote=bool(hosts))
                    and sup_restarts < max_restarts
                    and not aborting.is_set()):
                # Supervisor path: signal-killed (preempted/crashed)
                # worker — relaunch under the bounded backoff budget.
                sup_restarts += 1
                delay_ms = restart_delay_ms(sup_restarts,
                                            restart_backoff_ms)
                sys.stderr.write(
                    f"[launch_pod] supervisor: worker {i} died (exit "
                    f"{code}); relaunch #{sup_restarts}/{max_restarts} "
                    f"in {delay_ms:.0f} ms\n")
                sys.stderr.flush()
                time.sleep(delay_ms / 1000.0)
                continue
            if (elastic and is_dead_exit(code, remote=bool(hosts))
                    and not aborting.is_set()):
                # Elastic leave (same contract as launch_local): a
                # preempted worker past its restart budget departs —
                # the tracker scales the world down at the next commit
                # boundary instead of the job failing.  note_dead is
                # the only death signal without heartbeats armed (and
                # a dedup'd no-op with them).
                sys.stderr.write(
                    f"[launch_pod] elastic: worker {i} left the job "
                    f"(exit {code}); world scales down\n")
                sys.stderr.flush()
                tracker.note_dead(str(i), job=job)
                break
            codes[i] = code
            break
        # a permanent nonzero exit means the rendezvous barrier can never
        # fill — abort the job instead of letting peers wait forever
        # (same contract as launch_local)
        if codes[i] != 0 and not aborting.is_set():
            aborting.set()
            tracker.stop()
            with lock:
                for p in live.values():
                    p.terminate()

    threads = [threading.Thread(target=run_one, args=(i,))
               for i in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if not aborting.is_set():
        tracker.join(timeout=10)
    tracker.stop()
    return next((c for c in codes if c != 0), 0)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(
        description="launch rabit_tpu workers across hosts (TPU pod slice)")
    ap.add_argument("--hostfile", help="file with one host per line")
    ap.add_argument("--local", action="store_true",
                    help="run workers as local subprocesses")
    ap.add_argument("-n", "--num-workers", type=int, default=0,
                    help="worker count for --local")
    ap.add_argument("--tracker-host", default=None,
                    help="address workers use to reach the tracker "
                         "(default: this host's primary interface)")
    ap.add_argument("--ssh-opts", default="",
                    help="extra options passed to ssh")
    ap.add_argument("--watchdog", type=float, default=None, metavar="SEC",
                    help="kill+restart workers that stall a rendezvous "
                         "round this long (hung-worker detection; remote "
                         "workers are killed over ssh)")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="supervisor budget: relaunch a signal-killed "
                         "worker (crash/preemption/kill-all) up to this "
                         "many times, backoff-paced; 0 disables")
    ap.add_argument("--ckpt-dir", default=None,
                    help="durable checkpoint tier (RABIT_CKPT_DIR): "
                         "writer ranks persist committed versions; a "
                         "cold restart resumes from disk — use a path "
                         "valid on every host ('{rank}' expands per "
                         "worker)")
    ap.add_argument("--heartbeat", type=float, default=None, metavar="SEC",
                    help="worker keepalive period (RABIT_HEARTBEAT_SEC); "
                         "arms the tracker's proactive failure detector "
                         "(hung remotes are killed over ssh + restarted)")
    ap.add_argument("--min-workers", type=int, default=None,
                    help="elastic floor: heartbeat-detected deaths scale "
                         "the world down (never below this) at the next "
                         "checkpoint-commit boundary")
    ap.add_argument("--max-workers", type=int, default=None,
                    help="elastic ceiling: late cmd=start registrants "
                         "join at the next rescale epoch, up to this "
                         "world size")
    ap.add_argument("--state-dir", default=None,
                    help="journal the tracker's control-plane state so "
                         "a restarted tracker resumes the job (tracker "
                         "HA)")
    ap.add_argument("--job", default=None, metavar="ID",
                    help="tenant name (rabit_job_id / RABIT_JOB_ID): "
                         "workers register under this job and their "
                         "logs/obs summaries carry it (doc/"
                         "fault_tolerance.md 'Multi-tenant tracker')")
    ap.add_argument("--obs-port", type=int, default=None,
                    help="serve the live telemetry plane while the job "
                         "runs: GET /metrics (Prometheus) + GET /status "
                         "(JSON) on this port; 0 = ephemeral "
                         "(doc/observability.md 'Live telemetry')")
    ap.add_argument("-v", "--verbose", action="store_true")
    ap.add_argument("cmd", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    cmd = args.cmd[1:] if args.cmd[:1] == ["--"] else args.cmd
    if not cmd:
        ap.error("missing worker command")
    hosts = _read_hostfile(args.hostfile) if args.hostfile else None
    if not hosts and not args.local:
        ap.error("need --hostfile or --local")
    sys.exit(launch_pod(cmd, hosts=hosts, n_local=args.num_workers,
                        tracker_host=args.tracker_host,
                        ssh_opts=args.ssh_opts, verbose=args.verbose,
                        watchdog_sec=args.watchdog,
                        max_restarts=args.max_restarts,
                        ckpt_dir=args.ckpt_dir,
                        heartbeat_sec=args.heartbeat,
                        min_workers=args.min_workers,
                        max_workers=args.max_workers,
                        state_dir=args.state_dir, job=args.job,
                        obs_port=args.obs_port))


if __name__ == "__main__":
    main()
