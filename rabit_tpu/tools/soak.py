"""Randomized fault-injection soak for the recovery protocol.

Generates a seeded random kill-point matrix (ranks × versions × seqnos,
including die-hard second-life kills) and runs the self-verifying
recovery workers under the keepalive launcher — the randomized big
brother of the fixed scenario matrix in tests/test_recovery.py
(reference analogue: the die-same/die-hard cases of test/test.mk:7-24).

Usage:
    python -m rabit_tpu.tools.soak [--world 8] [--rounds 3] [--seed 0]
        [--worker model_recover] [--ndata 5000] [--niter 8]
        [--engine mock|pyrobust]   # native C++ or pure-Python recovery
    python -m rabit_tpu.tools.soak --worker xla_restart [--world 4]
        # randomized die-plans through the XLA engine's device-plane
        # re-formation (--ndata/--niter/--kills do not apply)
    python -m rabit_tpu.tools.soak --chaos [--engine pyrobust|pysocket]
        # wire-level chaos: each round additionally drives a seeded
        # RABIT_CHAOS plan (resets, refused dials, partial writes,
        # stalls) through the pure-Python engines; pyrobust rounds mix
        # kills + resets (full recovery), pysocket rounds restrict the
        # mix to faults the non-fault-tolerant base engine must absorb
        # (connect retries, splits, sub-timeout stalls)
    python -m rabit_tpu.tools.soak --cold-restart --engine pyrobust
        # the durable-tier headline gate: each round kills EVERY rank
        # right after a seeded checkpoint commit (no in-memory replica
        # survives), the supervisor relaunches the world under the
        # restart budget, the relaunched lives cold-resume from
        # RABIT_CKPT_DIR, and the final model is compared bit-for-bit
        # against an uninterrupted reference run; mix in --chaos for
        # wire faults on top
    python -m rabit_tpu.tools.soak --elastic [--rounds 1]
        # the elastic-membership headline gate: the world grows 4->6
        # (late joiners admitted at a checkpoint-commit boundary) and
        # shrinks 6->3 (three seeded SIGKILLs -> heartbeat scale-down)
        # mid-training, with the TRACKER killed and restarted once at a
        # seeded point (journal replayed from --state-dir; the workers'
        # registration retry bridges the outage).  Each rescale segment
        # is then re-run as a FRESH job at that world size from the
        # same committed blob and the models compared bit-for-bit at
        # the next boundary; mix in --chaos for wire faults on top
    python -m rabit_tpu.tools.soak --adapt [--chaos]
        # the closed-loop gate: a world-4 pyrobust job with rank 0
        # deliberately slowed runs under a tracker with the adaptive
        # controller armed (--adapt --tune-dir); the controller must
        # (a) converge to a measurably faster schedule than the static
        # pick (switch decision whose challenger cost beats the
        # incumbent, asserted from the merged span data), (b) demote
        # the slowed rank out of hierarchical leader roles, (c) keep
        # the final model bit-exact vs an uninterrupted run, and (d)
        # persist what it learned into the TuningCache so a FRESH
        # rabit_sched=auto job starts on the learned schedule; mix in
        # --chaos for wire faults on top
    python -m rabit_tpu.tools.soak --serve [--rounds 1]
        # the serving-plane gate (doc/serving.md): a 2-rank fleet with
        # pinned capacity (the slow-ms seam) serves bitwise-verified
        # traffic through steady load, a live model-version rollover,
        # a 2x-capacity open-loop overload spike (typed Overloaded
        # sheds with retry-after, served p99 within 5x steady — no
        # queue collapse), a mid-traffic rank SIGKILL absorbed by an
        # elastic epoch with bounded availability dip, and a
        # train-while-serving co-tenant job that must stay bit-exact
        # vs a solo run
    python -m rabit_tpu.tools.soak --postmortem [--rounds 1]
        # the crash-forensics gate (doc/observability.md "Causal
        # tracing & postmortem"): a world-4 pysocket job has a seeded
        # rank SIGKILLed immediately before a seeded allreduce; the
        # survivors' LinkError fault paths persist their always-on
        # flight recorders under --trace-dir and tools/postmortem.py
        # must name the first-dead rank and the in-flight op
        # (kind/seq) from the persisted artifacts alone
    python -m rabit_tpu.tools.soak --tenants 2 [--chaos] [--elastic]
        [--adapt]
        # the multi-tenant isolation gate: N jobs train concurrently
        # against ONE shared tracker (--max-jobs admission armed);
        # mid-training EVERY worker of tenant A is SIGKILLed — the
        # tracker must survive, orphan-GC tenant A's job, and tenant
        # B's final model must be BIT-EXACT against a solo run of the
        # same workload on a dedicated tracker (no cross-tenant
        # interference); mix in --chaos for wire faults on both
        # tenants, --elastic to arm elastic membership on the shared
        # tracker
Exits non-zero on the first failed run, printing the kill matrix (and
chaos plan) so the failure is reproducible.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import random
import sys

# repo root (tools/ -> rabit_tpu/ -> repo); the workers live in
# tests/workers/, so resolve against the source checkout instead of the
# cwd.  tests/ is not packaged — installed environments must pass
# --worker-path explicitly.
_REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]


def gen_matrix(rng: random.Random, world: int, niter: int,
               nkills: int) -> str:
    """';'-joined mock=rank,version,seqno,ndeath kill-points."""
    points = set()
    while len(points) < nkills:
        rank = rng.randrange(world)
        version = rng.randrange(niter)
        seqno = rng.randrange(4)
        # occasionally kill the same point on the restarted life too
        ndeath = 1 if rng.random() < 0.2 and any(
            p[:3] == (rank, version, seqno) for p in points) else 0
        points.add((rank, version, seqno, ndeath))
    return ";".join(",".join(map(str, p)) for p in sorted(points))


def gen_chaos(rng: random.Random, engine: str,
              link: bool = False) -> str:
    """One seeded RABIT_CHAOS plan (doc/fault_tolerance.md "Chaos
    testing").  pyrobust gets the full mix — recovery must absorb
    mid-stream resets on top of kill-points; pysocket (no recovery)
    gets only the faults the hardened base transport must survive:
    refused/slow dials (retry+backoff), partial splits, EINTR, and
    stalls well under the link timeout.  ``link`` (the --shards gate)
    additionally arms the tracker-link sites — seeded resets/stalls at
    the hello and heartbeat exchanges, the faults a dying shard
    produces — which the worker must turn into counted retries and
    re-dials, never a hang."""
    seed = rng.randrange(1 << 30)
    tracker_link = ("reset@hello=0.2*2;stall@hb=0.25*4;reset@hb=0.1*2;"
                    if link else "")
    if engine == "pyrobust":
        return (f"{seed}:{tracker_link}"
                f"reset@io=0.002*2;refuse@connect=0.25*6;"
                f"partial@io=0.05*400;eintr@io=0.02*50;stall@io=0.02*40;"
                f"stallms=25;budget=512")
    return (f"{seed}:{tracker_link}refuse@connect=0.25*6;"
            f"partial@io=0.08*400;"
            f"eintr@io=0.02*50;stall@io=0.02*40;stallms=20;budget=512")


def run_cold_restart(args, rng: random.Random,
                     round_obs_dir) -> int:
    """Seeded kill-ALL-ranks rounds against the durable checkpoint tier
    (--cold-restart): every rank SIGKILLs itself right after committing
    a seeded version, the supervisor relaunches the world, and the
    resumed run's final model must be bit-identical to an uninterrupted
    reference."""
    import shutil
    import tempfile

    from rabit_tpu.tracker.launch_local import launch

    worker_path = args.worker_path or str(
        _REPO_ROOT / "tests" / "workers" / "cold_restart.py")
    base = pathlib.Path(tempfile.mkdtemp(prefix="rabit_cold_soak_"))
    try:
        ref_dir = base / "ref"
        code = launch(
            args.world, [sys.executable, worker_path,
                         str(args.ndata), str(args.niter)],
            extra_env={"RABIT_ENGINE": "pyrobust",
                       "RABIT_OUT_DIR": str(ref_dir)})
        if code != 0:
            print(f"[soak] FAILED: uninterrupted reference run exited "
                  f"{code}", flush=True)
            return 1
        for r in range(args.rounds):
            kill_iter = 1 + rng.randrange(max(args.niter - 1, 1))
            rdir = base / f"round{r}"
            cold_dir = rdir / "cold"
            cold_dir.mkdir(parents=True)
            env = {"RABIT_ENGINE": "pyrobust",
                   "RABIT_OUT_DIR": str(rdir / "out"),
                   "RABIT_COLD_DIR": str(cold_dir),
                   "RABIT_COLD_KILL_ITER": str(kill_iter)}
            if args.chaos:
                env["RABIT_CHAOS"] = gen_chaos(rng, "pyrobust")
                if "RABIT_TIMEOUT_SEC" not in os.environ:
                    env["RABIT_TIMEOUT_SEC"] = "20"
                if "RABIT_BACKOFF_BASE_MS" not in os.environ:
                    env["RABIT_BACKOFF_BASE_MS"] = "20"
            print(f"[soak] round {r}: cold-restart kill_iter={kill_iter} "
                  f"chaos={env.get('RABIT_CHAOS', '')}", flush=True)
            code = launch(
                args.world, [sys.executable, worker_path,
                             str(args.ndata), str(args.niter)],
                extra_env=env, ckpt_dir=str(rdir / "ckpt"),
                heartbeat_sec=args.heartbeat,
                max_restarts=args.max_restarts, restart_backoff_ms=100,
                obs_dir=round_obs_dir(r))
            if code != 0:
                print(f"[soak] FAILED (exit {code}) — reproduce with "
                      f"RABIT_COLD_KILL_ITER='{kill_iter}' "
                      f"RABIT_CHAOS='{env.get('RABIT_CHAOS', '')}'",
                      flush=True)
                return 1
            for rank in range(args.world):
                ref = (ref_dir / f"final.{rank}").read_bytes()
                got = (rdir / "out" / f"final.{rank}").read_bytes()
                if ref != got:
                    print(f"[soak] FAILED: rank {rank} final model is "
                          f"NOT bit-identical after the cold restart "
                          f"(kill_iter={kill_iter})", flush=True)
                    return 1
            print(f"[soak] round {r}: resumed at v{kill_iter}, final "
                  "model bit-identical", flush=True)
        print(f"[soak] {args.rounds} cold-restart rounds passed",
              flush=True)
        return 0
    finally:
        shutil.rmtree(base, ignore_errors=True)


def _free_port() -> int:
    """A locally-bindable port for the restartable tracker (the restart
    must land on the SAME port, so the ephemeral-bind trick of the
    in-process tracker does not apply)."""
    from rabit_tpu.utils.net import free_port

    return free_port("127.0.0.1")


def _wait_port(port: int, deadline_sec: float = 20.0) -> bool:
    import socket
    import time

    end = time.monotonic() + deadline_sec
    while time.monotonic() < end:
        try:
            socket.create_connection(("127.0.0.1", port),
                                     timeout=1.0).close()
            return True
        except OSError:
            time.sleep(0.05)
    return False


def _scrape(port: int, path: str, timeout: float = 3.0) -> str | None:
    """One GET against the tracker's live telemetry plane (--obs-port);
    None while the endpoint is unreachable."""
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
            return r.read().decode()
    except (urllib.error.URLError, OSError, ValueError):
        return None


def _live_scrape_ok(port: int, tenants: int) -> str | None:
    """The mid-run live-plane check of the --tenants gate: GET /metrics
    and /status must return correctly job-labeled data for EVERY
    tenant, with no op series missing its job label.  Returns None when
    satisfied, else a description of what is (still) wrong — the
    caller polls until the deadline."""
    import json

    metrics = _scrape(port, "/metrics")
    raw = _scrape(port, "/status")
    if metrics is None or raw is None:
        return "GET /metrics or /status unreachable"
    try:
        status = json.loads(raw)
    except ValueError:
        return "/status is not valid JSON"
    for j in range(tenants):
        name = f"tenant{j}"
        if name not in (status.get("jobs") or {}):
            return f"/status has no job {name!r} yet"
        if f'job="{name}"' not in metrics:
            return f"/metrics has no series labeled job={name!r} yet"
    ops = [ln for ln in metrics.splitlines()
           if ln.startswith("rabit_op_") and not ln.startswith("#")]
    if not ops:
        return "no rabit_op_* series streamed yet"
    for ln in ops:
        if 'job="' not in ln:
            return f"op series without a job label: {ln!r}"
    return None


def _committed_version(ckpt_dir) -> int:
    """Newest version any writer's manifest records (driver-side poll:
    how the gate times joins/kills to checkpoint-commit progress)."""
    import glob
    import json

    best = 0
    for m in glob.glob(str(ckpt_dir / "manifest*.json")):
        try:
            with open(m) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue  # mid-rename read: the next poll sees it
        for e in doc.get("entries", []):
            if isinstance(e.get("version"), int):
                best = max(best, e["version"])
    return best


def _journal_state(state_dir) -> dict | None:
    """The tracker's newest journaled control-plane state, read WITHOUT
    CheckpointStore (whose stale-tmp sweep could race the live
    tracker's in-flight persist)."""
    import glob
    import json

    from rabit_tpu.ckpt.store import unpack_blob

    best = None
    for m in glob.glob(str(state_dir / "manifest*.json")):
        try:
            with open(m) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        for e in doc.get("entries", []):
            if isinstance(e.get("version"), int) and (
                    best is None or e["version"] > best["version"]):
                best = e
    if best is None:
        return None
    try:
        dc = unpack_blob((state_dir / best["file"]).read_bytes())
        return json.loads(dc.global_blob.decode())
    except (OSError, ValueError):
        return None


def _read_rescales(out_dir) -> dict[int, tuple[int, int, int]]:
    """epoch -> (version, old_world, new_world) from the workers'
    rescale markers; inconsistent reports for one epoch return -1
    versions so the caller fails loudly."""
    import glob
    import json

    got: dict[int, tuple[int, int, int]] = {}
    for path in glob.glob(str(out_dir / "rescale.*.jsonl")):
        try:
            lines = open(path).read().splitlines()
        except OSError:
            continue
        for line in lines:
            if not line.strip():
                continue
            ev = json.loads(line)
            key = int(ev["epoch"])
            val = (int(ev["version"]), int(ev["old_world"]),
                   int(ev["new_world"]))
            if key in got and got[key] != val:
                got[key] = (-1, -1, -1)
            else:
                got.setdefault(key, val)
    return got


def run_elastic(args, rng: random.Random, round_obs_dir) -> int:
    """The elastic-membership headline gate (--elastic): grow 4->6 via
    late joiners, shrink 6->3 via seeded SIGKILLs (heartbeat
    scale-down), a seeded tracker kill+restart mixed in — then each
    rescale segment re-run as a fresh job at that world size from the
    same committed blob, bit-identical at the next boundary."""
    import json
    import shutil
    import subprocess
    import tempfile
    import time

    from rabit_tpu import ckpt as ckpt_mod
    from rabit_tpu.tracker.launch_local import launch

    worker_path = args.worker_path or str(
        _REPO_ROOT / "tests" / "workers" / "elastic_worker.py")
    base = pathlib.Path(tempfile.mkdtemp(prefix="rabit_elastic_soak_"))

    def fail(r: int, why: str, procs, tracker) -> int:
        print(f"[soak] FAILED (round {r}): {why}", flush=True)
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        if tracker is not None and tracker.poll() is None:
            tracker.kill()
        return 1

    try:
        for r in range(args.rounds):
            rdir = base / f"round{r}"
            ckpt_dir = rdir / "ckpt"
            out = rdir / "out"
            state = rdir / "state"
            for d in (ckpt_dir, out, state):
                d.mkdir(parents=True)
            obs = round_obs_dir(r)
            grow_at = 2 + rng.randrange(3)
            shrink_gap = 4 + rng.randrange(3)
            kill_tracker_after_grow = bool(rng.randrange(2))
            # The commit hold pins the grow boundary near grow_at, so
            # this leaves the 6-world segment shrink_gap commits and
            # the 3-world tail a healthy remainder.
            niter = max(args.niter, grow_at + shrink_gap + 16)
            chaos = gen_chaos(rng, "pyrobust") if args.chaos else ""
            port = _free_port()
            print(f"[soak] round {r}: elastic 4->6->3, grow@v{grow_at}, "
                  f"shrink {shrink_gap} commits later, tracker restart "
                  f"{'after' if kill_tracker_after_grow else 'before'} "
                  f"the grow, niter={niter} chaos={chaos}", flush=True)

            tracker_cmd = [sys.executable, "-m",
                           "rabit_tpu.tracker.tracker", "-n", "4",
                           "--host", "127.0.0.1", "--port", str(port),
                           "--min-workers", "2", "--max-workers", "6",
                           "--state-dir", str(state)]
            if obs:
                tracker_cmd += ["--obs-dir", obs]
            tracker = subprocess.Popen(tracker_cmd)
            procs: dict[str, subprocess.Popen] = {}
            if not _wait_port(port):
                return fail(r, "tracker never came up", procs, tracker)

            env_base = dict(os.environ)
            env_base.update({
                "RABIT_TRACKER_URI": "127.0.0.1",
                "RABIT_TRACKER_PORT": str(port),
                "RABIT_HOLD_FILE": str(out / "hold"),
                "RABIT_ENGINE": "pyrobust",
                "RABIT_ELASTIC": "1",
                # EOF on the heartbeat channel (a SIGKILL) is the
                # scale-down signal; a generous miss budget keeps a
                # CPU-contended beat thread from false verdicts.
                "RABIT_HEARTBEAT_SEC": "0.5",
                "RABIT_HEARTBEAT_MISS": "10",
                "RABIT_CKPT_DIR": str(ckpt_dir),
                "RABIT_CKPT_KEEP": "512",  # every boundary blob kept
                "RABIT_OUT_DIR": str(out),
                "RABIT_ITER_SLEEP": "0.15",
                "RABIT_TIMEOUT_SEC": "20",
                "RABIT_BACKOFF_BASE_MS": "20",
            })
            if obs:
                env_base["RABIT_OBS_DIR"] = obs
            if chaos:
                env_base["RABIT_CHAOS"] = chaos

            def spawn(tid: str) -> subprocess.Popen:
                env = dict(env_base)
                env["RABIT_TASK_ID"] = tid
                env["RABIT_WORLD_SIZE"] = "4"
                return subprocess.Popen(
                    [sys.executable, worker_path, str(args.ndata),
                     str(niter)], env=env)

            for i in range(4):
                procs[str(i)] = spawn(str(i))

            def wait_for(pred, what: str, deadline_sec: float) -> bool:
                end = time.monotonic() + deadline_sec
                while time.monotonic() < end:
                    if pred():
                        return True
                    if any(p.poll() not in (None, 0)
                           for p in procs.values()):
                        return False  # a worker failed; caller reports
                    time.sleep(0.1)
                return False

            def restart_tracker(t):
                t.kill()
                t.wait()
                print(f"[soak] round {r}: tracker killed; restarting on "
                      f"port {port} from {state}", flush=True)
                time.sleep(0.5)
                t2 = subprocess.Popen(tracker_cmd)
                if not _wait_port(port):
                    return None
                return t2

            if not wait_for(
                    lambda: _committed_version(ckpt_dir) >= grow_at,
                    "grow point", 120):
                return fail(r, f"never committed v{grow_at} "
                            "(pre-grow)", procs, tracker)
            if not kill_tracker_after_grow:
                tracker = restart_tracker(tracker)
                if tracker is None:
                    return fail(r, "tracker restart never came up",
                                procs, tracker)
            # Hold the commit boundary while BOTH joiners park, so the
            # grow lands as one 4->6 epoch instead of 4->5->6 (the
            # tracker batches every parked joiner into one pending
            # target; the journal tells us when it reached 6).
            hold = out / "hold"
            hold.touch()
            for tid in ("4", "5"):
                procs[tid] = spawn(tid)
            both_parked = wait_for(
                lambda: (_journal_state(state) or {}).get(
                    "target_world") == 6, "joiners parked", 60)
            hold.unlink()
            if not both_parked:
                return fail(r, "the tracker never saw both joiners "
                            "(target_world != 6)", procs, tracker)
            if not wait_for(
                    lambda: any(v[2] == 6
                                for v in _read_rescales(out).values()),
                    "grow rescale", 120):
                return fail(r, "the 4->6 rescale never landed",
                            procs, tracker)
            if kill_tracker_after_grow:
                tracker = restart_tracker(tracker)
                if tracker is None:
                    return fail(r, "tracker restart never came up",
                                procs, tracker)
            v_grow = next(v[0] for v in _read_rescales(out).values()
                          if v[2] == 6)
            shrink_at = max(grow_at, v_grow) + shrink_gap
            if not wait_for(
                    lambda: _committed_version(ckpt_dir) >= shrink_at,
                    "shrink point", 120):
                return fail(r, f"never committed v{shrink_at} "
                            "(post-grow)", procs, tracker)
            victims = rng.sample(sorted(procs), 3)
            print(f"[soak] round {r}: grow landed at v{v_grow}; killing "
                  f"tasks {victims} at >=v{shrink_at} for the 6->3 "
                  "scale-down", flush=True)
            for tid in victims:
                procs[tid].kill()
            survivors = {t: p for t, p in procs.items()
                         if t not in victims}

            deadline = time.monotonic() + 300
            for tid, p in survivors.items():
                left = max(deadline - time.monotonic(), 1)
                try:
                    code = p.wait(timeout=left)
                except subprocess.TimeoutExpired:
                    return fail(r, f"worker {tid} hung past the deadline",
                                procs, tracker)
                if code != 0:
                    return fail(r, f"worker {tid} exited {code}",
                                procs, tracker)
            try:
                code = tracker.wait(timeout=60)
            except subprocess.TimeoutExpired:
                return fail(r, "tracker never saw the job finish",
                            procs, tracker)
            if code != 0:
                return fail(r, f"tracker exited {code}", procs, tracker)

            # -- verification: world history + segmented bit-identity --
            rescales = sorted(_read_rescales(out).items())
            history = [(v, ow, nw) for _e, (v, ow, nw) in rescales]
            worlds = [(ow, nw) for _v, ow, nw in history]
            if worlds != [(4, 6), (6, 3)] or any(
                    v < 0 for v, _o, _n in history):
                return fail(r, f"unexpected rescale history {history}",
                            procs, tracker)
            v1, v2 = history[0][0], history[1][0]
            finals = sorted(out.glob("final.*"))
            blobs = {f.name: f.read_bytes() for f in finals}
            if len(finals) != 3 or len(set(blobs.values())) != 1:
                return fail(r, f"expected 3 identical finals, got "
                            f"{sorted(blobs)}", procs, tracker)
            elastic_final = finals[0].read_bytes()
            est = ckpt_mod.CheckpointStore(str(ckpt_dir), rank=0)

            print(f"[soak] round {r}: elastic run done (4->6 at v{v1}, "
                  f"6->3 at v{v2}); running fixed-world reference "
                  "segments", flush=True)
            for v0, world, vend in ((0, 4, v1), (v1, 6, v2),
                                    (v2, 3, None)):
                ref = rdir / f"ref_w{world}"
                ref_ckpt = ref / "ckpt"
                ref_out = ref / "out"
                ref_ckpt.mkdir(parents=True)
                if v0:
                    dc = est.load_version(v0)
                    if dc is None:
                        return fail(r, f"boundary blob v{v0} missing "
                                    "from the elastic durable tier",
                                    procs, tracker)
                    ckpt_mod.CheckpointStore(
                        str(ref_ckpt), rank=0, keep=512).persist(
                            v0, world, dc.global_blob)
                env = {"RABIT_ENGINE": "pyrobust",
                       "RABIT_OUT_DIR": str(ref_out),
                       "RABIT_CKPT_DIR": str(ref_ckpt),
                       "RABIT_CKPT_KEEP": "512"}
                if v0:
                    env["RABIT_EXPECT_START_VERSION"] = str(v0)
                if vend:
                    env["RABIT_STOP_ITER"] = str(vend)
                code = launch(world, [sys.executable, worker_path,
                                      str(args.ndata), str(niter)],
                              extra_env=env)
                if code != 0:
                    return fail(r, f"reference segment (world {world}, "
                                f"v{v0}->{vend or niter}) exited {code}",
                                procs, tracker)
                if vend:
                    a = est.load_version(vend)
                    b = ckpt_mod.CheckpointStore(
                        str(ref_ckpt), rank=0).load_version(vend)
                    if a is None or b is None \
                            or a.global_blob != b.global_blob:
                        return fail(
                            r, f"model at v{vend} differs from a fresh "
                            f"world-{world} job resumed at v{v0}",
                            procs, tracker)
                else:
                    ref_final = sorted(ref_out.glob("final.*"))
                    if not ref_final or ref_final[0].read_bytes() \
                            != elastic_final:
                        return fail(
                            r, f"final model differs from a fresh "
                            f"world-{world} job resumed at v{v0}",
                            procs, tracker)
            print(f"[soak] round {r}: rescales bit-identical to fixed-"
                  f"world references at v{v1}/v{v2}/final", flush=True)
        print(f"[soak] {args.rounds} elastic rounds passed", flush=True)
        return 0
    finally:
        shutil.rmtree(base, ignore_errors=True)


def run_adapt(args, rng: random.Random, round_obs_dir) -> int:
    """The closed-loop adaptive gate (--adapt): a world-4 pyrobust job
    with rank 0 deliberately slowed (RABIT_SLOW_RANK) runs under a
    tracker whose AdaptiveController is armed.  The gate fails unless
    the controller (a) converges to a measurably FASTER schedule than
    the static pick — a switch decision whose challenger cost beats
    the pre-switch incumbent, asserted from the merged span data — (b)
    demotes the slowed rank out of hierarchical leader roles, (c)
    leaves the final model bit-exact vs an uninterrupted reference
    run, and (d) round-trips the learned TuningCache: a FRESH
    rabit_sched=auto job must start on the learned schedule.

    With --chaos the wire timing is deliberately poisoned, so (a)
    relaxes to "the controller keeps deciding" (a switch, when it does
    happen, is still evidence-checked and round-tripped); demotion,
    bit-exactness and tracker survival stay mandatory."""
    import json as _json
    import shutil
    import subprocess
    import tempfile
    import time

    from rabit_tpu.sched import TuningCache
    from rabit_tpu.tracker.launch_local import launch

    world = 4
    # Room for the exploration probes; chaos rounds burn iterations on
    # forced recovery, so they get a longer run.
    niter = max(args.niter, 72 if args.chaos else 48)
    # 256KB f32 / 512KB f64 payloads: the regime where BENCH_sched.json
    # measured multi-x schedule gains, so a faster-than-static winner
    # exists for the controller to find.  An explicit --ndata wins.
    ndata = args.ndata if args.ndata_explicit else 65536
    worker_path = args.worker_path or str(
        _REPO_ROOT / "tests" / "workers" / "cold_restart.py")
    base = pathlib.Path(tempfile.mkdtemp(prefix="rabit_adapt_soak_"))
    groups = "0,0,1,1"                 # two host groups: hier applies

    def fail(r, why, procs=(), tracker=None) -> int:
        print(f"[soak] FAILED (round {r}): {why}", flush=True)
        for p in procs:
            if p.poll() is None:
                p.kill()
        if tracker is not None and tracker.poll() is None:
            tracker.kill()
        return 1

    # launch_local's tracker runs IN-PROCESS and reads the group
    # override from its own environment (extra_env only reaches the
    # workers) — the warm-start auto job below needs the same two-group
    # handout or hier could never apply.
    saved_groups = os.environ.get("RABIT_TRACKER_GROUPS")
    os.environ["RABIT_TRACKER_GROUPS"] = groups
    try:
        # Uninterrupted reference (dedicated tracker, no controller, no
        # slow rank): the bits the adaptive run must reproduce — the
        # worker's ops are exact-arithmetic, so schedule switches and
        # pacing sleeps must not change a single bit.
        ref_out = base / "ref"
        code = launch(world, [sys.executable, worker_path, str(ndata),
                              str(niter)],
                      extra_env={"RABIT_ENGINE": "pyrobust",
                                 "RABIT_OUT_DIR": str(ref_out)})
        if code != 0:
            print(f"[soak] FAILED: reference run exited {code}",
                  flush=True)
            return 1
        ref = {i: (ref_out / f"final.{i}").read_bytes()
               for i in range(world)}

        for r in range(args.rounds):
            rdir = base / f"round{r}"
            tune = rdir / "tune"
            obs = round_obs_dir(r)
            chaos = gen_chaos(rng, "pyrobust") if args.chaos else ""
            port = _free_port()
            obs_port = _free_port()
            print(f"[soak] round {r}: adaptive controller armed, world "
                  f"{world}, {niter} iters x {ndata} floats; rank 0 "
                  f"deliberately slowed; live plane on :{obs_port}"
                  + (f" chaos={chaos}" if chaos else ""), flush=True)
            tenv = dict(os.environ)
            tenv.update({
                "RABIT_TRACKER_GROUPS": groups,
                # Fast convergence knobs for the gate: small per-
                # (schedule, bucket) windows, a short demotion streak,
                # and a tight switch margin — the pessimized tree
                # incumbent usually loses by 1.5-2x here, but a noisy
                # shared box occasionally compresses the gap under the
                # production 15% margin and the controller (correctly)
                # settles; 5% keeps the gate about the LOOP, not about
                # one box's run-to-run variance.  Production defaults
                # are deliberately slower/wider (doc/performance.md
                # "Online adaptation").
                "RABIT_ADAPT_MIN_SAMPLES": "4",
                "RABIT_ADAPT_MARGIN": "0.05",
                "RABIT_DEMOTE_CHECKS": "2",
            })
            tracker_cmd = [sys.executable, "-m",
                           "rabit_tpu.tracker.tracker", "-n", str(world),
                           "--host", "127.0.0.1", "--port", str(port),
                           "--obs-port", str(obs_port),
                           "--adapt", "--tune-dir", str(tune)]
            if obs:
                tracker_cmd += ["--obs-dir", obs]
            tracker = subprocess.Popen(tracker_cmd, env=tenv)
            procs: list[subprocess.Popen] = []
            if not _wait_port(port):
                return fail(r, "tracker never came up", procs, tracker)

            out_dir = rdir / "out"
            out_dir.mkdir(parents=True)
            env = dict(os.environ)
            env.update({
                "RABIT_TRACKER_URI": "127.0.0.1",
                "RABIT_TRACKER_PORT": str(port),
                "RABIT_WORLD_SIZE": str(world),
                "RABIT_ENGINE": "pyrobust",
                "RABIT_ADAPT": "1",
                "RABIT_OUT_DIR": str(out_dir),
                "RABIT_CKPT_DIR": str(rdir / "ckpt"),
                "RABIT_OBS": "1",
                "RABIT_OBS_FLUSH_SEC": "0.2",
                "RABIT_HEARTBEAT_SEC": "0.3",
                "RABIT_HEARTBEAT_MISS": "10",
                "RABIT_ITER_SLEEP": "0.05",
                # The injected straggler: rank 0 — a hier GROUP LEADER
                # by default, so its demotion observably moves the
                # leadership (groups 0,0,1,1: leaders [0,2] -> [1,2]).
                "RABIT_SLOW_RANK": "0",
                "RABIT_SLOW_EXTRA": "0.3",
                # Pessimize the static pick DETERMINISTICALLY: with the
                # crossover pushed past the payload sizes, static rides
                # the latency-bound tree at these bandwidth-bound
                # 256-512KB payloads — the regime where BENCH_sched.json
                # measured the ring-family schedules 2-3x faster, so a
                # measurably-better challenger exists for the
                # controller to find regardless of box noise.  (The
                # bit-exact reference runs the DEFAULT static config:
                # the worker's ops are exact arithmetic, so schedule
                # choice never changes the model bits.)
                "RABIT_RING_THRESHOLD_BYTES": "8MB",
            })
            if chaos:
                env["RABIT_CHAOS"] = chaos
                env.setdefault("RABIT_TIMEOUT_SEC", "20")
                env.setdefault("RABIT_BACKOFF_BASE_MS", "20")
            if obs:
                env["RABIT_OBS_DIR"] = obs
            for i in range(world):
                env_i = dict(env)
                env_i["RABIT_TASK_ID"] = str(i)
                procs.append(subprocess.Popen(
                    [sys.executable, worker_path, str(ndata),
                     str(niter)], env=env_i))

            # Watch /status while the job runs: the gate's evidence is
            # the controller's own decision records.
            switch = None          # the final switch decision record
            decided = 0            # ANY controller decisions recorded
            last_ctl: dict = {}    # last controller snapshot (diagnosis)
            demoted_seen = False
            deadline = time.monotonic() + 420
            while any(p.poll() is None for p in procs):
                if time.monotonic() > deadline:
                    return fail(r, "job never finished (controller "
                                "wedged the commit boundaries?)",
                                procs, tracker)
                if tracker.poll() is not None:
                    return fail(r, "tracker died mid-run", procs,
                                tracker)
                raw = _scrape(obs_port, "/status")
                if raw:
                    try:
                        jobs = _json.loads(raw).get("jobs") or {}
                    except ValueError:
                        jobs = {}
                    ctl = (jobs.get("default") or {}).get(
                        "controller") or {}
                    if ctl:
                        last_ctl = ctl
                    if "0" in [str(x) for x in ctl.get("demoted") or []]:
                        demoted_seen = True
                    counters = ctl.get("counters") or {}
                    decided = max(decided,
                                  sum(counters.values()) if counters
                                  else len(ctl.get("decisions") or []))
                    for d in ctl.get("decisions") or []:
                        if d.get("kind") == "switch":
                            switch = d
                time.sleep(0.3)
            for i, p in enumerate(procs):
                if p.wait() != 0:
                    return fail(r, f"rank {i} exited {p.returncode}",
                                procs, tracker)
            try:
                code = tracker.wait(timeout=90)
            except subprocess.TimeoutExpired:
                return fail(r, "tracker never exited after the job",
                            procs, tracker)
            if code != 0:
                return fail(r, f"tracker exited {code}", procs, tracker)

            # (a) converged to a measurably faster schedule: the switch
            # decision's challenger cost (rolling mean over merged
            # spans AFTER convergence) beats the pre-switch incumbent.
            # Under --chaos the wire timing is deliberately poisoned
            # (stalls, resets, recovery rounds), so demanding a
            # specific switch would assert on injected noise: the
            # chaos composition instead requires the control plane to
            # keep DECIDING (probes/settles recorded, nothing wedged)
            # while every structural check below still holds.
            if switch is None and args.chaos:
                if not decided:
                    return fail(r, "under chaos the controller never "
                                "recorded a single decision", procs,
                                tracker)
                print(f"[soak] round {r}: chaos round — controller "
                      f"made {decided} decision(s), no switch verdict "
                      "demanded under injected wire noise", flush=True)
            elif switch is None:
                return fail(r, "the controller never switched the "
                            "schedule (no switch decision on /status); "
                            f"last controller state: {last_ctl}",
                            procs, tracker)
            winner = bucket = None
            if switch is not None:
                evd = switch.get("evidence") or {}
                inc, cha = (evd.get("incumbent_sec"),
                            evd.get("challenger_sec"))
                if not (isinstance(inc, (int, float))
                        and isinstance(cha, (int, float)) and cha < inc):
                    return fail(r, f"switch evidence does not show the "
                                f"challenger beating the incumbent: "
                                f"{evd}", procs, tracker)
                winner, bucket = switch.get("sched"), switch.get("bucket")
                print(f"[soak] round {r}: switch {bucket}B -> {winner} "
                      f"({evd.get('incumbent')} {inc * 1e3:.2f}ms -> "
                      f"{cha * 1e3:.2f}ms over {evd.get('samples')})",
                      flush=True)
            # (b) the slowed rank lost its hier leader role.
            if not demoted_seen:
                return fail(r, "the slowed rank 0 was never demoted "
                            "out of leader roles", procs, tracker)
            from rabit_tpu.sched import topo as _topo
            leaders = _topo.group_leaders([0, 0, 1, 1], {0})
            if 0 in leaders or leaders != [1, 2]:
                return fail(r, f"demoted rank 0 still leads: {leaders}",
                            procs, tracker)
            print(f"[soak] round {r}: rank 0 demoted — hier leaders "
                  f"moved to {leaders}", flush=True)
            # (c) bit-exact vs the uninterrupted reference.
            for i in range(world):
                got = out_dir / f"final.{i}"
                if not got.exists() or got.read_bytes() != ref[i]:
                    return fail(r, f"rank {i} final model is NOT "
                                "bit-exact vs the uninterrupted "
                                "reference", procs, tracker)
            # (d) the TuningCache round-trips: the learned winner is on
            # disk and a FRESH auto job starts on it.  (Chaos rounds
            # without a switch verdict have nothing to round-trip.)
            if winner is None:
                print(f"[soak] round {r}: chaos round survived — "
                      "controller live, model bit-exact", flush=True)
                continue
            cache = TuningCache.load(str(tune))
            if cache is None:
                return fail(r, "no usable TuningCache persisted under "
                            "--tune-dir", procs, tracker)
            if cache.pick("allreduce", int(bucket), world) != winner:
                return fail(r, f"TuningCache does not serve the "
                            f"learned winner {winner} for "
                            f"{bucket}B/world {world}", procs, tracker)
            warm_obs = rdir / "warm_obs"
            code = launch(world, [sys.executable, worker_path,
                                  str(ndata), "3"],
                          extra_env={"RABIT_ENGINE": "pyrobust",
                                     "RABIT_SCHED": "auto",
                                     "RABIT_TUNE_DIR": str(tune),
                                     "RABIT_OUT_DIR": str(rdir / "wout")},
                          obs_dir=str(warm_obs))
            if code != 0:
                return fail(r, f"fresh warm-start job exited {code}",
                            procs, tracker)
            try:
                rep = _json.loads(
                    (warm_obs / "obs_report.json").read_text())
            except (OSError, ValueError) as e:
                return fail(r, f"warm-start obs report unreadable: {e}",
                            procs, tracker)
            picks = (rep.get("aggregate") or {}).get(
                f"sched.pick.{winner}") or {}
            if not picks.get("max", 0) > 0:
                return fail(r, f"the fresh auto job never dispatched "
                            f"the learned schedule {winner} "
                            f"(sched.pick counters: "
                            f"{sorted(k for k in rep.get('aggregate', {}) if k.startswith('sched.pick.'))})",
                            procs, tracker)
            print(f"[soak] round {r}: TuningCache round-trip OK — a "
                  f"fresh rabit_sched=auto job started on {winner}",
                  flush=True)
        print(f"[soak] {args.rounds} adaptive rounds passed", flush=True)
        return 0
    finally:
        if saved_groups is None:
            os.environ.pop("RABIT_TRACKER_GROUPS", None)
        else:
            os.environ["RABIT_TRACKER_GROUPS"] = saved_groups
        shutil.rmtree(base, ignore_errors=True)


def run_serve(args, rng: random.Random, round_obs_dir) -> int:
    """The serving-plane gate (--serve; doc/serving.md).  Each round
    drives one fleet through the four production failure shapes:

    1. **Steady load** at half the fleet's (pinned, via the slow-ms
       capacity seam) capacity: everything served, every reply
       bit-consistent with the committed model version it names —
       including a mid-phase **version rollover** (a new version is
       committed to the store; every rank must atomically swap to it
       via the control loop's agreement broadcast).
    2. **2x-capacity open-loop spike**: the service must SHED with
       typed Overloaded replies (retry-after set) instead of queue-
       collapsing — served-request p99 stays within 5x the steady p99
       (structurally enforced by the deadline budget + shed-before-
       compute), the accounting identity holds exactly, zero wrong
       answers.
    3. **SIGKILL a serving rank mid-traffic**: the availability dip is
       bounded (most requests still served), the fleet recovers via an
       elastic epoch (asserted from the supervisor's event log), and
       every served answer remains bit-consistent.
    4. **Train-while-serving**: a co-tenant training job runs on the
       SAME tracker under live traffic and must finish bit-exact vs a
       solo run on a dedicated tracker (the PR 8 isolation contract,
       now with a serving workload as the neighbor).
    """
    import json as _json
    import shutil
    import signal as _signal
    import subprocess
    import tempfile
    import threading
    import time

    import numpy as np

    from rabit_tpu import ckpt as ckpt_mod
    from rabit_tpu.tools.loadgen import run_load
    from rabit_tpu.tracker.launch_local import launch
    from rabit_tpu.utils.serial import serialize_model

    base = pathlib.Path(tempfile.mkdtemp(prefix="rabit_serve_soak_"))
    worker_path = args.worker_path or str(
        _REPO_ROOT / "tests" / "workers" / "cold_restart.py")
    fleet = 2
    # Low ABSOLUTE rates on purpose: the open-loop generator runs
    # in-process on the same (often 2-core) box as the fleet, and the
    # gate's claims are about RATIOS (0.5x vs 2x capacity, p99 vs
    # steady p99) — rates the client cannot honestly offer would turn
    # "the server sheds" into "the client throttled" and prove
    # nothing.  25 ms/request × batch 4 = 40 req/s per rank.
    slow_ms = 25.0
    batch_max = 4
    max_workers = 3
    capacity = fleet * 1000.0 / slow_ms
    # The spike overloads the fleet's MAXIMUM capacity (autoscale may
    # legitimately grow the world to max_workers before or during the
    # spike — the overload factor must survive that, or the gate would
    # race its own autoscaler).
    capacity_max = max_workers * 1000.0 / slow_ms
    # Small per-rank queue bound: the queue-full shed engages within
    # ~queue_max/excess-rate seconds of sustained overload, and caps a
    # served request's queue wait at queue_max/capacity regardless of
    # how generous its deadline is.
    queue_max = 16
    # One FULL batch's compute time: the irreducible service quantum a
    # served request can pay on top of its deadline (it enters a batch
    # just before its budget dies, then the batch computes).  The p99
    # baseline is floored at TWO quanta: a served spike request costs
    # up to deadline + one batch + scheduling slack, all of which
    # quantize against the batch time — a baseline below two quanta
    # reads a quiet box's idle-path luck, and 5x of luck is not a
    # bound the service's own granularity can honor.
    batch_service = batch_max * slow_ms / 1000.0
    dim = 16

    def _teardown(procs) -> None:
        """SIGTERM first (the supervisor's handler drains its serving
        ranks — a bare kill would orphan them holding the log pipe),
        then kill whatever is left."""
        for p in procs:
            if p is not None and p.poll() is None:
                try:
                    p.terminate()
                except OSError:
                    pass
        deadline = time.monotonic() + 15
        for p in procs:
            if p is None:
                continue
            try:
                p.wait(timeout=max(deadline - time.monotonic(), 0.1))
            except subprocess.TimeoutExpired:
                p.kill()

    def fail(r: int, why: str, procs=(), extra: dict | None = None
             ) -> int:
        print(f"[soak] FAILED (round {r}): {why}", flush=True)
        if extra:
            print(f"[soak]   detail: {_json.dumps(extra, default=str)}",
                  flush=True)
        _teardown(procs)
        return 1

    procs: list = []
    try:
        for r in range(args.rounds):
            rdir = base / f"round{r}"
            model_dir = rdir / "model"
            eps_dir = rdir / "eps"
            state_json = rdir / "supervisor.json"
            rdir.mkdir(parents=True)
            rng_w = np.random.default_rng(args.seed * 7919 + r)
            store = ckpt_mod.CheckpointStore(str(model_dir), rank=0)
            w1 = rng_w.standard_normal(dim)
            store.persist(1, fleet, serialize_model({"w": w1}))

            port = _free_port()
            obs_port = _free_port()
            tracker_cmd = [sys.executable, "-m",
                           "rabit_tpu.tracker.tracker", "-n", str(fleet),
                           "--host", "127.0.0.1", "--port", str(port),
                           "--min-workers", "1",
                           "--max-workers", str(max_workers),
                           "--max-jobs", "4", "--obs-port",
                           str(obs_port)]
            obs = round_obs_dir(r)
            if obs:
                tracker_cmd += ["--obs-dir", obs]
            tracker = subprocess.Popen(tracker_cmd)
            procs = [tracker]
            if not _wait_port(port):
                return fail(r, "tracker never came up", procs)

            sup_cmd = [sys.executable, "-m", "rabit_tpu.tools.serve",
                       "--tracker", f"127.0.0.1:{port}",
                       "--model-dir", str(model_dir),
                       "--endpoints-dir", str(eps_dir),
                       "--workers", str(fleet),
                       "--min-workers", "1",
                       "--max-workers", str(max_workers),
                       "--slow-ms", str(slow_ms),
                       "--sync-sec", "0.5", "--tick-sec", "0.5",
                       "--batch-max", str(batch_max),
                       "--queue-max", str(queue_max),
                       "--state-json", str(state_json),
                       "--max-restarts", "2",
                       "--stop-file", str(rdir / "STOP")]
            sup_env = dict(os.environ)
            if obs:
                sup_env["RABIT_OBS_DIR"] = obs
            sup = subprocess.Popen(sup_cmd, env=sup_env)
            procs.append(sup)
            deadline = time.monotonic() + 90
            while time.monotonic() < deadline:
                try:
                    if len([p for p in eps_dir.iterdir()
                            if p.suffix == ".json"]) >= fleet:
                        break
                except OSError:
                    pass
                if sup.poll() is not None:
                    return fail(r, f"supervisor exited "
                                f"{sup.returncode} during startup",
                                procs)
                time.sleep(0.3)
            else:
                return fail(r, "serving fleet never published its "
                            "endpoints", procs)
            print(f"[soak] round {r}: fleet of {fleet} up "
                  f"(capacity {capacity:.0f} req/s; live plane on "
                  f":{obs_port})", flush=True)

            # -- phase 1: steady load at 0.5x capacity ----------------
            steady = run_load(str(eps_dir), None,
                              rate=capacity * 0.5, duration=6,
                              deadline_ms=2000, dim=dim,
                              seed=args.seed, verify_dir=str(model_dir))
            if not steady["accounting_ok"]:
                return fail(r, "steady-phase accounting mismatch",
                            procs, steady)
            if steady["wrong"]:
                return fail(r, f"{steady['wrong']} bitwise-WRONG "
                            "answers under steady load", procs, steady)
            if steady["ok"] < 0.9 * steady["offered"]:
                return fail(r, "steady load not served "
                            f"({steady['ok']}/{steady['offered']} ok)",
                            procs, steady)
            p99_steady = max(steady["latency_ok_sec"]["p99"],
                             2 * batch_service)
            print(f"[soak] round {r}: steady OK "
                  f"{steady['ok']}/{steady['offered']} served, "
                  f"p99 {p99_steady * 1e3:.1f}ms", flush=True)

            # -- version rollover under live reading ------------------
            w2 = rng_w.standard_normal(dim)
            store.persist(2, fleet, serialize_model({"w": w2}))
            roll = run_load(str(eps_dir), None, rate=40, duration=4,
                            deadline_ms=2000, dim=dim,
                            seed=args.seed + 1,
                            verify_dir=str(model_dir))
            if roll["wrong"]:
                return fail(r, "wrong answers during the version "
                            "rollover (old/new weights crossed a "
                            "version tag)", procs, roll)
            v2 = run_load(str(eps_dir), None, rate=20, duration=2,
                          deadline_ms=2000, dim=dim,
                          seed=args.seed + 2,
                          verify_dir=str(model_dir))
            if v2["wrong"] or not v2["statuses"].get("ok"):
                return fail(r, "post-rollover traffic not served "
                            "cleanly", procs, v2)
            # The spike's p99 baseline must be CONTEMPORANEOUS: phases
            # run minutes apart and a shared box's background load
            # drifts — fold the rollover-check loads (the closest in
            # time to the spike) into the steady baseline.
            p99_steady = max(p99_steady,
                             roll["latency_ok_sec"]["p99"],
                             v2["latency_ok_sec"]["p99"])
            print(f"[soak] round {r}: version rollover v1 -> v2 served "
                  "bit-consistently (every reply verified against the "
                  "version it named)", flush=True)

            # -- phase 2: 2x-capacity overload spike ------------------
            # The deadline budget is what BOUNDS served latency under
            # overload (shed-before-compute): a served request pays at
            # most its deadline in queue plus one full batch of
            # compute plus scheduling slack.  With the baseline
            # floored at 2*batch_service, a 2x-baseline deadline
            # leaves the 5x acceptance bound structural headroom of
            # 3*p99_base - batch_service (>= 5 batch quanta of slack).
            spike_deadline_ms = max(int(2.0 * p99_steady * 1000), 80)
            # outstanding=64: enough in-flight slots to OFFER 2x the
            # max fleet capacity (240/s x ~0.25s roundtrips), small
            # enough that the client's sender threads don't starve the
            # co-located servers of the 2-core box's GIL time — a
            # starved server's latency lives in the kernel socket
            # buffers where no admission gate can see it, which is box
            # contention, not the queue collapse this phase tests for.
            spike = run_load(str(eps_dir), None,
                             rate=capacity_max * 2, duration=6,
                             deadline_ms=spike_deadline_ms, dim=dim,
                             seed=args.seed + 3, outstanding=64,
                             verify_dir=str(model_dir))
            if not spike["accounting_ok"]:
                return fail(r, "spike accounting mismatch: served + "
                            "shed + timeout + error != offered",
                            procs, spike)
            if spike["wrong"]:
                return fail(r, f"{spike['wrong']} bitwise-WRONG "
                            "answers under overload", procs, spike)
            if not spike["shed"]:
                return fail(r, "a 2x-capacity spike produced ZERO "
                            "typed shed replies — where did the "
                            "excess load go?", procs, spike)
            if not spike["retry_after_seen"]:
                return fail(r, "shed replies carried no retry-after "
                            "hint", procs, spike)
            p99_spike = spike["latency_ok_sec"]["p99"]
            if p99_spike > 5 * p99_steady:
                return fail(r, f"served-request p99 under the spike "
                            f"({p99_spike * 1e3:.1f}ms) exceeds 5x "
                            f"the steady p99 ({p99_steady * 1e3:.1f}"
                            "ms) — queue collapse", procs, spike)
            print(f"[soak] round {r}: spike OK — offered "
                  f"{spike['offered']}, served {spike['ok']}, shed "
                  f"{spike['shed']} (typed, retry-after set), served "
                  f"p99 {p99_spike * 1e3:.1f}ms <= 5x steady",
                  flush=True)

            # -- autoscale: the spike's queue depth must GROW the
            # fleet — a supervisor scale_up spawn whose joiner then
            # PUBLISHES its endpoint (publication happens after
            # rabit init, i.e. after the elastic epoch admitted it
            # into the serving world, so this asserts the whole
            # scale-up choreography end to end).
            deadline = time.monotonic() + 60
            scaled_up = False
            while time.monotonic() < deadline and not scaled_up:
                try:
                    evs = _json.loads(
                        state_json.read_text()).get("events", [])
                except (OSError, ValueError):
                    evs = []
                spawned = {e.get("task") for e in evs
                           if e.get("kind") == "spawn"
                           and str(e.get("why", "")).startswith(
                               "queue depth")}
                published = {e.get("task") for e in evs
                             if e.get("kind") == "published"}
                scaled_up = bool(spawned & published)
                if not scaled_up:
                    time.sleep(0.5)
            if not scaled_up:
                return fail(r, "the 2x spike never produced a "
                            "COMPLETED scale-up (no queue-depth-"
                            "spawned joiner published an endpoint — "
                            "did the elastic epoch admit it?)", procs)
            print(f"[soak] round {r}: autoscale landed — a queue-"
                  f"depth joiner joined the serving world "
                  f"({sorted(spawned & published)})", flush=True)

            # -- phase 3: SIGKILL a serving rank mid-traffic ----------
            def _serve_epoch() -> int | None:
                raw = _scrape(obs_port, "/status", timeout=5)
                if not raw:
                    return None
                try:
                    jobs = _json.loads(raw).get("jobs") or {}
                    return int((jobs.get("serve") or {}).get("epoch"))
                except (ValueError, TypeError):
                    return None

            # Snapshot BEFORE the kill: the autoscale phase already
            # moved the epoch, so "epoch is truthy afterwards" would
            # be vacuous — the assertion is that the kill itself
            # moves it again (the scale-down rescale).
            epoch_before = _serve_epoch() or 0
            victims = sorted(eps_dir.glob("*.json"))
            if not victims:
                return fail(r, "no endpoint left to kill", procs)
            victim_doc = _json.loads(victims[0].read_text())
            kill_result: dict = {}

            def _kill_later():
                time.sleep(2.0)
                try:
                    os.kill(int(victim_doc["pid"]), _signal.SIGKILL)
                    kill_result["killed"] = victim_doc["task_id"]
                except OSError as e:
                    kill_result["error"] = str(e)
            killer = threading.Thread(target=_kill_later, daemon=True)
            killer.start()
            under_kill = run_load(str(eps_dir), None,
                                  rate=capacity * 0.4, duration=8,
                                  deadline_ms=2000, dim=dim,
                                  seed=args.seed + 4,
                                  verify_dir=str(model_dir))
            killer.join()
            if "error" in kill_result:
                return fail(r, f"could not SIGKILL the victim: "
                            f"{kill_result['error']}", procs)
            if under_kill["wrong"]:
                return fail(r, "wrong answers while a rank was "
                            "SIGKILLed — replies must stay bit-"
                            "consistent with their version", procs,
                            under_kill)
            if not under_kill["accounting_ok"]:
                return fail(r, "kill-phase accounting mismatch",
                            procs, under_kill)
            if under_kill["ok"] < 0.6 * under_kill["offered"]:
                return fail(r, "availability dip unbounded: only "
                            f"{under_kill['ok']}/"
                            f"{under_kill['offered']} served through "
                            "the rank kill", procs, under_kill)
            # The fleet must have absorbed the death via an elastic
            # epoch: the supervisor logged the death, and the serve
            # job's world moved (or a replacement joined) on /status.
            deadline = time.monotonic() + 20
            died_seen = False
            while time.monotonic() < deadline and not died_seen:
                try:
                    sup_state = _json.loads(state_json.read_text())
                    died_seen = any(e["kind"] in ("died", "left")
                                    and e.get("task")
                                    == kill_result.get("killed")
                                    for e in sup_state.get("events", []))
                except (OSError, ValueError):
                    pass
                time.sleep(0.3)
            if not died_seen:
                return fail(r, "the supervisor never noticed the "
                            "SIGKILLed rank", procs)
            # The kill must move the membership epoch PAST its
            # pre-kill value (heartbeat EOF → scale-down rescale);
            # poll briefly — the boundary lands within ~sync_sec.
            deadline = time.monotonic() + 30
            epoch_after = epoch_before
            while time.monotonic() < deadline:
                e = _serve_epoch()
                if e is not None:
                    epoch_after = e
                    if e > epoch_before:
                        break
                time.sleep(0.5)
            if epoch_after <= epoch_before:
                return fail(r, f"the serve job's membership epoch "
                            f"never moved after the rank kill "
                            f"({epoch_before} -> {epoch_after}; no "
                            "elastic recovery)", procs)
            post = run_load(str(eps_dir), None, rate=30, duration=3,
                            deadline_ms=2000, dim=dim,
                            seed=args.seed + 5,
                            verify_dir=str(model_dir))
            if post["wrong"] or post["ok"] < 0.8 * post["offered"]:
                return fail(r, "service did not recover cleanly after "
                            "the rank kill", procs, post)
            print(f"[soak] round {r}: rank "
                  f"{kill_result.get('killed')} SIGKILLed mid-traffic "
                  f"— {under_kill['ok']}/{under_kill['offered']} "
                  f"served through the dip, elastic epoch "
                  f"{epoch_after} absorbed it, recovery clean",
                  flush=True)

            # -- phase 4: train-while-serving (co-tenant) -------------
            ndata, niter = 4000, 6
            solo_out = rdir / "solo"
            code = launch(2, [sys.executable, worker_path, str(ndata),
                              str(niter)],
                          extra_env={"RABIT_ENGINE": "pyrobust",
                                     "RABIT_OUT_DIR": str(solo_out)})
            if code != 0:
                return fail(r, f"solo trainer reference exited {code}",
                            procs)
            train_out = rdir / "train"
            tenv = dict(os.environ)
            tenv.update({
                "RABIT_TRACKER_URI": "127.0.0.1",
                "RABIT_TRACKER_PORT": str(port),
                "RABIT_WORLD_SIZE": "2",
                "RABIT_ENGINE": "pyrobust",
                "RABIT_JOB_ID": "train",
                "RABIT_OUT_DIR": str(train_out),
            })
            trainers = []
            for i in range(2):
                env_i = dict(tenv)
                env_i["RABIT_TASK_ID"] = f"t{i}"
                trainers.append(subprocess.Popen(
                    [sys.executable, worker_path, str(ndata),
                     str(niter)], env=env_i))
            procs += trainers
            co_load = run_load(str(eps_dir), None, rate=40,
                               duration=6, deadline_ms=2000, dim=dim,
                               seed=args.seed + 6,
                               verify_dir=str(model_dir))
            for i, t in enumerate(trainers):
                try:
                    if t.wait(timeout=120) != 0:
                        return fail(r, f"co-tenant trainer {i} exited "
                                    f"{t.returncode}", procs)
                except subprocess.TimeoutExpired:
                    return fail(r, f"co-tenant trainer {i} hung",
                                procs)
            if co_load["wrong"] or not co_load["statuses"].get("ok"):
                return fail(r, "serving degraded wrongly under the "
                            "co-tenant trainer", procs, co_load)
            for i in range(2):
                ref = (solo_out / f"final.{i}").read_bytes()
                got_p = train_out / f"final.{i}"
                if not got_p.exists() or got_p.read_bytes() != ref:
                    return fail(r, f"train-while-serving rank {i} "
                                "final model NOT bit-exact vs the "
                                "solo reference", procs)
            print(f"[soak] round {r}: train-while-serving co-tenant "
                  "bit-exact vs solo; serving stayed healthy "
                  f"({co_load['ok']}/{co_load['offered']} ok)",
                  flush=True)

            # -- teardown ---------------------------------------------
            (rdir / "STOP").touch()
            try:
                if sup.wait(timeout=30) != 0:
                    return fail(r, f"supervisor exited "
                                f"{sup.returncode}", procs)
            except subprocess.TimeoutExpired:
                return fail(r, "supervisor never exited on the stop "
                            "file", procs)
            tracker.kill()
            tracker.wait()
        print(f"[soak] {args.rounds} serving rounds passed", flush=True)
        return 0
    finally:
        _teardown(procs)  # exception paths must not orphan the fleet
        shutil.rmtree(base, ignore_errors=True)


def run_qos(args, rng: random.Random, round_obs_dir) -> int:
    """The tail-tolerance gate (--qos; doc/serving.md "QoS classes",
    "Hedged retries", "Straggler-aware routing").  Each round drives
    one 3-rank fleet — one rank a deliberate 4x straggler via the
    supervisor's per-task slow seam — through five phases:

    1. **Straggler-aware routing**: under routed load (client EWMA +
       the tracker's serve-fold ``rabit_straggler_score``), the slow
       rank's traffic share must fall to <= 70% of its fair share.
    2. **QoS overload**: a 2x-capacity mixed-class spike against
       per-class budgets — gold keeps being served while bronze sheds,
       and the accounting identity closes exactly PER CLASS.
    3. **Hedge storm** (``run_storm``): every idempotency key fired 4x
       back-to-back at one rank — exactly one OK serve per key, every
       suppressed copy a typed Duplicate, cached answers bit-exact.
    4. **Hedged tail run**: aggressive hedging (p50 trigger) across the
       fleet — hedges fire, zero per-endpoint double serves, books
       balanced, zero wrong answers.
    5. **Chaos on the serving wire**: seeded resets/stalls at the
       ``serve_req``/``serve_reply`` sites — every injection paired
       with a client-side detection, books still exact under retries
       (idempotency keys make the retry safe).

    Every phase uses a DISTINCT seed: idempotency keys derive from the
    seed, so reusing one against the same fleet would re-answer phase
    N+1 from phase N's dedup window (correct server behavior, wrong
    test)."""
    import json as _json
    import shutil
    import subprocess
    import tempfile
    import time

    import numpy as np

    from rabit_tpu import ckpt as ckpt_mod
    from rabit_tpu.tools.loadgen import run_load, run_storm
    from rabit_tpu.utils.serial import serialize_model

    base = pathlib.Path(tempfile.mkdtemp(prefix="rabit_qos_soak_"))
    fleet = 3
    # Pinned capacity (the --serve gate's reasoning): 25 ms/request x
    # batch 4 = 40 req/s per healthy rank; the straggler runs 4x
    # slower (100 ms/request = 10 req/s).
    slow_ms = 25.0
    straggler_ms = 100.0
    batch_max = 4
    queue_max = 16
    capacity = (fleet - 1) * 1000.0 / slow_ms + 1000.0 / straggler_ms
    dim = 16

    def _teardown(procs) -> None:
        for p in procs:
            if p is not None and p.poll() is None:
                try:
                    p.terminate()
                except OSError:
                    pass
        deadline = time.monotonic() + 15
        for p in procs:
            if p is None:
                continue
            try:
                p.wait(timeout=max(deadline - time.monotonic(), 0.1))
            except subprocess.TimeoutExpired:
                p.kill()

    def fail(r: int, why: str, procs=(), extra: dict | None = None
             ) -> int:
        print(f"[soak] FAILED (round {r}): {why}", flush=True)
        if extra:
            print(f"[soak]   detail: {_json.dumps(extra, default=str)}",
                  flush=True)
        _teardown(procs)
        return 1

    procs: list = []
    try:
        for r in range(args.rounds):
            rdir = base / f"round{r}"
            model_dir = rdir / "model"
            eps_dir = rdir / "eps"
            state_json = rdir / "supervisor.json"
            rdir.mkdir(parents=True)
            rng_w = np.random.default_rng(args.seed * 6007 + r)
            store = ckpt_mod.CheckpointStore(str(model_dir), rank=0)
            store.persist(1, fleet,
                          serialize_model({"w":
                                           rng_w.standard_normal(dim)}))
            # Distinct per-phase seeds (idempotency keys derive from
            # them; see the docstring).
            sbase = args.seed * 1000 + r * 100

            port = _free_port()
            obs_port = _free_port()
            tracker_cmd = [sys.executable, "-m",
                           "rabit_tpu.tracker.tracker", "-n", str(fleet),
                           "--host", "127.0.0.1", "--port", str(port),
                           "--min-workers", "2",
                           "--max-workers", str(fleet),
                           "--max-jobs", "2",
                           "--obs-port", str(obs_port)]
            obs = round_obs_dir(r)
            if obs:
                tracker_cmd += ["--obs-dir", obs]
            tracker = subprocess.Popen(tracker_cmd)
            procs = [tracker]
            if not _wait_port(port):
                return fail(r, "tracker never came up", procs)

            # s001 is the straggler: spawned first, slowed via the
            # per-task seam.  Tight bronze budget so the overload
            # phase has a class to shed first; gold+silver together
            # still fit the queue.
            sup_cmd = [sys.executable, "-m", "rabit_tpu.tools.serve",
                       "--tracker", f"127.0.0.1:{port}",
                       "--model-dir", str(model_dir),
                       "--endpoints-dir", str(eps_dir),
                       "--workers", str(fleet),
                       "--min-workers", "2",
                       "--max-workers", str(fleet),
                       "--slow-ms", str(slow_ms),
                       "--slow-task-ms", f"s001:{straggler_ms:g}",
                       "--qos-budgets", "gold:10,silver:8,bronze:2",
                       "--sync-sec", "0.5", "--tick-sec", "0.5",
                       "--batch-max", str(batch_max),
                       "--queue-max", str(queue_max),
                       "--state-json", str(state_json),
                       "--max-restarts", "2",
                       "--stop-file", str(rdir / "STOP")]
            sup_env = dict(os.environ)
            if obs:
                sup_env["RABIT_OBS_DIR"] = obs
            sup = subprocess.Popen(sup_cmd, env=sup_env)
            procs.append(sup)
            deadline = time.monotonic() + 90
            while time.monotonic() < deadline:
                try:
                    if len([p for p in eps_dir.iterdir()
                            if p.suffix == ".json"]) >= fleet:
                        break
                except OSError:
                    pass
                if sup.poll() is not None:
                    return fail(r, f"supervisor exited "
                                f"{sup.returncode} during startup",
                                procs)
                time.sleep(0.3)
            else:
                return fail(r, "serving fleet never published its "
                            "endpoints", procs)
            slow_doc = _json.loads(
                (eps_dir / "s001.json").read_text())
            slow_ep = f"{slow_doc['host']}:{slow_doc['port']}"
            fast_doc = _json.loads(
                (eps_dir / "s002.json").read_text())
            fast_ep = f"{fast_doc['host']}:{fast_doc['port']}"
            metrics_url = f"http://127.0.0.1:{obs_port}/metrics"
            print(f"[soak] round {r}: fleet of {fleet} up, straggler "
                  f"s001 at {straggler_ms:g}ms ({slow_ep}); capacity "
                  f"~{capacity:.0f} req/s", flush=True)

            # -- phase 1: straggler-aware routing ---------------------
            routed = run_load(str(eps_dir), None, rate=40, duration=10,
                              deadline_ms=2000, dim=dim,
                              seed=sbase + 1,
                              verify_dir=str(model_dir),
                              route=True, metrics_url=metrics_url)
            if not routed["accounting_ok"] or routed["wrong"]:
                return fail(r, "routing-phase books broken",
                            procs, routed)
            fair = routed["offered"] / fleet
            slow_sent = routed["per_endpoint"].get(
                slow_ep, {}).get("sent", 0)
            if slow_sent > 0.7 * fair:
                return fail(r, f"router left {slow_sent} requests on "
                            f"the straggler (fair share {fair:.0f}; "
                            "wanted <= 70% of fair)", procs, routed)
            if not routed["router"] or not routed["router"]["convicted"]:
                return fail(r, "the straggler was never convicted by "
                            "the router hysteresis", procs, routed)
            print(f"[soak] round {r}: routing OK — straggler got "
                  f"{slow_sent}/{routed['offered']} "
                  f"(fair {fair:.0f}), convicted="
                  f"{routed['router']['convicted']}", flush=True)

            # -- phase 2: QoS-classed overload ------------------------
            spike = run_load(str(eps_dir), None, rate=capacity * 2,
                             duration=6, deadline_ms=1000, dim=dim,
                             seed=sbase + 2, outstanding=64,
                             verify_dir=str(model_dir),
                             qos_mix="gold:0.25,silver:0.35,bronze:0.4",
                             route=True, metrics_url=metrics_url)
            if spike["wrong"]:
                return fail(r, f"{spike['wrong']} bitwise-WRONG "
                            "answers under the QoS spike", procs, spike)
            if not spike["accounting_ok"]:
                return fail(r, "QoS-spike aggregate accounting "
                            "mismatch", procs, spike)
            pc = spike["per_class"]
            for name, cls in pc.items():
                if cls["offered"] and not cls["accounting_ok"]:
                    return fail(r, f"per-class accounting identity "
                                f"broken for {name}", procs, spike)
            gold, bronze = pc["gold"], pc["bronze"]
            gold_frac = gold["ok"] / max(gold["offered"], 1)
            bronze_frac = bronze["ok"] / max(bronze["offered"], 1)
            if bronze["shed"] == 0:
                return fail(r, "a 2x mixed-class spike shed ZERO "
                            "bronze — budgets not engaging",
                            procs, spike)
            if gold_frac < 0.6:
                return fail(r, f"gold served fraction {gold_frac:.2f} "
                            "under the spike — the gold SLO did not "
                            "hold", procs, spike)
            if gold_frac < bronze_frac + 0.15:
                return fail(r, f"gold ({gold_frac:.2f}) not "
                            f"meaningfully better than bronze "
                            f"({bronze_frac:.2f}) under overload — "
                            "classes are not classes", procs, spike)
            page = _scrape(obs_port, "/metrics", timeout=5) or ""
            if "rabit_serve_qos_requests_total{" not in page:
                return fail(r, "tracker exposition never rendered the "
                            "per-class serving series", procs)
            print(f"[soak] round {r}: QoS spike OK — gold "
                  f"{gold_frac:.0%} served, bronze {bronze_frac:.0%} "
                  f"served / {bronze['shed']} shed, per-class books "
                  "exact", flush=True)

            # -- phase 3: forced hedge storm (one rank) ---------------
            storm = run_storm(fast_ep, keys=24, copies=4, dim=dim,
                              seed=sbase + 3,
                              verify_dir=str(model_dir))
            if storm["double_served"]:
                return fail(r, f"{storm['double_served']} keys served "
                            "twice by ONE rank under the hedge storm "
                            "— dedup broken", procs, storm)
            if storm["unserved_keys"]:
                return fail(r, "hedge storm lost keys entirely",
                            procs, storm)
            if not storm["duplicates"]:
                return fail(r, "hedge storm produced zero typed "
                            "Duplicate replies", procs, storm)
            if storm["wrong"]:
                return fail(r, "cached duplicate answers not bit-exact",
                            procs, storm)
            print(f"[soak] round {r}: hedge storm OK — "
                  f"{storm['ok_serves']}/{storm['keys']} keys served "
                  f"exactly once, {storm['duplicates']} duplicates "
                  "typed, cached answers bit-exact", flush=True)

            # -- phase 4: hedged tail run across the fleet ------------
            hedged = run_load(str(eps_dir), None, rate=40, duration=6,
                              deadline_ms=2000, dim=dim,
                              seed=sbase + 4,
                              verify_dir=str(model_dir),
                              hedge_after_pct=50.0, idem=True,
                              route=True, metrics_url=metrics_url)
            if not hedged["hedges"]["fired"]:
                return fail(r, "aggressive hedging fired zero hedges",
                            procs, hedged)
            if hedged["double_served"]:
                return fail(r, f"{hedged['double_served']} per-"
                            "endpoint double serves under hedging",
                            procs, hedged)
            if not hedged["accounting_ok"] or hedged["wrong"]:
                return fail(r, "hedged-phase books broken",
                            procs, hedged)
            print(f"[soak] round {r}: hedged run OK — "
                  f"{hedged['hedges']['fired']} hedges, "
                  f"{hedged['hedges']['wins']} wins, "
                  f"{hedged['hedges']['cross_rank_serves']} cross-rank "
                  "serves, zero double serves", flush=True)

            # -- phase 5: chaos on the serving wire -------------------
            chaos_spec = (f"{args.seed + 7 + r}:"
                          "reset@serve_req=0.04;reset@serve_reply=0.03;"
                          "stall@serve_reply=0.04;stallms=60")
            chaotic = run_load(str(eps_dir), None, rate=40, duration=6,
                               deadline_ms=2000, dim=dim,
                               seed=sbase + 5,
                               verify_dir=str(model_dir),
                               idem=True, chaos_spec=chaos_spec)
            books = chaotic["chaos"] or {}
            injected = books.get("injected") or {}
            detected = books.get("detected") or {}
            if not injected:
                return fail(r, "the seeded serving-wire chaos plan "
                            "never fired", procs, chaotic)
            if injected != detected:
                return fail(r, "chaos injected/detected books diverge "
                            f"({injected} vs {detected})",
                            procs, chaotic)
            if not chaotic["accounting_ok"] or chaotic["wrong"]:
                return fail(r, "chaos-phase books broken",
                            procs, chaotic)
            print(f"[soak] round {r}: serving-wire chaos OK — "
                  f"{sum(injected.values())} injections, every one "
                  "detected, books exact, zero wrong", flush=True)

            # -- teardown ---------------------------------------------
            (rdir / "STOP").touch()
            try:
                if sup.wait(timeout=30) != 0:
                    return fail(r, f"supervisor exited "
                                f"{sup.returncode}", procs)
            except subprocess.TimeoutExpired:
                return fail(r, "supervisor never exited on the stop "
                            "file", procs)
            tracker.kill()
            tracker.wait()
        print(f"[soak] {args.rounds} QoS rounds passed", flush=True)
        return 0
    finally:
        _teardown(procs)
        shutil.rmtree(base, ignore_errors=True)


def run_tenants(args, rng: random.Random, round_obs_dir) -> int:
    """The multi-tenant isolation gate (--tenants N): N jobs share one
    tracker process; tenant A's whole worker set is SIGKILLed
    mid-training and the gate fails on ANY cross-tenant interference —
    tenant B erroring/hanging, a final model that is not bit-exact
    against a solo run on a dedicated tracker, or the shared tracker
    process dying."""
    import shutil
    import subprocess
    import tempfile
    import time

    from rabit_tpu.tracker.launch_local import launch

    world = 2                     # per-tenant world (N*world workers)
    worker_path = args.worker_path or str(
        _REPO_ROOT / "tests" / "workers" / "cold_restart.py")
    base = pathlib.Path(tempfile.mkdtemp(prefix="rabit_tenant_soak_"))

    def fail(r: int, why: str, procs, tracker) -> int:
        print(f"[soak] FAILED (round {r}): {why}", flush=True)
        for p in procs:
            if p.poll() is None:
                p.kill()
        if tracker is not None and tracker.poll() is None:
            tracker.kill()
        return 1

    try:
        # Solo reference: tenant B's exact workload on a dedicated
        # tracker — the bits tenant B must reproduce next to a dying
        # co-tenant.
        ref_out = base / "ref"
        code = launch(world, [sys.executable, worker_path,
                              str(args.ndata), str(args.niter)],
                      extra_env={"RABIT_ENGINE": "pyrobust",
                                 "RABIT_OUT_DIR": str(ref_out)})
        if code != 0:
            print(f"[soak] FAILED: solo reference run exited {code}",
                  flush=True)
            return 1
        ref = {i: (ref_out / f"final.{i}").read_bytes()
               for i in range(world)}

        for r in range(args.rounds):
            rdir = base / f"round{r}"
            state = rdir / "state"
            state.mkdir(parents=True)
            obs = round_obs_dir(r)
            kill_at = 1 + rng.randrange(max(args.niter - 2, 1))
            chaos = {f"tenant{j}": gen_chaos(rng, "pyrobust")
                     for j in range(args.tenants)} if args.chaos else {}
            port = _free_port()
            obs_port = _free_port()
            print(f"[soak] round {r}: {args.tenants} tenants x world "
                  f"{world} on one tracker; massacre tenant0 at "
                  f">=v{kill_at}; live plane on :{obs_port} "
                  "(tenant1 rank 1 deliberately slowed)"
                  + (f" chaos={sorted(chaos.values())}" if chaos else "")
                  + (" elastic" if args.elastic else ""), flush=True)

            tracker_cmd = [sys.executable, "-m",
                           "rabit_tpu.tracker.tracker", "-n", str(world),
                           "--host", "127.0.0.1", "--port", str(port),
                           "--state-dir", str(state),
                           "--max-jobs", str(args.tenants),
                           "--job-gc-sec", "4",
                           "--obs-port", str(obs_port)]
            if args.elastic:
                tracker_cmd += ["--min-workers", "1",
                                "--max-workers", str(world + 2)]
            if args.adapt:
                # Composition: the adaptive controller runs on the
                # SHARED tracker — adaptation on one tenant must never
                # leak into a co-tenant (the bit-exact check below is
                # the judge).
                tracker_cmd += ["--adapt", "--tune-dir",
                                str(rdir / "tune")]
            if obs:
                tracker_cmd += ["--obs-dir", obs]
            tracker = subprocess.Popen(tracker_cmd)
            procs: list[subprocess.Popen] = []
            by_tenant: dict[str, list[subprocess.Popen]] = {}
            if not _wait_port(port):
                return fail(r, "tracker never came up", procs, tracker)

            for j in range(args.tenants):
                name = f"tenant{j}"
                tdir = rdir / name
                (tdir / "out").mkdir(parents=True)
                env = dict(os.environ)
                env.update({
                    "RABIT_TRACKER_URI": "127.0.0.1",
                    "RABIT_TRACKER_PORT": str(port),
                    "RABIT_JOB_ID": name,
                    "RABIT_WORLD_SIZE": str(world),
                    "RABIT_ENGINE": "pyrobust",
                    "RABIT_OUT_DIR": str(tdir / "out"),
                    "RABIT_CKPT_DIR": str(tdir / "ckpt"),
                    # A SIGKILL'd tenant must EOF its channel for the
                    # orphan GC's evidence; the generous miss budget
                    # avoids false verdicts on a loaded CI box.
                    "RABIT_HEARTBEAT_SEC": "0.3",
                    "RABIT_HEARTBEAT_MISS": "10",
                    # Pacing so the massacre lands mid-training.
                    "RABIT_ITER_SLEEP": "0.2",
                    # Live telemetry plane: every tenant streams delta
                    # frames + collective spans so the mid-run scrape
                    # has per-job labeled data to verify.
                    "RABIT_OBS": "1",
                    "RABIT_OBS_FLUSH_SEC": "0.3",
                })
                if name == "tenant1":
                    # The deliberate straggler: tenant1's rank 1 pads
                    # every iteration — the tracker's span merge must
                    # attribute the slowness to exactly that rank.
                    env["RABIT_SLOW_RANK"] = "1"
                    env["RABIT_SLOW_EXTRA"] = "0.4"
                if args.elastic:
                    env["RABIT_ELASTIC"] = "1"
                if args.adapt:
                    env["RABIT_ADAPT"] = "1"
                if name in chaos:
                    env["RABIT_CHAOS"] = chaos[name]
                    env.setdefault("RABIT_TIMEOUT_SEC", "20")
                    env.setdefault("RABIT_BACKOFF_BASE_MS", "20")
                if obs:
                    env["RABIT_OBS_DIR"] = os.path.join(obs, name)
                by_tenant[name] = []
                for i in range(world):
                    env_i = dict(env)
                    env_i["RABIT_TASK_ID"] = str(i)
                    p = subprocess.Popen(
                        [sys.executable, worker_path, str(args.ndata),
                         str(args.niter)], env=env_i)
                    procs.append(p)
                    by_tenant[name].append(p)

            # Massacre tenant0 once its commits reach the seeded point —
            # and, concurrently, prove the LIVE plane: mid-run, GET
            # /metrics and /status must return correctly job-labeled
            # data for both tenants (the acceptance gate of the
            # streaming-telemetry plane, doc/observability.md).
            victim_ckpt = rdir / "tenant0" / "ckpt"
            deadline = time.monotonic() + 120
            live_why: str | None = "never scraped"
            while True:
                committed = _committed_version(victim_ckpt) >= kill_at
                if live_why is not None:
                    live_why = _live_scrape_ok(obs_port, args.tenants)
                if committed and live_why is None:
                    break
                if time.monotonic() > deadline:
                    if not committed:
                        return fail(r, f"tenant0 never committed "
                                    f"v{kill_at}", procs, tracker)
                    return fail(r, "live scrape never became healthy: "
                                + str(live_why), procs, tracker)
                if tracker.poll() is not None:
                    return fail(r, "tracker died before the massacre",
                                procs, tracker)
                if all(p.poll() is not None for p in by_tenant["tenant0"]):
                    break  # tenant0 already finished: still a valid round
                time.sleep(0.05)
            # tenant0 finishing early must not skip the live-plane
            # verdict: keep polling the scrape against the deadline.
            while live_why is not None and time.monotonic() <= deadline:
                live_why = _live_scrape_ok(obs_port, args.tenants)
                time.sleep(0.2)
            if live_why is not None:
                return fail(r, "live scrape never became healthy: "
                            + str(live_why), procs, tracker)
            print(f"[soak] round {r}: mid-run scrape OK — /metrics and "
                  "/status carry correctly job-labeled live data for "
                  f"all {args.tenants} tenants", flush=True)
            for p in by_tenant["tenant0"]:
                if p.poll() is None:
                    p.kill()
            print(f"[soak] round {r}: tenant0 massacred at "
                  f">=v{_committed_version(victim_ckpt)}", flush=True)
            time.sleep(1.0)
            if tracker.poll() is not None:
                return fail(r, "tracker died with tenant0 (isolation "
                            "breach)", procs, tracker)

            # Every OTHER tenant must finish cleanly — and while they
            # run, the tracker's span merge must flag tenant1's
            # deliberately slowed rank 1 with a straggler verdict
            # (polled via /status; the verdict also lands as a
            # straggler event on the job timeline).  Generous deadline:
            # chaos-forced recovery rounds on a loaded CI box stack up;
            # a genuine cross-tenant wedge still fails loudly well
            # under the outer test timeout.
            import json as _json

            straggler_seen = False
            waiting = {(j, i): p for j in range(1, args.tenants)
                       for i, p in enumerate(by_tenant[f"tenant{j}"])}
            # Same worst-case envelope as the sequential per-worker
            # p.wait(300) this loop replaced: chaos-forced recovery
            # rounds stack PER worker on a loaded box.
            wait_deadline = time.monotonic() + 300 * max(len(waiting), 1)
            while waiting:
                if time.monotonic() > wait_deadline:
                    j, i = next(iter(waiting))
                    return fail(r, f"tenant{j} rank {i} hung after "
                                "the tenant0 massacre", procs, tracker)
                for (j, i), p in list(waiting.items()):
                    code = p.poll()
                    if code is None:
                        continue
                    del waiting[(j, i)]
                    if code != 0:
                        return fail(r, f"tenant{j} rank {i} exited "
                                    f"{code} after the tenant0 "
                                    "massacre", procs, tracker)
                if not straggler_seen:
                    raw = _scrape(obs_port, "/status")
                    if raw:
                        try:
                            jobs = _json.loads(raw).get("jobs") or {}
                        except ValueError:
                            jobs = {}
                        t1 = jobs.get("tenant1") or {}
                        if "1" in (t1.get("stragglers") or {}):
                            straggler_seen = True
                            print(f"[soak] round {r}: straggler verdict "
                                  "fired for tenant1 rank 1 (score "
                                  f"{t1['stragglers']['1']})", flush=True)
                time.sleep(0.2)
            # Grace window: the verdict may land with the final flush
            # frames of tenant1's shutdown, just after the last exit.
            grace = time.monotonic() + 10
            while not straggler_seen and time.monotonic() < grace:
                raw = _scrape(obs_port, "/status")
                if raw:
                    try:
                        t1 = (_json.loads(raw).get("jobs")
                              or {}).get("tenant1") or {}
                    except ValueError:
                        t1 = {}
                    if "1" in (t1.get("stragglers") or {}):
                        straggler_seen = True
                        break
                time.sleep(0.2)
            if not straggler_seen:
                return fail(r, "the deliberately slowed tenant1 rank 1 "
                            "never earned a straggler verdict on "
                            "/status", procs, tracker)
            # ... the tracker must orphan-GC tenant0 and exit cleanly...
            try:
                code = tracker.wait(timeout=90)
            except subprocess.TimeoutExpired:
                return fail(r, "tracker never GC'd the orphaned tenant0 "
                            "job", procs, tracker)
            if code != 0:
                return fail(r, f"tracker exited {code}", procs, tracker)
            # ... and tenant1's model must be bit-exact vs the solo run.
            for i in range(world):
                got = (rdir / "tenant1" / "out" / f"final.{i}")
                if not got.exists():
                    return fail(r, f"tenant1 rank {i} wrote no final "
                                "model", procs, tracker)
                if got.read_bytes() != ref[i]:
                    return fail(r, f"tenant1 rank {i} final model is "
                                "NOT bit-exact vs the solo reference "
                                "(cross-tenant interference)", procs,
                                tracker)
            if obs:
                # The written report must carry the straggler table
                # (rank 1 flagged, per-schedule lateness split) and the
                # per-schedule span latency breakdown, and obs_report
                # must render it.
                from rabit_tpu.tools import obs_report as obs_report_mod

                rp = pathlib.Path(obs) / "tenant1" / "obs_report.json"
                try:
                    rep = _json.loads(rp.read_text())
                except (OSError, ValueError) as e:
                    return fail(r, f"tenant1 obs report unreadable: {e}",
                                procs, tracker)
                stragg = rep.get("straggler") or {}
                if 1 not in (stragg.get("straggling") or []):
                    return fail(r, "tenant1 obs report does not flag "
                                f"rank 1 as straggling: {stragg}",
                                procs, tracker)
                if not rep.get("sched_latency"):
                    return fail(r, "tenant1 obs report has no "
                                "per-schedule span latency", procs,
                                tracker)
                if obs_report_mod.main([str(rp.parent)]) != 0:
                    return fail(r, "obs_report failed to render the "
                                "tenant1 report", procs, tracker)
            print(f"[soak] round {r}: tenant1 bit-exact vs solo run "
                  "(straggler attributed to its slowed rank 1); "
                  "tracker survived and GC'd tenant0", flush=True)
        print(f"[soak] {args.rounds} tenant rounds passed", flush=True)
        return 0
    finally:
        shutil.rmtree(base, ignore_errors=True)


def run_shards(args, rng: random.Random, round_obs_dir) -> int:
    """The sharded-control-plane failover gate (--shards N with
    --tenants M): M co-tenant jobs hash across N tracker shards behind
    the job directory; one job-owning shard is SIGKILLed mid-training
    and its jobs must journal-replay onto a survivor within the
    workers' retry budget — finishing bit-exact vs a solo reference —
    while co-tenants on other shards never stall, the fleet books
    balance hierarchically (admitted == finished + orphan-GC'd summed
    across shards), and mid-run the directory's hierarchical /status
    and /metrics folds attribute every job to its shard (rendered
    through rabit_top).

    Self-healing extensions (doc/fault_tolerance.md "Replicated
    directory & job migration"): --dir-replicas N runs the directory
    as N lease-elected replicas; --dir-kill SIGKILLs the leader
    mid-training (a successor must take the lease and the postmortem
    must name the dead replica from the membership journal);
    --migrate holds one shard back and adds it mid-training — the
    armed shards must live-migrate >=1 RUNNING job to its new ring
    owner (migrated_out == migrated_in, bit-exact finals, balanced
    books)."""
    import io
    import json as _json
    import shutil
    import subprocess
    import tempfile
    import time

    from rabit_tpu.tools import rabit_top
    from rabit_tpu.tracker.directory import DirectoryClient
    from rabit_tpu.tracker.launch_local import launch

    world = 2                     # per-job world (M*world workers)
    worker_path = args.worker_path or str(
        _REPO_ROOT / "tests" / "workers" / "cold_restart.py")
    base = pathlib.Path(tempfile.mkdtemp(prefix="rabit_shard_soak_"))
    all_procs: list[subprocess.Popen] = []

    def down(procs) -> None:
        for p in procs:
            if p is not None and p.poll() is None:
                try:
                    p.terminate()
                except OSError:
                    pass
        deadline = time.monotonic() + 15
        for p in procs:
            if p is None:
                continue
            try:
                p.wait(timeout=max(deadline - time.monotonic(), 0.1))
            except subprocess.TimeoutExpired:
                p.kill()

    def fail(r: int, why: str) -> int:
        print(f"[soak] FAILED (round {r}): {why}", flush=True)
        return 1

    try:
        # Solo reference: each job runs the same deterministic
        # workload, so ONE uninterrupted run on a dedicated tracker is
        # the bits every tenant must reproduce across the shard kill.
        ref_out = base / "ref"
        code = launch(world, [sys.executable, worker_path,
                              str(args.ndata), str(args.niter)],
                      extra_env={"RABIT_ENGINE": "pyrobust",
                                 "RABIT_OUT_DIR": str(ref_out)})
        if code != 0:
            print(f"[soak] FAILED: solo reference run exited {code}",
                  flush=True)
            return 1
        ref = {i: (ref_out / f"final.{i}").read_bytes()
               for i in range(world)}

        names = [f"tenant{j}" for j in range(args.tenants)]
        for r in range(args.rounds):
            rdir = base / f"round{r}"
            state = rdir / "state"
            state.mkdir(parents=True)
            obs = round_obs_dir(r)
            kill_at = 1 + rng.randrange(2)
            chaos = {name: gen_chaos(rng, "pyrobust", link=True)
                     for name in names} if args.chaos else {}

            # -- control plane: directory replica(s) + N shards --------
            n_rep = max(1, args.dir_replicas)
            dports = [_free_port() for _ in range(n_rep)]
            dir_url = ",".join(f"http://127.0.0.1:{p}" for p in dports)
            dir_procs: list[subprocess.Popen] = []
            for di, dp in enumerate(dports):
                cmd = [sys.executable, "-m",
                       "rabit_tpu.tracker.directory",
                       "--host", "127.0.0.1", "--port", str(dp),
                       "--max-jobs", str(args.tenants),
                       "--health-sec", "0.5", "--health-miss", "4"]
                if n_rep > 1:
                    # Replicated: deterministic lease (lowest healthy
                    # id leads); each replica journals membership into
                    # the shared state dir — the postmortem coordinate
                    # for --dir-kill.
                    cmd += ["--replica-index", str(di),
                            "--peers", dir_url,
                            "--lease-sec", "0.3", "--lease-miss", "3",
                            "--state-dir", str(state)]
                p = subprocess.Popen(cmd)
                all_procs.append(p)
                dir_procs.append(p)
            for dp in dports:
                if not _wait_port(dp):
                    return fail(r, "directory replica never came up")
            dead_dirs: set[int] = set()   # SIGKILLed by design

            def dir_down_why() -> str | None:
                for di, p in enumerate(dir_procs):
                    if di not in dead_dirs and p.poll() is not None:
                        return (f"directory replica {di} died "
                                "unexpectedly")
                return None

            def scrape_dir(path: str) -> str | None:
                for di, dp in enumerate(dports):
                    if di in dead_dirs:
                        continue
                    raw = _scrape(dp, path)
                    if raw is not None:
                        return raw
                return None

            shard_procs: dict[int, subprocess.Popen] = {}
            killed_shards: set[int] = set()
            # The directory link sites (dir_register/dir_poll/
            # dir_resolve) fire in the SHARD's DirectoryClient — the
            # detectors (counted register retries, poll-outage
            # episodes, ride-the-cache) live there, so their chaos
            # plan rides the shard env, not the workers'.
            shard_chaos = None
            if args.chaos:
                shard_chaos = (f"{rng.randrange(1 << 30)}:"
                               "reset@dir_register=0.5*2;"
                               "reset@dir_poll=0.15*3;"
                               "stall@dir_resolve=0.25*3;stallms=40")

            def start_shard(i: int) -> bool:
                port, oport = _free_port(), _free_port()
                cmd = [sys.executable, "-m", "rabit_tpu.tracker.tracker",
                       "-n", str(world), "--host", "127.0.0.1",
                       "--port", str(port), "--shard-index", str(i),
                       "--directory", dir_url,
                       "--state-dir", str(state),
                       "--job-gc-sec", "4", "--obs-port", str(oport)]
                if args.migrate:
                    cmd += ["--migrate-after-sec", "0.5",
                            "--migrate-max", "2"]
                if obs:
                    cmd += ["--obs-dir", os.path.join(obs, f"shard{i}")]
                senv = dict(os.environ)
                if shard_chaos:
                    senv["RABIT_CHAOS"] = shard_chaos
                p = subprocess.Popen(cmd, env=senv)
                all_procs.append(p)
                shard_procs[i] = p
                return _wait_port(port)

            # --migrate holds the last shard back: it joins mid-training
            # as the scale-up that makes running jobs misowned.
            n_start = args.shards - 1 if args.migrate else args.shards
            for i in range(n_start):
                if not start_shard(i):
                    return fail(r, f"shard {i} never came up")
            dc = DirectoryClient(dir_url)
            deadline = time.monotonic() + 20
            while True:
                try:
                    snap = dc.refresh()
                except (OSError, ValueError):
                    snap = {"shards": []}
                if len(snap.get("shards", ())) >= n_start:
                    break
                if time.monotonic() > deadline:
                    return fail(r, "shards never all registered with "
                                "the directory")
                time.sleep(0.1)

            owner_of = {}
            by_shard: dict[int, list[str]] = {}
            for name in names:
                own = dc.owner(name)
                if own is None:
                    return fail(r, f"directory has no owner for {name!r}")
                owner_of[name] = own
                by_shard.setdefault(own[0], []).append(name)
            if n_start > 1 and len(by_shard) < 2:
                return fail(r, "degenerate hash spread (every job on "
                            f"one shard): {by_shard}")
            # With --migrate the victim shard is only the commit-point
            # trigger (nothing is killed); otherwise it is SIGKILLed.
            victim = rng.choice(sorted(by_shard))
            action = ("scale-up + live migration"
                      if args.migrate else f"SIGKILL shard {victim}")
            print(f"[soak] round {r}: {args.tenants} jobs x world "
                  f"{world} over {n_start} shards "
                  + " ".join(f"shard{i}={by_shard.get(i, [])}"
                             for i in range(n_start))
                  + f"; {action} at >=v{kill_at}"
                  + (f"; {n_rep} directory replicas" if n_rep > 1
                     else "")
                  + ("; leader SIGKILL" if args.dir_kill else "")
                  + (" chaos(+tracker-link)" if chaos else ""),
                  flush=True)

            # -- workers ------------------------------------------------
            workers: list[subprocess.Popen] = []
            by_job: dict[str, list[subprocess.Popen]] = {}
            for name in names:
                idx, shost, sport = owner_of[name]
                tdir = rdir / name
                (tdir / "out").mkdir(parents=True)
                env = dict(os.environ)
                env.update({
                    "RABIT_TRACKER_URI": shost,
                    "RABIT_TRACKER_PORT": str(sport),
                    # The failover coordinate: a dead shard turns into
                    # a directory re-resolve, not a lost job.
                    "RABIT_DIRECTORY": dir_url,
                    "RABIT_JOB_ID": name,
                    "RABIT_WORLD_SIZE": str(world),
                    "RABIT_ENGINE": "pyrobust",
                    "RABIT_OUT_DIR": str(tdir / "out"),
                    "RABIT_CKPT_DIR": str(tdir / "ckpt"),
                    "RABIT_HEARTBEAT_SEC": "0.3",
                    "RABIT_HEARTBEAT_MISS": "10",
                    # Pacing so the shard kill (or the scale-up's
                    # migration window) lands mid-training.
                    "RABIT_ITER_SLEEP": "1.0" if args.migrate
                                        else "0.3",
                    # Redial budget across the failover window:
                    # health-removal (~2 s) + the survivor's adoption
                    # tick must fit inside the backoff walk.
                    "RABIT_CONNECT_RETRIES": "16",
                    "RABIT_OBS": "1",
                    "RABIT_OBS_FLUSH_SEC": "0.3",
                })
                if args.migrate:
                    # Elastic epoch polls are the steering wheel: the
                    # source's tombstone answers them with a forced
                    # epoch bump, driving workers through the rescale
                    # re-registration that redirects to the new owner.
                    env["RABIT_ELASTIC"] = "1"
                if name in chaos:
                    env["RABIT_CHAOS"] = chaos[name]
                    env.setdefault("RABIT_TIMEOUT_SEC", "20")
                    env.setdefault("RABIT_BACKOFF_BASE_MS", "20")
                if obs:
                    env["RABIT_OBS_DIR"] = os.path.join(obs, name)
                by_job[name] = []
                for i in range(world):
                    env_i = dict(env)
                    env_i["RABIT_TASK_ID"] = str(i)
                    p = subprocess.Popen(
                        [sys.executable, worker_path, str(args.ndata),
                         str(args.niter)], env=env_i)
                    all_procs.append(p)
                    workers.append(p)
                    by_job[name].append(p)

            # -- mid-run: hierarchical fold + the kill trigger ----------
            def fold_ok() -> str | None:
                raw = scrape_dir("/status")
                met = scrape_dir("/metrics")
                if raw is None or met is None:
                    return "directory /status or /metrics unreachable"
                try:
                    doc = _json.loads(raw)
                except ValueError:
                    return "/status fold is not valid JSON"
                jobs = doc.get("jobs") or {}
                for name in names:
                    row = jobs.get(name)
                    if row is None:
                        return f"/status fold has no job {name!r} yet"
                    if row.get("shard") != owner_of[name][0]:
                        return (f"job {name!r} attributed to shard "
                                f"{row.get('shard')!r}; owner is "
                                f"{owner_of[name][0]}")
                    if f'job="{name}"' not in met:
                        return (f"/metrics fold has no series labeled "
                                f"job={name!r} yet")
                buf = io.StringIO()
                try:
                    rabit_top.render(doc, None, out=buf)
                except Exception as e:  # noqa: BLE001 — verdict, not crash
                    return f"rabit_top failed on the fold: {e}"
                if "shard=" not in buf.getvalue():
                    return "rabit_top render shows no shard attribution"
                return None

            victim_job = by_shard[victim][0]
            victim_ckpt = rdir / victim_job / "ckpt"
            deadline = time.monotonic() + 120
            fold_why: str | None = "never scraped"
            while True:
                committed = _committed_version(victim_ckpt) >= kill_at
                if fold_why is not None:
                    fold_why = fold_ok()
                if committed and fold_why is None:
                    break
                if time.monotonic() > deadline:
                    if fold_why is not None:
                        return fail(r, "hierarchical fold never became "
                                    "healthy: " + str(fold_why))
                    return fail(r, f"{victim_job} never committed "
                                f"v{kill_at}")
                why = dir_down_why()
                if why:
                    return fail(r, why)
                for i, p in shard_procs.items():
                    if p.poll() is not None:
                        return fail(r, f"shard {i} died before the "
                                    "seeded kill")
                if all(p.poll() is not None for p in by_job[victim_job]):
                    return fail(r, f"{victim_job} finished before the "
                                "kill point — nothing to hand off")
                time.sleep(0.05)
            print(f"[soak] round {r}: mid-run fold OK — directory "
                  "/status + /metrics attribute all "
                  f"{args.tenants} jobs to their shards (rabit_top "
                  "renders shard columns)", flush=True)
            leader_killed: int | None = None
            if args.dir_kill:
                # SIGKILL the leader replica (lowest live id): the
                # successor must claim the lease within the window and
                # keep serving at a strictly HIGHER generation.
                leader_killed = min(di for di in range(n_rep)
                                    if di not in dead_dirs)
                dir_procs[leader_killed].kill()
                dead_dirs.add(leader_killed)
                print(f"[soak] round {r}: directory leader replica "
                      f"{leader_killed} SIGKILLed mid-training "
                      "(successor must take the lease)", flush=True)
                fo_deadline = time.monotonic() + 30
                new_leader = None
                while new_leader is None:
                    for di, dp in enumerate(dports):
                        if di in dead_dirs:
                            continue
                        raw = _scrape(dp, "/replica")
                        if raw is None:
                            continue
                        try:
                            doc = _json.loads(raw)
                        except ValueError:
                            continue
                        if doc.get("leader"):
                            new_leader = di
                            break
                    if new_leader is not None:
                        break
                    if time.monotonic() > fo_deadline:
                        return fail(r, "no surviving replica took the "
                                    "lease after SIGKILLing replica "
                                    f"{leader_killed}")
                    why = dir_down_why()
                    if why:
                        return fail(r, why)
                    time.sleep(0.1)
                print(f"[soak] round {r}: replica {new_leader} leads "
                      "after the kill (fenced takeover journaled)",
                      flush=True)

            if args.migrate:
                # Scale-up: the held-back shard joins, remapping part
                # of the ring — armed shards must hand >=1 RUNNING job
                # to its new owner at a commit boundary.
                grow = args.shards - 1
                print(f"[soak] round {r}: scale-up — starting shard "
                      f"{grow} (live migration must follow)",
                      flush=True)
                if not start_shard(grow):
                    return fail(r, f"shard {grow} (the scale-up) "
                                "never came up")
                mig_deadline = time.monotonic() + 90
                mig_why = "never scraped"
                while True:
                    raw = scrape_dir("/status")
                    c: dict = {}
                    if raw:
                        try:
                            c = (_json.loads(raw).get("service")
                                 or {}).get("counters") or {}
                        except ValueError:
                            c = {}
                    out_n = c.get("job.migrated_out", 0)
                    in_n = c.get("job.migrated_in", 0)
                    if out_n >= 1 and out_n == in_n:
                        print(f"[soak] round {r}: {out_n} live "
                              "migration(s) committed (migrated_out "
                              "== migrated_in)", flush=True)
                        break
                    mig_why = (f"migrated_out={out_n} "
                               f"migrated_in={in_n}")
                    if time.monotonic() > mig_deadline:
                        return fail(r, "no live migration committed "
                                    "after the scale-up: " + mig_why)
                    why = dir_down_why()
                    if why:
                        return fail(r, why)
                    for i, p in shard_procs.items():
                        if p.poll() is not None:
                            return fail(r, f"shard {i} died during "
                                        "the migration window")
                    time.sleep(0.2)
            else:
                shard_procs[victim].kill()
                killed_shards.add(victim)
                print(f"[soak] round {r}: shard {victim} SIGKILLed at "
                      f">=v{_committed_version(victim_ckpt)} "
                      f"(jobs {by_shard[victim]} must replay onto a "
                      "survivor)", flush=True)

            # -- every worker must finish (handoff + co-tenants) --------
            waiting = {(name, i): p for name in names
                       for i, p in enumerate(by_job[name])}
            wait_deadline = time.monotonic() + 300 * max(len(waiting), 1)
            while waiting:
                if time.monotonic() > wait_deadline:
                    name, i = next(iter(waiting))
                    return fail(r, f"{name} rank {i} hung after the "
                                f"{action}")
                why = dir_down_why()
                if why:
                    return fail(r, why + " after the " + action)
                for i, p in shard_procs.items():
                    if i not in killed_shards and p.poll() is not None:
                        return fail(r, f"surviving shard {i} died "
                                    "(handoff overload?)")
                for (name, i), p in list(waiting.items()):
                    code = p.poll()
                    if code is None:
                        continue
                    del waiting[(name, i)]
                    if code != 0:
                        return fail(r, f"{name} rank {i} exited {code} "
                                    f"after the {action}")
                time.sleep(0.1)

            # -- fleet books: admitted == finished + orphan-GC'd --------
            # job.created counted on survivors + job.restored counted by
            # the adopting shard must equal job.finished + job.orphan_gc
            # across the fold — each job accounted exactly once
            # fleet-wide, none lost, none doubled.
            deadline = time.monotonic() + 30
            books_why: str | None = "never scraped"
            while time.monotonic() < deadline:
                raw = scrape_dir("/status")
                counters: dict = {}
                if raw:
                    try:
                        counters = (_json.loads(raw).get("service")
                                    or {}).get("counters") or {}
                    except ValueError:
                        counters = {}
                admitted = (counters.get("job.created", 0)
                            + counters.get("job.restored", 0))
                closed = (counters.get("job.finished", 0)
                          + counters.get("job.orphan_gc", 0))
                if admitted == closed == args.tenants:
                    books_why = None
                    break
                books_why = (f"admitted={admitted} "
                             f"finished+orphan_gc={closed} "
                             f"(want {args.tenants} == {args.tenants}); "
                             f"counters={counters}")
                time.sleep(0.2)
            if books_why is not None:
                return fail(r, "fleet books never balanced: " + books_why)
            if args.migrate:
                # Migration is a transfer, not an admission: the pair
                # of counters must mirror exactly or a job was double-
                # entered / lost in flight.
                out_n = counters.get("job.migrated_out", 0)
                in_n = counters.get("job.migrated_in", 0)
                if not (out_n >= 1 and out_n == in_n):
                    return fail(r, "migration books skewed at the end: "
                                f"migrated_out={out_n} "
                                f"migrated_in={in_n}")

            # -- postmortem: the membership journal names the corpse ----
            if leader_killed is not None:
                from rabit_tpu.tools import postmortem as _pm
                dj = _pm.load_directory_journals(str(state))
                verdict = _pm.reconstruct([], [], dir_journals=dj)
                named = verdict.get("dead_replicas") or []
                if leader_killed not in named:
                    return fail(r, "postmortem does not name dead "
                                f"replica {leader_killed}: takeovers="
                                f"{verdict.get('directory_takeovers')}")
                print(f"[soak] round {r}: postmortem names dead "
                      f"replica(s) {named} from the membership "
                      "journal", flush=True)

            # -- finals: every job bit-exact vs the solo reference ------
            for name in names:
                for i in range(world):
                    got = rdir / name / "out" / f"final.{i}"
                    if not got.exists():
                        return fail(r, f"{name} rank {i} wrote no final "
                                    "model")
                    if got.read_bytes() != ref[i]:
                        return fail(r, f"{name} rank {i} final model is "
                                    "NOT bit-exact vs the solo "
                                    f"reference across the {action}")
            print(f"[soak] round {r}: all {args.tenants} jobs bit-exact "
                  f"vs solo across the {action}; books balanced "
                  "fleet-wide", flush=True)
            down([p for i, p in shard_procs.items()] + dir_procs)
        print(f"[soak] {args.rounds} shard rounds passed", flush=True)
        return 0
    finally:
        down(all_procs)  # exception paths must not orphan the fleet
        shutil.rmtree(base, ignore_errors=True)


def run_postmortem(args, rng: random.Random, round_obs_dir) -> int:
    """The crash-forensics gate (--postmortem): a world-4 pysocket job
    has one seeded rank SIGKILLed immediately before entering a seeded
    allreduce (an uncatchable death — the victim leaves NO flight
    record).  The survivors' link timeouts escalate to LinkErrors whose
    fault paths persist their always-on flight recorders under
    --trace-dir, the in-process tracker dumps its control-plane journal
    at teardown, and ``tools/postmortem.py`` must then reconstruct the
    incident FROM THE PERSISTED ARTIFACTS ALONE: the first-dead rank
    (the blamed peer that never wrote a record) and the op that was in
    flight (kind/seq matching the seeded kill point)."""
    import shutil
    import tempfile

    from rabit_tpu.obs import load_flight_records
    from rabit_tpu.tools.postmortem import (load_tracker_journals,
                                            reconstruct)
    from rabit_tpu.tracker.launch_local import launch

    world = 4
    niter = max(args.niter, 6)
    worker_path = args.worker_path or str(
        _REPO_ROOT / "tests" / "workers" / "postmortem_victim.py")
    base = pathlib.Path(tempfile.mkdtemp(prefix="rabit_pm_soak_"))
    try:
        for r in range(args.rounds):
            rdir = base / f"round{r}"
            trace_dir = rdir / "trace"
            trace_dir.mkdir(parents=True)
            victim = rng.randrange(world)
            kill_iter = 2 + rng.randrange(max(niter - 3, 1))
            env = {"RABIT_ENGINE": "pysocket",
                   "RABIT_OBS": "1",
                   "RABIT_OBS_FLUSH_SEC": "0.2",
                   # Trace EVERY op: the gate also proves the hop
                   # records kept streaming right up to the death.
                   "RABIT_TRACE_SAMPLE": "1",
                   "RABIT_PM_KILL_RANK": str(victim),
                   "RABIT_PM_KILL_ITER": str(kill_iter),
                   "RABIT_ITER_SLEEP": "0.05"}
            # Fast wedge->LinkError escalation so survivors persist and
            # exit in seconds; a caller's exported value wins.
            if "RABIT_TIMEOUT_SEC" not in os.environ:
                env["RABIT_TIMEOUT_SEC"] = "5"
            print(f"[soak] round {r}: postmortem — SIGKILL rank "
                  f"{victim} before allreduce #{kill_iter} "
                  f"(world {world}, {niter} iters)", flush=True)
            code = launch(
                world, [sys.executable, worker_path,
                        str(args.ndata), str(niter)],
                extra_env=env, trace_dir=str(trace_dir),
                obs_dir=round_obs_dir(r))
            if code == 0:
                print("[soak] FAILED: the job survived the SIGKILL — "
                      "the gate ran vacuously", flush=True)
                return 1
            records = load_flight_records(str(trace_dir))
            journals = load_tracker_journals(str(trace_dir))
            if not records:
                print("[soak] FAILED: no survivor persisted a flight "
                      f"record under {trace_dir}", flush=True)
                return 1
            verdict = reconstruct(records, journals)
            if verdict.get("first_dead") != victim:
                print(f"[soak] FAILED: postmortem blamed rank "
                      f"{verdict.get('first_dead')}, the corpse is rank "
                      f"{victim} (votes={verdict.get('blame_votes')})",
                      flush=True)
                return 1
            op = verdict.get("op_in_flight") or {}
            if op.get("kind") != "allreduce" or op.get("seq") != kill_iter:
                print(f"[soak] FAILED: postmortem named op {op}, the "
                      f"seeded kill point is allreduce #{kill_iter}",
                      flush=True)
                return 1
            print(f"[soak] round {r}: postmortem verdict correct — "
                  f"first dead rank {victim} "
                  f"({len(verdict.get('survivors') or [])} survivor "
                  f"records, votes={verdict.get('blame_votes')}), op in "
                  f"flight allreduce seq={op.get('seq')} "
                  f"epoch={op.get('epoch')} version={op.get('version')}",
                  flush=True)
        print(f"[soak] {args.rounds} postmortem rounds passed",
              flush=True)
        return 0
    finally:
        shutil.rmtree(base, ignore_errors=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--world", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--worker", default="model_recover",
                    choices=["model_recover", "local_recover",
                             "lazy_recover", "xla_restart"])
    ap.add_argument("--engine", default="mock",
                    choices=["mock", "pyrobust", "pysocket"],
                    help="robust engine the kill matrix drives: the "
                         "native C++ mock (default) or the pure-Python "
                         "pyrobust engine (no .so needed; same "
                         "RABIT_MOCK kill-point format); pysocket is "
                         "valid only with --chaos (no recovery — the "
                         "chaos mix is restricted to survivable faults)")
    ap.add_argument("--chaos", action="store_true",
                    help="layer a seeded RABIT_CHAOS wire-fault plan "
                         "(resets/refusals/partial writes/stalls) onto "
                         "each round; python engines only")
    ap.add_argument("--cold-restart", action="store_true",
                    help="kill ALL ranks after a seeded checkpoint "
                         "commit each round, relaunch the world under "
                         "the supervisor, cold-resume from the durable "
                         "tier and verify the final model bit-for-bit "
                         "against an uninterrupted run (pyrobust only)")
    ap.add_argument("--elastic", action="store_true",
                    help="elastic membership gate: grow the world 4->6 "
                         "(late joiners), shrink 6->3 (seeded SIGKILLs "
                         "-> heartbeat scale-down) mid-training with a "
                         "seeded tracker kill+restart (journal replay "
                         "from --state-dir); each rescale segment is "
                         "verified bit-identical against a fresh fixed-"
                         "world job resumed from the same committed "
                         "blob (pyrobust only; mixable with --chaos)")
    ap.add_argument("--tenants", type=int, default=0, metavar="N",
                    help="multi-tenant isolation gate: N concurrent "
                         "jobs against ONE shared tracker (admission "
                         "armed); tenant0's workers are all SIGKILLed "
                         "mid-training and the gate fails on any "
                         "cross-tenant interference — tenant1 must "
                         "finish bit-exact vs a solo run on a "
                         "dedicated tracker and the tracker must "
                         "survive + orphan-GC the dead job (pyrobust; "
                         "mixable with --chaos and --elastic)")
    ap.add_argument("--shards", type=int, default=0, metavar="N",
                    help="sharded-control-plane gate (requires "
                         "--tenants M): the M jobs hash across N "
                         "tracker shards behind the job directory; one "
                         "job-owning shard is SIGKILLed mid-training "
                         "and its jobs must journal-replay onto a "
                         "survivor — bit-exact finals, co-tenants on "
                         "other shards unstalled, fleet books balanced "
                         "through the hierarchical fold (pyrobust; "
                         "mixable with --chaos, which arms the "
                         "tracker-link fault kinds)")
    ap.add_argument("--dir-replicas", type=int, default=1, metavar="N",
                    help="with --shards: run the job directory as N "
                         "lease-elected replicas (lowest healthy id "
                         "leads; followers sync the membership journal "
                         "and redirect writes) — doc/fault_tolerance.md "
                         "'Replicated directory & job migration'")
    ap.add_argument("--dir-kill", action="store_true",
                    help="with --dir-replicas >= 2: SIGKILL the leader "
                         "replica mid-training; a successor must take "
                         "the lease within the window, registrations "
                         "keep flowing at a fenced higher generation, "
                         "and the postmortem must name the dead "
                         "replica from the membership journal")
    ap.add_argument("--migrate", action="store_true",
                    help="with --shards: hold the last shard back and "
                         "add it mid-training (scale-up); shards armed "
                         "with --migrate-after-sec must live-migrate "
                         ">=1 RUNNING job to its new ring owner at a "
                         "commit boundary — migrated_out == "
                         "migrated_in, bit-exact finals, balanced "
                         "fleet books")
    ap.add_argument("--adapt", action="store_true",
                    help="closed-loop adaptive gate: a world-4 job "
                         "with a deliberately slowed rank under a "
                         "tracker with the adaptive controller armed "
                         "must converge to a measurably faster "
                         "schedule than the static pick, demote the "
                         "slow rank from hier leadership, stay "
                         "bit-exact vs an uninterrupted run, and "
                         "round-trip the learned TuningCache "
                         "(pyrobust; mixable with --chaos; with "
                         "--tenants it arms the controller on the "
                         "shared tracker instead)")
    ap.add_argument("--postmortem", action="store_true",
                    help="crash-forensics gate: a world-4 pysocket job "
                         "has a seeded rank SIGKILLed immediately "
                         "before a seeded allreduce; the survivors' "
                         "fault paths persist their flight recorders "
                         "and tools/postmortem.py must name the first-"
                         "dead rank and the in-flight op from the "
                         "persisted artifacts alone "
                         "(doc/observability.md)")
    ap.add_argument("--serve", action="store_true",
                    help="serving-plane gate (doc/serving.md): a "
                         "2-rank fleet with pinned capacity serves "
                         "verified traffic through steady load, a "
                         "mid-read version rollover, a 2x-capacity "
                         "open-loop spike (typed sheds, served p99 "
                         "bounded at 5x steady), a mid-traffic rank "
                         "SIGKILL absorbed by an elastic epoch, and a "
                         "train-while-serving co-tenant run that must "
                         "stay bit-exact vs solo training")
    ap.add_argument("--qos", action="store_true",
                    help="tail-tolerance gate (doc/serving.md): a "
                         "3-rank fleet with one 4x straggler must "
                         "route >= 30% of its traffic share away "
                         "(conviction hysteresis), hold the gold SLO "
                         "through a 2x mixed-class spike while bronze "
                         "sheds (per-class books exact), survive a "
                         "forced hedge storm with zero double serves "
                         "(typed Duplicates, cached answers bit-"
                         "exact), and keep exact books under seeded "
                         "serving-wire chaos")
    ap.add_argument("--max-restarts", type=int, default=4,
                    help="supervisor relaunch budget per worker for "
                         "--cold-restart rounds")
    ap.add_argument("--heartbeat", type=float, default=0.5,
                    help="worker heartbeat period for --cold-restart "
                         "rounds (proactive tracker-side liveness)")
    # None = unset (the shared default 5000 is applied after parsing),
    # so scenarios with their own payload default — --adapt wants the
    # bandwidth-bound 256KB regime — can tell an EXPLICIT --ndata 5000
    # apart from the default.
    ap.add_argument("--ndata", type=int, default=None)
    ap.add_argument("--niter", type=int, default=8)
    ap.add_argument("--kills", type=int, default=6)
    ap.add_argument("--worker-path", default=None,
                    help="explicit path to the worker script (defaults "
                         "to tests/workers/<worker>.py in the repo)")
    ap.add_argument("--obs-dir", default=None,
                    help="enable telemetry: each round writes per-rank "
                         "event traces plus the tracker-aggregated "
                         "obs_report.json under <obs-dir>/round<N> "
                         "(render with python -m "
                         "rabit_tpu.tools.obs_report)")
    args = ap.parse_args(argv)
    args.ndata_explicit = args.ndata is not None
    if args.ndata is None:
        args.ndata = 5000
    if (args.chaos and args.engine == "mock" and not args.cold_restart
            and not args.elastic and not args.tenants
            and not args.adapt):
        ap.error("--chaos drives the Python engines only; pass "
                 "--engine pyrobust (recovery mix) or pysocket "
                 "(survivable mix)")
    if args.engine == "pysocket" and not args.chaos:
        ap.error("--engine pysocket is only meaningful with --chaos "
                 "(it has no recovery protocol for a kill matrix)")
    if args.chaos and args.worker == "xla_restart":
        ap.error("--chaos does not apply to the xla_restart worker")
    if args.cold_restart and args.engine != "pyrobust":
        ap.error("--cold-restart drives the durable tier through the "
                 "pure-Python robust engine; pass --engine pyrobust")
    if args.elastic and not args.tenants:
        if args.engine not in ("mock", "pyrobust"):
            ap.error("--elastic drives the pure-Python robust engine; "
                     "pass --engine pyrobust (or leave the default)")
        if args.cold_restart or args.worker != "model_recover":
            ap.error("--elastic is its own scenario (elastic_worker); "
                     "it does not combine with --cold-restart or "
                     "--worker")
    if args.adapt and not args.tenants:
        if args.engine not in ("mock", "pyrobust"):
            ap.error("--adapt drives the pure-Python robust engine; "
                     "pass --engine pyrobust (or leave the default)")
        if args.cold_restart or args.elastic \
                or args.worker != "model_recover":
            ap.error("--adapt is its own scenario (cold_restart worker "
                     "with a slowed rank); it only combines with "
                     "--chaos (or rides --tenants)")
    if args.postmortem:
        if (args.cold_restart or args.elastic or args.adapt
                or args.tenants
                or args.serve or args.chaos
                or args.worker != "model_recover"):
            ap.error("--postmortem is its own scenario (a seeded "
                     "SIGKILL mid-collective through the pysocket "
                     "engine); it does not combine with the other "
                     "gates")
    if args.serve:
        if args.engine not in ("mock", "pyrobust"):
            ap.error("--serve drives the pure-Python robust engine; "
                     "pass --engine pyrobust (or leave the default)")
        if (args.cold_restart or args.elastic or args.adapt
                or args.tenants
                or args.chaos or args.qos
                or args.worker != "model_recover"):
            ap.error("--serve is its own scenario (serving fleet + "
                     "co-tenant trainer); it does not combine with "
                     "the other gates")
    if args.qos:
        if args.engine not in ("mock", "pyrobust"):
            ap.error("--qos drives the pure-Python robust engine; "
                     "pass --engine pyrobust (or leave the default)")
        if (args.cold_restart or args.elastic or args.adapt
                or args.tenants
                or args.chaos or args.postmortem
                or args.worker != "model_recover"):
            ap.error("--qos is its own scenario (serving fleet with a "
                     "pinned straggler; it seeds its OWN serving-wire "
                     "chaos phase); it does not combine with the "
                     "other gates")
    if args.tenants:
        if args.tenants < 2:
            ap.error("--tenants needs at least 2 jobs to prove "
                     "isolation")
        if args.engine not in ("mock", "pyrobust"):
            ap.error("--tenants drives the pure-Python robust engine; "
                     "pass --engine pyrobust (or leave the default)")
        if args.cold_restart or args.worker != "model_recover":
            ap.error("--tenants is its own scenario (cold_restart "
                     "worker per tenant); it does not combine with "
                     "--cold-restart or --worker")
    if args.shards:
        if args.shards < 2:
            ap.error("--shards needs at least 2 shards for a handoff "
                     "to have a survivor")
        if not args.tenants:
            ap.error("--shards needs --tenants N (the jobs to spread "
                     "across the shard fleet)")
        if args.elastic or args.adapt:
            ap.error("--shards is its own scenario (sharded control "
                     "plane with a shard kill); it only combines with "
                     "--tenants and --chaos")
    if args.dir_replicas < 1:
        ap.error("--dir-replicas needs at least 1 replica")
    if (args.dir_replicas > 1 or args.dir_kill or args.migrate) \
            and not args.shards:
        ap.error("--dir-replicas/--dir-kill/--migrate ride the "
                 "--shards scenario; pass --shards N --tenants M")
    if args.dir_kill and args.dir_replicas < 2:
        ap.error("--dir-kill needs --dir-replicas >= 2 (a failover "
                 "needs a successor)")

    from rabit_tpu.tracker.launch_local import launch

    worker_path = args.worker_path or str(
        _REPO_ROOT / "tests" / "workers" / f"{args.worker}.py")
    rng = random.Random(args.seed)

    def round_obs_dir(r: int) -> str | None:
        if not args.obs_dir:
            return None
        return str(pathlib.Path(args.obs_dir) / f"round{r}")

    if args.postmortem:
        return run_postmortem(args, rng, round_obs_dir)
    if args.qos:
        return run_qos(args, rng, round_obs_dir)
    if args.serve:
        return run_serve(args, rng, round_obs_dir)
    if args.shards:
        return run_shards(args, rng, round_obs_dir)
    if args.tenants:
        return run_tenants(args, rng, round_obs_dir)
    if args.adapt:
        return run_adapt(args, rng, round_obs_dir)
    if args.elastic:
        return run_elastic(args, rng, round_obs_dir)
    if args.cold_restart:
        return run_cold_restart(args, rng, round_obs_dir)

    for r in range(args.rounds):
        if args.worker == "xla_restart":
            # Randomized deaths through the XLA engine's device-plane
            # re-formation: distinct victims at random iterations (the
            # worker's fixed NITER is 4; iters 1-3 leave room to resume,
            # re-form, and verify the post-reform device path).
            # --ndata/--niter/--kills are mock-matrix knobs, inert here.
            if r == 0 and (args.ndata != 5000 or args.niter != 8
                           or args.kills != 6):
                print("[soak] note: --ndata/--niter/--kills do not apply "
                      "to the xla_restart worker (fixed NITER=4, 1-2 "
                      "victims)", flush=True)
            nvictims = min(1 + rng.randrange(2), args.world - 1)
            victims = rng.sample(range(args.world), nvictims)
            plan = ";".join(f"{v}:{1 + rng.randrange(3)}" for v in victims)
            print(f"[soak] round {r}: xla die-plan={plan}", flush=True)
            # --engine maps onto the XLA engine's host control plane:
            # mock -> the native robust inner, pyrobust -> the pure-
            # Python one.  A caller-exported RABIT_INNER still wins.
            inner = "native" if args.engine == "mock" else args.engine
            code = launch(
                args.world, [sys.executable, worker_path],
                extra_env={"RABIT_INNER": os.environ.get("RABIT_INNER",
                                                         inner),
                           "RABIT_XLA_DIE": plan},
                # worlds share one core on the CI box: scale the grace
                # period so jax import/startup isn't mistaken for a hang
                watchdog_sec=max(20, 4 * args.world),
                obs_dir=round_obs_dir(r))
            if code != 0:
                print(f"[soak] FAILED (exit {code}) — reproduce with "
                      f"RABIT_XLA_DIE='{plan}'", flush=True)
                return 1
            continue
        # pysocket has no recovery: chaos rounds on it run kill-free.
        matrix = ("" if args.engine == "pysocket"
                  else gen_matrix(rng, args.world, args.niter, args.kills))
        env = {"RABIT_ENGINE": args.engine}
        if matrix:
            env["RABIT_MOCK"] = matrix
        if args.chaos:
            env["RABIT_CHAOS"] = gen_chaos(rng, args.engine)
            # Fast hung-peer detection so injected stalls/resets turn
            # into recovery rounds in seconds, not the 600 s default;
            # quick backoff keeps the chaos rounds snappy.  A caller's
            # exported value wins (launch() overlays this dict onto
            # os.environ, so defaulting here would clobber it).
            if "RABIT_TIMEOUT_SEC" not in os.environ:
                env["RABIT_TIMEOUT_SEC"] = "20"
            if "RABIT_BACKOFF_BASE_MS" not in os.environ:
                env["RABIT_BACKOFF_BASE_MS"] = "20"
        print(f"[soak] round {r}: engine={args.engine} mock={matrix} "
              f"chaos={env.get('RABIT_CHAOS', '')}", flush=True)
        code = launch(
            args.world,
            [sys.executable, worker_path,
             str(args.ndata), str(args.niter)],
            extra_env=env, obs_dir=round_obs_dir(r))
        if code != 0:
            print(f"[soak] FAILED (exit {code}) — reproduce with "
                  f"RABIT_ENGINE='{args.engine}' RABIT_MOCK='{matrix}' "
                  f"RABIT_CHAOS='{env.get('RABIT_CHAOS', '')}'",
                  flush=True)
            return 1
    print(f"[soak] {args.rounds} rounds passed", flush=True)
    return 0


def cli() -> int:
    """Console-script entry point."""
    return main()


if __name__ == "__main__":
    sys.exit(main())
