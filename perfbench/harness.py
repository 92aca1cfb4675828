"""The harness: one cell of ``BENCHMARK.json``, one run.

Driven by data.  A cell names a configuration and a traffic mix; the
configuration names its learner adapter; a per-layer metric names its
reader.  Each is a file of its own under this directory, found by the
name in ``BENCHMARK.json``, so a later PR adds files and entries and
edits nothing here:

    configs/<config>.json   traffic/<mix>.json   learners/<learner>.py
    layers/<metric>.json|.py   kernels/<kernel>.py   peaks.json

What the harness knows of a job is the public ``rabit_tpu`` surface
every learner of this repository goes through: ``init``, ``checkpoint``
(a version is a commit), ``load_checkpoint``, ``allreduce``, the
engine's ``path_stats``.  Everything of one learner (its data, its
entry point, what it committed, the comparison with its reference, the
spans around its layers) is behind the adapter, whose functions are
listed in ``learners/kmeans.py``.

A run with one rank is one process.  A run with several is a parent
that stays off JAX and sleeps while one child per chip (under the
tracker, ``rabit_tpu.tracker.launch_local``) owns the chips; every rank
writes what it observed into a directory of the run and the parent
reduces that to the result line.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
CHILD_TIMEOUT_S = 1100          # under the 1200 s a first run may take
EXCHANGE_TIMEOUT_S = 300


class Refused(SystemExit):
    """The run cannot produce a result (no chip, a broken cell): exit
    non-zero and print no result line."""

    def __init__(self, why: str):
        print(f"perfbench: {why}", file=sys.stderr, flush=True)
        super().__init__(3)


# ----------------------------------------------------------------------
# the cell, from files
# ----------------------------------------------------------------------
def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> dict:
    manifest = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json "
                      f"(have {sorted(cells)})")
    cell = cells[workload]
    config = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    bench_dir = os.path.join(root, manifest["paths"][0])
    traffic = read_json(os.path.join(
        bench_dir, "traffic", cell["traffic"] + ".json"))
    if int(traffic["world"]) != int(cell["chips"]):
        raise Refused(f"{workload}: traffic world {traffic['world']} != "
                      f"chips {cell['chips']}")
    return {"manifest": manifest, "cell": cell, "bench_dir": bench_dir,
            "cfg": read_json(os.path.join(root, config["file"])),
            "traffic": traffic}


def metrics_of(loaded: dict, group: str) -> list[dict]:
    """The metrics of ``group`` that this cell reports."""
    name = loaded["cell"]["name"]
    return [m for m in loaded["manifest"][group]
            if "workloads" not in m or name in m["workloads"]]


_MODULES: dict = {}


def load_module(path: str):
    """A file of the benchmark that is code (an adapter, a reader, a
    kernel's cost), found by its path and run once a process."""
    path = os.path.abspath(path)
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            "perfbench_dyn_"
            + os.path.basename(path)[:-3].replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def load_learner(loaded: dict):
    """The adapter a configuration names: ``learners/<learner>.py``."""
    path = os.path.join(loaded["bench_dir"], "learners",
                        loaded["cfg"]["learner"] + ".py")
    if not os.path.exists(path):
        raise Refused(f"no adapter {path} for learner "
                      f"{loaded['cfg']['learner']!r}")
    return load_module(path)


# ----------------------------------------------------------------------
# the device
# ----------------------------------------------------------------------
def require_chip(devices, chips: int) -> None:
    """No result off the chip, and none on fewer chips than the cell
    asks for."""
    if devices[0].platform != "tpu":
        raise Refused(f"no accelerator: jax.devices() = {devices}")
    if len(devices) != chips:
        raise Refused(f"the cell needs {chips} chip(s), JAX sees "
                      f"{len(devices)}: {devices}")


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout
    (where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    no other is set in code).  Every program is kept, however quick it
    compiled, so a second run finds them all."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ----------------------------------------------------------------------
# spans
# ----------------------------------------------------------------------
class Spans:
    """Host spans around the calls into each layer, from the
    benchmark's own files.  Kept in memory; with ``annotate`` each is
    also a ``jax.profiler.TraceAnnotation``, so it sits on the device
    trace's clock and an idle gap can be given to the span the host was
    in."""

    PREFIX = "perfbench:"

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.seconds: dict[str, list[float]] = {}
        self.counters: dict[str, int] = {}

    def begin(self, name: str):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(self.PREFIX + name)
            ann.__enter__()
        return (time.perf_counter(), ann)

    def end(self, name: str, token) -> float:
        t0, ann = token
        dt = time.perf_counter() - t0
        if ann is not None:
            ann.__exit__(None, None, None)
        self.seconds.setdefault(name, []).append(dt)
        return dt

    def wrap(self, fn, name: str):
        def spanned(*a, **kw):
            token = self.begin(name)
            try:
                return fn(*a, **kw)
            finally:
                self.end(name, token)
        return spanned


# ----------------------------------------------------------------------
# one rank
# ----------------------------------------------------------------------
class Exchange:
    """The benchmark's own way across ranks, used after the window by
    the reference and the checks: one file per rank and tag in the
    run's directory.  Nothing of the program's collectives."""

    def __init__(self, run_dir: str, rank: int, world: int):
        self.dir, self.rank, self.world = run_dir, rank, world

    def __call__(self, tag: str, array):
        import numpy as np

        if self.world == 1:
            return [np.asarray(array)]
        mine = os.path.join(self.dir, f"x-{tag}-{self.rank}.npy")
        with open(mine + ".tmp", "wb") as f:
            np.save(f, np.asarray(array))
        os.replace(mine + ".tmp", mine)
        deadline = time.monotonic() + EXCHANGE_TIMEOUT_S
        paths = [os.path.join(self.dir, f"x-{tag}-{r}.npy")
                 for r in range(self.world)]
        while not all(os.path.exists(p) for p in paths):
            if time.monotonic() > deadline:
                raise RuntimeError(f"exchange {tag}: a rank never wrote")
            time.sleep(0.005)
        return [np.load(p) for p in paths]


def percentile(values, q: float) -> float:
    """The q-th percentile by the nearest-rank rule on sorted values."""
    s = sorted(values)
    if not s:
        return float("nan")
    return s[min(len(s) - 1, max(0, int(-(-q * len(s) // 100)) - 1))]


def median(values) -> float:
    import statistics

    return statistics.median(values) if values else float("nan")


def run_rank(loaded: dict, seed: int, seconds: float, trace: bool,
             run_dir: str, t_start: float, rows: int | None = None,
             grid: str | None = None) -> dict:
    """Set-up, the window, the checks: what one rank does.  Returns (and
    writes to ``run_dir``) what it observed."""
    import gc
    import resource

    import jax
    import numpy as np

    import rabit_tpu
    from perfbench import trace_reduce
    from perfbench.window import StopWord, VersionClock, WindowClosed
    from rabit_tpu import engine as engine_mod

    phases = {"imports": time.time() - t_start}
    cfg, traffic, cell = loaded["cfg"], loaded["traffic"], loaded["cell"]
    world = int(traffic["world"])
    learner = load_learner(loaded)
    # the data is made on host threads while the device is opened and
    # the process group forms (both mostly wait)
    threads = max(1, min(8, (os.cpu_count() or 1) // world))
    shard = int(os.environ.get("RABIT_TASK_ID", "0")) if world > 1 else 0
    made = {}

    def generate():
        t_gen = time.perf_counter()
        try:
            made["data"] = learner.make_data(cfg, seed, shard, world,
                                             threads, rows, grid)
        except BaseException as e:          # re-raised by the main thread
            made["error"] = e
        made["gen_s"] = time.perf_counter() - t_gen

    generator = threading.Thread(target=generate, name="perfbench-generate",
                                 daemon=True)
    generator.start()
    enable_compile_cache()
    if world == 1:                    # before anything is staged
        require_chip(jax.devices(), 1)
    rabit_tpu.init(list(traffic.get("engine_args", [])),
                   rabit_engine=traffic["engine"])
    rank = rabit_tpu.get_rank()
    if rabit_tpu.get_world_size() != world:
        raise Refused(f"world {rabit_tpu.get_world_size()} != {world}")
    devices = jax.devices()
    require_chip(devices, int(cell["chips"]))
    device = jax.local_devices()[0]
    phases["device_and_init"] = time.time() - t_start

    generator.join()
    if "error" in made:
        raise made["error"]
    data, gen_s = made["data"], made["gen_s"]
    phases["generated"] = time.time() - t_start
    about = learner.describe(cfg, traffic, data)

    spans = Spans(annotate=trace)
    # the adapter's eyes on its learner's layers: what was staged and
    # which kernels were traced in every run, spans in the traced one
    undo = list(learner.watch(data, spans, trace))
    trace_dir = os.path.join(run_dir, f"trace-{rank}")
    if trace:
        for name, span in (("allreduce", "allreduce_call"),
                           ("load_checkpoint", "load_checkpoint")):
            fn = getattr(rabit_tpu, name)
            setattr(rabit_tpu, name, spans.wrap(fn, span))
            undo.append((rabit_tpu, name, fn))

    def start_trace():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    stop_word = StopWord(os.path.join(run_dir, "stop")) if world > 1 else None
    commit = rabit_tpu.checkpoint
    clock = VersionClock(
        spans.wrap(commit, "commit") if trace else commit,
        rabit_tpu.version_number, seconds,
        int(traffic.get("warmup_versions", 2)), rank == 0, stop_word,
        on_open=start_trace if trace else None,
        on_close=jax.profiler.stop_trace if trace else None)
    rabit_tpu.checkpoint = clock
    undo.append((rabit_tpu, "checkpoint", commit))

    eng = engine_mod.get_engine()

    def job():
        try:
            learner.run_job(cfg, traffic, data)
        except WindowClosed:
            return
        raise RuntimeError("the learner returned before the window closed")

    usage0 = resource.getrusage(resource.RUSAGE_SELF)
    try:
        job()
        phases["first_commit"] = clock.first_wall - t_start
        phases["window_open"] = clock.opened_wall - t_start
        phases["window_closed"] = time.time() - t_start
        versions, span_s = clock.versions(), clock.span_s()
        path_stats = dict(getattr(eng, "path_stats", {}) or {})
        memory = device.memory_stats() or {}
        committed_version = rabit_tpu.version_number()
        # what the timed path committed, read back through the public
        # call, before anything else runs
        _version, model = rabit_tpu.load_checkpoint()
        committed = {k: np.array(v) for k, v in
                     learner.committed(model).items()}

        resume_s = None
        if trace:
            # a second run on the committed state, to its first new
            # commit: the resume a killed job pays
            t0 = time.perf_counter()
            resume = VersionClock(commit, rabit_tpu.version_number, 0.0, 1,
                                  rank == 0, None)
            resume.stop_at = committed_version + 1
            rabit_tpu.checkpoint = resume
            job()
            resume_s = resume.stamps[0] - t0
            rabit_tpu.checkpoint = clock
    finally:
        for owner, name, fn in reversed(undo):
            setattr(owner, name, fn)
    usage1 = resource.getrusage(resource.RUSAGE_SELF)

    del model
    gc.collect()                      # the program's arrays are freed

    exchange = Exchange(run_dir, rank, world)
    t_check = time.perf_counter()
    compared = {k: float(v) for k, v in learner.check(
        cfg, traffic, data, committed, exchange).items()}
    reference_s = time.perf_counter() - t_check
    # every rank committed the same bytes
    compared["rank_disagreement"] = max(
        ((float(np.max(np.abs(theirs.astype(np.float64)
                              - mine.astype(np.float64)), initial=0.0))
          if theirs.shape == mine.shape else float("inf"))
         for name, mine in sorted(committed.items())
         for theirs in exchange("committed-" + name, mine)), default=0.0)
    # every commit the job made went through the wrapper, and all of
    # them are in the store
    compared["version_gap"] = float(
        abs(committed_version - len(clock.stamps))
        + abs(_version - committed_version))
    compared["host_ops"] = float(path_stats.get("host_ops", 0))

    phases["checked"] = time.time() - t_start
    reduced = None
    if trace:
        reduced = trace_reduce.reduce_dir(trace_dir, Spans.PREFIX)
        shutil.rmtree(trace_dir, ignore_errors=True)
    rabit_tpu.finalize()
    phases["done"] = time.time() - t_start
    # where this process's set-up went: the kernel's share of the job
    # (page faults on fresh memory are most of it on a cold machine)
    phases["job_sys_s"] = usage1.ru_stime - usage0.ru_stime
    phases["job_user_s"] = usage1.ru_utime - usage0.ru_utime
    phases["job_minor_faults"] = usage1.ru_minflt - usage0.ru_minflt
    print("perfbench phases (s from start) " + json.dumps(
        {"rank": rank, **{k: round(v, 2) for k, v in phases.items()},
         "spans_first": {k: round(v[0], 2) for k, v in spans.seconds.items()},
         **spans.counters, "reference_s": round(reference_s, 2)}),
        file=sys.stderr, flush=True)

    out = {
        "rank": rank,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices),
                   "memory_peak_bytes": int(memory.get(
                       "peak_bytes_in_use", 0))},
        "versions": versions, "span_s": span_s, "failed": clock.failed,
        "work_per_version": about["work_per_version"],
        "kernel_shape": about.get("kernel_shape"),
        "setup_s": clock.opened_wall - t_start,
        "gen_s": gen_s,
        "phases": phases,
        "version_gaps": clock.version_gaps(),
        "spans": spans.seconds,
        "counters": spans.counters,
        "commit_s": clock.commit_seconds(),
        "resume_s": resume_s,
        "path_stats": path_stats,
        "memory": {k: int(v) for k, v in memory.items()
                   if isinstance(v, (int, float))},
        "compared": compared,
        "reference_s": reference_s,
        "trace": reduced,
    }
    with open(os.path.join(run_dir, f"rank-{rank}.json.tmp"), "w") as f:
        json.dump(out, f)
    os.replace(os.path.join(run_dir, f"rank-{rank}.json.tmp"),
               os.path.join(run_dir, f"rank-{rank}.json"))
    return out


# ----------------------------------------------------------------------
# the result line
# ----------------------------------------------------------------------
def judge(cfg: dict, ranks: list[dict]) -> tuple[bool, list[dict]]:
    """Every number compared beside its limit; ``correct`` is that none
    is over (a number that is not there, or not finite, is over)."""
    rows, ok = [], True
    for name, spec in cfg["correct"]["limits"].items():
        limit = spec["limit"]
        values = [r["compared"].get(name) for r in ranks]
        worst = max((float("inf") if v is None or v != v else v)
                    for v in values)
        passed = worst <= limit
        ok = ok and passed
        rows.append({"compared": name, "value": worst, "limit": limit,
                     "ok": passed})
    return ok, rows


def reduce_run(loaded: dict, ranks: list[dict], trace: bool) -> dict:
    """The per-rank observations to the contract's result line."""
    from perfbench import readers

    ranks = sorted(ranks, key=lambda r: r["rank"])
    r0 = ranks[0]
    correct, compared = judge(loaded["cfg"], ranks)
    for row in compared:
        print("compared " + json.dumps(row), flush=True)
    obs = readers.Observed(loaded, ranks)
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(loaded, group):
        value = obs.read(m, group)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(r0["device"])
    device["memory_peak_bytes"] = max(
        r["device"]["memory_peak_bytes"] for r in ranks)
    out = {"correct": bool(correct), "attempted": r0["versions"],
           "failed": max(r["failed"] for r in ranks), "metrics": metrics,
           "device": device}
    if trace:
        traces = [r["trace"] for r in ranks if r.get("trace")]
        if not traces or any(t["busy_s"] <= 0 for t in traces):
            raise Refused("the traced run saw no operation on the device")
        device["busy_s"] = sum(t["busy_s"] for t in traces) / len(traces)
        device["window_s"] = sum(t["window_s"] for t in traces) / len(traces)
        out["breakdown"] = readers.breakdown(traces)
    return out


# ----------------------------------------------------------------------
# entry
# ----------------------------------------------------------------------
def parse(argv):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rows", type=int, default=None,
                    help="the size per chip handed to the adapter, for "
                         "rehearsals and tests only: a cell is timed at "
                         "its configuration's size")
    ap.add_argument("--grid", default=None,
                    help="the control of `correct`: the adapter rounds the "
                         "data to this float grid before the job sees it")
    ap.add_argument("--rank-of", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--t-start", type=float, default=None,
                    help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def run_children(args, loaded: dict, run_dir: str, t_start: float,
                 entry: str) -> list[dict]:
    """One child per chip under the tracker.  This process has not
    touched JAX and does not while they run."""
    world = int(loaded["traffic"]["world"])
    child = [sys.executable, entry, "--workload", args.workload,
             "--seed", str(args.seed), "--seconds", repr(args.seconds),
             "--trace", str(args.trace), "--rank-of", run_dir,
             "--t-start", repr(t_start)]
    for flag, value in (("--rows", args.rows), ("--grid", args.grid)):
        if value is not None:
            child += [flag, str(value)]
    cmd = [sys.executable, "-m", "rabit_tpu.tracker.launch_local",
           "-n", str(world)] + child
    log = open(os.path.join(run_dir, "children.log"), "wb")
    proc = subprocess.Popen(cmd, cwd=ROOT, stdout=log,
                            stderr=subprocess.STDOUT, start_new_session=True,
                            env={**os.environ, "PYTHONPATH": os.pathsep.join(
                                filter(None, [ROOT, os.environ.get(
                                    "PYTHONPATH")]))})
    try:
        code = proc.wait(timeout=CHILD_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        code = 124
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
        log.close()
    paths = [os.path.join(run_dir, f"rank-{r}.json") for r in range(world)]
    if code != 0 or not all(os.path.exists(p) for p in paths):
        with open(os.path.join(run_dir, "children.log"), "rb") as f:
            sys.stderr.write(f.read()[-8000:].decode("utf-8", "replace"))
        raise Refused(f"the ranks did not finish (launcher exit {code})")
    with open(os.path.join(run_dir, "children.log"), "rb") as f:
        for text in f.read().decode("utf-8", "replace").splitlines():
            if text.startswith("perfbench phases"):
                print(text, file=sys.stderr, flush=True)
    return [read_json(p) for p in paths]


def main(argv=None, entry: str | None = None,
         t_start: float | None = None) -> int:
    t_start = t_start or time.time()
    args = parse(sys.argv[1:] if argv is None else argv)
    loaded = load_cell(args.workload)
    if args.rank_of is not None:            # a child: one rank of several
        run_rank(loaded, args.seed, args.seconds, bool(args.trace),
                 args.rank_of, args.t_start, args.rows, args.grid)
        return 0
    run_dir = tempfile.mkdtemp(prefix="perfbench-")
    try:
        if int(loaded["traffic"]["world"]) == 1:
            ranks = [run_rank(loaded, args.seed, args.seconds,
                              bool(args.trace), run_dir, t_start, args.rows,
                              args.grid)]
        else:
            ranks = run_children(args, loaded, run_dir, t_start,
                                 entry or os.path.join(HERE, "run.py"))
        line = reduce_run(loaded, ranks, bool(args.trace))
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)
    print(json.dumps(line), flush=True)
    return 0
