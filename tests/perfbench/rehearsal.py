"""Helpers of the rehearsal tests: the benchmark's command run here at a
tiny size (steering in ``as_if_on_chip.py``), and a copy of the
benchmark in a temporary directory to which a test adds files and
entries — a configuration, a traffic mix, a per-layer metric, a second
learner — without editing a file that is there."""
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
STEERED = os.path.join("tests", "perfbench", "as_if_on_chip.py")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
ROWS = 16384


def run(args, root=ROOT, entry=STEERED, env=None, timeout=300):
    """The command as the driver gives it; returns (process, result line
    as a dict or None)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(root, entry)] + [str(a) for a in args],
        cwd=root, capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": ROOT, "XLA_FLAGS": "", **(env or {})})
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    try:
        line = json.loads(lines[-1]) if lines else None
    except ValueError:
        line = None
    return proc, line if isinstance(line, dict) else None


def cell_args(workload, trace, seed=2 ** 31 + 77, seconds=1.5, rows=ROWS):
    return ["--workload", workload, "--seed", seed, "--seconds", seconds,
            "--trace", trace, "--rows", rows]


def compared(proc) -> dict:
    """The numbers a run printed beside their limits."""
    out = {}
    for ln in proc.stdout.splitlines():
        if ln.startswith("compared "):
            row = json.loads(ln[len("compared "):])
            out[row["compared"]] = row
    return out


def copy_benchmark(tmp) -> str:
    """BENCHMARK.json and the files under its paths, copied to ``tmp``
    (what the driver's bare checkout holds, plus nothing)."""
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
    for path in manifest["paths"]:
        shutil.copytree(os.path.join(ROOT, path), os.path.join(tmp, path),
                        ignore=shutil.ignore_patterns("__pycache__"))
    return str(tmp)


def add_tiny_cell(root) -> str:
    """Adds — as files and entries only — a configuration at a size a
    test can hold, a traffic mix, a cell of the two, and two per-layer
    metrics, each read by a file of its own (one data, one code).
    Returns the cell's name."""
    bench = os.path.join(root, "perfbench")
    cfg = json.load(open(os.path.join(
        bench, "configs", "kmeans-dense-d256-k64.json")))
    cfg["rows_per_chip"] = ROWS
    cfg["reduced"] = ["rows_per_chip"]
    json.dump(cfg, open(os.path.join(
        bench, "configs", "kmeans-tiny-d256-k64.json"), "w"))
    json.dump({"world": 1, "chips": 1, "engine": "empty", "engine_args": [],
               "device_chain": 2, "warmup_versions": 1,
               "why": "two iterations a commit"},
              open(os.path.join(bench, "traffic", "chain2-x1.json"), "w"))
    json.dump({"kind": "field", "field": "gen_s", "over": "max",
               "layer": "staging"},
              open(os.path.join(bench, "layers", "generate_s.json"), "w"))
    with open(os.path.join(bench, "layers", "versions_per_s.py"), "w") as f:
        f.write("def read(observed):\n"
                "    r = observed.ranks[0]\n"
                "    return r['versions'] / r['span_s']\n")
    path = os.path.join(root, "BENCHMARK.json")
    manifest = json.load(open(path))
    manifest["configs"].append({
        "name": "kmeans-tiny-d256-k64", "source": cfg["source"],
        "file": "perfbench/configs/kmeans-tiny-d256-k64.json",
        "reduced": ["rows_per_chip"], "why": "a test's size"})
    manifest["workloads"].append({
        "name": "kmeans-tiny-chain2-x1", "config": "kmeans-tiny-d256-k64",
        "traffic": "chain2-x1", "chips": 1, "why": "added by a test"})
    for name, unit in (("generate_s", "s"), ("versions_per_s", "1/s")):
        manifest["per_layer"].append({
            "name": name, "unit": unit, "better": "lower",
            "source": "host_clock", "layer": "staging", "moves": "setup_s",
            "workloads": ["kmeans-tiny-chain2-x1"]})
    json.dump(manifest, open(path, "w"))
    return "kmeans-tiny-chain2-x1"


COLSUM_ADAPTER = '''"""A second learner, added by a test as a file: column sums of a seeded
matrix, added up on the device once a step, committed every step."""
import numpy as np


class Data:
    def __init__(self, cfg, seed, rows, grid):
        self.n = rows or cfg["rows_per_chip"]
        rng = np.random.default_rng([seed, self.n])
        self.x = rng.random((self.n, cfg["dim"]), dtype=np.float32)
        self.x_run = self.x
        if grid:                       # the control: a lower precision
            import ml_dtypes

            self.x_run = self.x.astype(getattr(ml_dtypes, grid)).astype(
                np.float32)


def make_data(cfg, seed, shard, world, threads, rows=None, grid=None):
    return Data(cfg, seed, rows, grid)


def describe(cfg, traffic, data):
    return {"work_per_version": data.n}


def watch(data, spans, trace):
    return []


def run_job(cfg, traffic, data):
    import jax
    import jax.numpy as jnp

    import rabit_tpu

    version, model = rabit_tpu.load_checkpoint()
    acc = jnp.asarray(model["acc"] if version else
                      np.zeros(cfg["dim"], np.float32))
    steps = int(model["steps"]) if version else 0
    x = jax.device_put(data.x_run)
    step = jax.jit(lambda a, m: a + jnp.sum(m, axis=0))
    while True:
        acc = step(acc, x)
        steps += 1
        rabit_tpu.checkpoint({"acc": np.asarray(acc), "steps": steps})


def committed(model):
    return {"acc": model["acc"], "steps": np.array([model["steps"]])}


def check(cfg, traffic, data, committed, exchange):
    want = int(committed["steps"][0]) * data.x.sum(axis=0, dtype=np.float64)
    return {"acc_gap": float(np.max(np.abs(committed["acc"] - want) / want))}
'''


def add_second_learner(root) -> str:
    """Adds — as files and entries only — a learner that is not k-means
    (its adapter, a configuration that names it, a traffic mix) and a
    cell of them.  Returns the cell's name."""
    bench = os.path.join(root, "perfbench")
    with open(os.path.join(bench, "learners", "colsum.py"), "w") as f:
        f.write(COLSUM_ADAPTER)
    exact = {"limit": 0, "why": "exact"}
    json.dump({"learner": "colsum", "source": "a test", "dim": 64,
               "rows_per_chip": 4096, "reduced": [],
               "correct": {"limits": {
                   "acc_gap": {"limit": 1e-4, "why": "float32 sums"},
                   "version_gap": exact, "rank_disagreement": exact,
                   "host_ops": exact}}},
              open(os.path.join(bench, "configs", "colsum-d64.json"), "w"))
    json.dump({"world": 1, "chips": 1, "engine": "empty", "engine_args": [],
               "warmup_versions": 2, "why": "a commit every step"},
              open(os.path.join(bench, "traffic", "step-x1.json"), "w"))
    path = os.path.join(root, "BENCHMARK.json")
    manifest = json.load(open(path))
    manifest["configs"].append({
        "name": "colsum-d64", "source": "a test",
        "file": "perfbench/configs/colsum-d64.json", "reduced": [],
        "why": "a learner that is not k-means"})
    manifest["workloads"].append({
        "name": "colsum-step-x1", "config": "colsum-d64",
        "traffic": "step-x1", "chips": 1, "why": "added by a test"})
    json.dump(manifest, open(path, "w"))
    return "colsum-step-x1"


def digest(root) -> dict:
    """Every file under ``root`` but BENCHMARK.json (which gains
    entries), by content: what a test that only adds leaves as it was."""
    import hashlib

    out = {}
    for base, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            path = os.path.join(base, name)
            if os.path.relpath(path, root) != "BENCHMARK.json":
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    open(path, "rb").read()).hexdigest()
    return out
