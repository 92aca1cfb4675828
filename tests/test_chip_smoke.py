"""chip_smoke.py rehearsed without the chip (on-chip-measurement guide,
section 2): the phases end to end on the CPU at a tiny size with the
Pallas kernels interpreted, the four-chip phases on virtual devices, the
process-per-chip worker on the CPU backend — plus the two ways the
script must FAIL: off the chip before any phase, and when a phase raises.

Steering lives here, not in options of the program: the backend name is
faked so the library's one "on the chip?" switch says yes, the kernels
are wrapped to interpret, and the tier budgets are shrunk so tiny data
lands in the tiers the real sizes reach.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


@pytest.fixture
def as_if_on_chip(monkeypatch):
    import jax

    import rabit_tpu
    import rabit_tpu.ops.histogram_kernel as hk
    import rabit_tpu.ops.kmeans_kernel as kk
    import rabit_tpu.ops.sparse_linear_kernel as sk
    from rabit_tpu.learn import kmeans

    if rabit_tpu.initialized():
        rabit_tpu.finalize()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for mod, name in ((kk, "kmeans_stats_fused"),
                      (kk, "kmeans_ell_stats_fused"),
                      (hk, "hist_fused_multi"),
                      (sk, "lbfgs_margin"), (sk, "lbfgs_grad")):
        def interpreted(*a, _orig=getattr(mod, name), **kw):
            kw["interpret"] = True
            return _orig(*a, **kw)

        monkeypatch.setattr(mod, name, interpreted)
    stage = kmeans.prepare_shard
    monkeypatch.setattr(
        kmeans, "prepare_shard",
        lambda *a, **kw: stage(*a, **{**kw, "budget": 0}))
    monkeypatch.setattr(kmeans, "_dense16_budget", lambda: 1 << 40)
    # the interpreter lowers to plain HLO: no custom call to find
    monkeypatch.setattr(chip_smoke, "kernel_in_program",
                        lambda fn, *shapes: True)
    monkeypatch.setattr(chip_smoke, "K", 8)
    # four devices, as the chips of one host (and the distributed
    # interpreter wedges at eight devices from 256 KB up — at the parent
    # commit too — so the ring is rehearsed at the real mesh size only)
    four = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a: four)
    yield
    if rabit_tpu.initialized():
        rabit_tpu.finalize()


@pytest.mark.parametrize("phase,args", [
    ("phase_kmeans_dense16", (0, 1 << 14)),
    ("phase_kmeans_ell", (0, 1 << 14)),
    ("phase_gbdt", (0, 1 << 12)),
    ("phase_lbfgs_products", (0, 1 << 12)),
    ("phase_mesh_kmeans", (0, 1 << 11)),
    ("phase_mesh_allreduce", (0, (64 << 10, 1 << 20), True)),
])
def test_phase_rehearsal(as_if_on_chip, phase, args):
    out = getattr(chip_smoke, phase)(*args)
    assert out["seconds"]


def test_dense_rows_sizing():
    # the chip tool's v5e: 16.9 GB reported, 47 GB of host memory
    budget = 16909336064 - (16909336064 >> 3)
    assert chip_smoke.dense_rows(budget, 47 << 30) == 23 << 20
    # a smaller device shrinks the shard instead of overflowing it
    assert chip_smoke.dense_rows(8 << 30, 47 << 30) == 15 << 20
    with pytest.raises(RuntimeError, match="dense16 tier needs"):
        chip_smoke.dense_rows(1 << 30, 47 << 30)


def test_dense16_budget_refuses_silent_tpu(monkeypatch):
    """A TPU that reports no memory limit is an error, not 14 GiB."""
    import jax

    from rabit_tpu.learn import kmeans
    from rabit_tpu.utils.checks import RabitError

    assert kmeans._dense16_budget() > 0        # CPU: host memory
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RabitError, match="reports no memory limit"):
        kmeans._dense16_budget()


def test_refuses_to_run_off_the_chip():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert '"ok"' not in proc.stdout and '"phase"' not in proc.stdout


def test_failing_phase_fails_the_run(monkeypatch, capsys):
    class Dev:
        platform, device_kind = "tpu", "fake"

    class Clock:
        def take(self):
            return {}

    def boom(seed, rows):
        raise RuntimeError("phase blew up")

    monkeypatch.setattr(chip_smoke, "open_device",
                        lambda chips: ([Dev()], Clock()))
    monkeypatch.setattr(chip_smoke, "dense_rows", lambda *a: 1)
    monkeypatch.setattr(chip_smoke, "phase_kmeans_dense16", boom)
    with pytest.raises(RuntimeError, match="phase blew up"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_process_per_device_worker_on_cpu():
    """tests/workers/check_xla_chip.py pins no backend; with
    JAX_PLATFORMS=cpu in the environment it is the CPU rehearsal of the
    process-per-chip phase: formed (not degraded), device ops only."""
    from rabit_tpu.tracker.launch_local import launch

    code = launch(4, [sys.executable, "tests/workers/check_xla_chip.py"],
                  extra_env={"JAX_PLATFORMS": "cpu"})
    assert code == 0


def test_chip_envs(monkeypatch, capsys):
    from rabit_tpu.tracker import launch_local as ll

    monkeypatch.setattr(ll, "local_chips", lambda: 0)
    assert ll.chip_envs(4) == [{}] * 4       # CPU host: nothing to give
    monkeypatch.setattr(ll, "local_chips", lambda: 4)
    envs = ll.chip_envs(4)
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == list("0123")
    assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == list("0123")
    assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
    assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
    addrs = envs[0]["TPU_PROCESS_ADDRESSES"].split(",")
    assert len(set(addrs)) == 4
    assert all(e["TPU_PROCESS_ADDRESSES"] == envs[0]["TPU_PROCESS_ADDRESSES"]
               for e in envs)
    assert [f"localhost:{e['TPU_PROCESS_PORT']}" for e in envs] == addrs
    # chips, but not one per worker: say so, pin nothing
    assert ll.chip_envs(8) == [{}] * 8
    assert "NOT pinned" in capsys.readouterr().err
