"""XLA engine tests: single-process semantics + multi-process device path."""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import rabit_tpu


@pytest.fixture
def xla_world1():
    if rabit_tpu.initialized():
        rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="xla")
    yield
    rabit_tpu.finalize()


def test_world1_identity(xla_world1):
    assert rabit_tpu.get_world_size() == 1
    assert rabit_tpu.get_rank() == 0
    x = jnp.arange(8, dtype=jnp.float32)
    out = rabit_tpu.allreduce(x, rabit_tpu.SUM)
    np.testing.assert_array_equal(
        np.asarray(out), np.arange(8, dtype=np.float32))
    a = np.ones(4)
    assert rabit_tpu.allreduce(a, rabit_tpu.MAX) is a


def test_pallas_ring_routing():
    """rabit_device_impl=pallas_ring routes large supported allreduces
    through the ring kernel and leaves small payloads / unsupported ops
    on psum (the latency-bound regime)."""
    from rabit_tpu.engine.xla import XLAEngine
    from rabit_tpu.ops import ReduceOp

    eng = XLAEngine()
    eng.init({"rabit_device_impl": "pallas_ring",
              "rabit_pallas_min_bytes": 4096})
    try:
        assert eng._use_pallas_ring((2048,), "float32", ReduceOp.SUM)
        assert eng._use_pallas_ring((64, 64), "float32", ReduceOp.MAX)
        # below the size gate
        assert not eng._use_pallas_ring((16,), "float32", ReduceOp.SUM)
        # no kernel combine for bitwise ops
        assert not eng._use_pallas_ring((2048,), "int32", ReduceOp.BITOR)
    finally:
        eng.shutdown()
    # default impl: everything stays on psum
    eng2 = XLAEngine()
    eng2.init({})
    try:
        assert not eng2._use_pallas_ring((1 << 20,), "float32",
                                         ReduceOp.SUM)
    finally:
        eng2.shutdown()
    with pytest.raises(Exception, match="rabit_device_impl"):
        bad = XLAEngine()
        bad.init({"rabit_device_impl": "warp"})


def test_world1_prepare_fun_called(xla_world1):
    called = []
    x = jnp.zeros(3)
    rabit_tpu.allreduce(x, rabit_tpu.SUM, prepare_fun=lambda: called.append(1))
    assert called == [1]


def test_world1_checkpoint_roundtrip(xla_world1):
    version, model = rabit_tpu.load_checkpoint()
    assert version == 0 and model is None
    rabit_tpu.checkpoint({"w": [1, 2, 3]})
    version, model = rabit_tpu.load_checkpoint()
    assert version == 1 and model == {"w": [1, 2, 3]}


def test_world1_broadcast(xla_world1):
    assert rabit_tpu.broadcast({"k": 7}, 0) == {"k": 7}


@pytest.mark.parametrize("world", [2, 3])
def test_multiprocess_xla_engine(world):
    """N processes: tracker control plane + Gloo-backed XLA data plane."""
    from rabit_tpu.tracker.launch_local import launch

    code = launch(world, [sys.executable, "tests/workers/check_xla.py"])
    assert code == 0


def test_multiprocess_xla_engine_native_inner(request):
    """XLA data plane over the C++ robust engine control plane."""
    from rabit_tpu.tracker.launch_local import launch

    request.getfixturevalue("native_lib")
    code = launch(2, [sys.executable, "tests/workers/check_xla.py"],
                  extra_env={"RABIT_INNER": "native"})
    assert code == 0


def test_xla_worker_death_relaunch_resume(request):
    """The device-plane fault story end-to-end: rank 1 dies mid-run, the
    survivors' device collective fails and degrades to the host
    transport, the keepalive launcher restarts rank 1, which rejoins
    degraded and resumes from the last checkpoint (reference recovery
    contract: src/allreduce_robust.cc:73-105)."""
    from rabit_tpu.tracker.launch_local import launch

    request.getfixturevalue("native_lib")
    code = launch(3, [sys.executable, "tests/workers/xla_restart.py"],
                  extra_env={"RABIT_INNER": "native"}, watchdog_sec=20)
    assert code == 0


def test_xla_worker_death_world4_blocked_peer(request):
    """World 4: a peer death leaves rank 3 BLOCKED inside its Gloo
    collective (its direct transport peers are alive — they abandoned the
    collective after degrading — so no error ever reaches it).  The
    tracker watchdog is the designed answer: it reports the silent rank,
    the launcher kills and restarts it, and the relaunch (flagged by the
    tracker) rejoins degraded and resumes from the checkpoint."""
    from rabit_tpu.tracker.launch_local import launch

    request.getfixturevalue("native_lib")
    code = launch(4, [sys.executable, "tests/workers/xla_restart.py"],
                  extra_env={"RABIT_INNER": "native"}, watchdog_sec=20)
    assert code == 0


def test_xla_rank0_death_relaunch_resume(request):
    """Rank 0 dies mid-run.  Because the JAX coordination service is
    hosted in the TRACKER (cmd=jaxsvc), losing rank 0 is an ordinary
    recoverable peer death — survivors degrade instead of being
    LOG(FATAL)-terminated by the error-polling thread, the relaunch
    rejoins, and the next checkpoint re-forms the device plane."""
    from rabit_tpu.tracker.launch_local import launch

    request.getfixturevalue("native_lib")
    code = launch(3, [sys.executable, "tests/workers/xla_restart.py"],
                  extra_env={"RABIT_INNER": "native",
                             "RABIT_XLA_DIE": "0:2"},
                  watchdog_sec=20)
    assert code == 0


def test_xla_whole_job_restart_reforms(request):
    """Every rank flagged as a mid-job relaunch (long-lived tracker +
    coordinated platform restart): all come up degraded, and the first
    checkpoint boundary forms a device plane from nothing — the
    permanent performance cliff of the round-2 design is gone."""
    from rabit_tpu.tracker.launch_local import launch

    request.getfixturevalue("native_lib")
    code = launch(3, [sys.executable, "tests/workers/xla_restart.py"],
                  extra_env={"RABIT_INNER": "native",
                             "RABIT_XLA_DIE": "none",
                             "RABIT_XLA_FORCE_RELAUNCH": "1"},
                  watchdog_sec=20)
    assert code == 0


def test_xla_reform_disabled_stays_degraded(request):
    """RABIT_DEVICE_REFORM=0 keeps the round-2 contract: a relaunched
    job runs degraded (host transport) to completion, no re-formation."""
    from rabit_tpu.tracker.launch_local import launch

    request.getfixturevalue("native_lib")
    code = launch(3, [sys.executable, "tests/workers/xla_restart.py"],
                  extra_env={"RABIT_INNER": "native",
                             "RABIT_DEVICE_REFORM": "0"},
                  watchdog_sec=20)
    assert code == 0


def test_xla_two_deaths_different_iterations(request):
    """Two workers die at different iterations: each relaunch rejoins
    degraded and catches up from its own checkpoint version while the
    other death is still being recovered (the die-different-versions
    matrix of test/test.mk, lifted onto the XLA engine)."""
    from rabit_tpu.tracker.launch_local import launch

    request.getfixturevalue("native_lib")
    code = launch(4, [sys.executable, "tests/workers/xla_restart.py"],
                  extra_env={"RABIT_INNER": "native",
                             "RABIT_XLA_DIE": "1:1;3:2"},
                  watchdog_sec=20)
    assert code == 0


def test_xla_world8_two_simultaneous_deaths(request):
    """World 8, two workers die at the SAME iteration (die-same matrix
    of test/test.mk on the XLA engine at the verdict-requested world):
    both relaunches rejoin degraded, one checkpoint boundary re-forms
    the 8-process device plane, and the numerics stay exact."""
    from rabit_tpu.tracker.launch_local import launch

    request.getfixturevalue("native_lib")
    code = launch(8, [sys.executable, "tests/workers/xla_restart.py"],
                  extra_env={"RABIT_INNER": "native",
                             "RABIT_XLA_DIE": "2:2;5:2"},
                  watchdog_sec=30)
    assert code == 0


def test_xla_world8_death_during_reform(request):
    """World 8: rank 1 dies mid-run; at the checkpoint boundary the
    plane re-forms, and rank 6 dies INSIDE the replayed post-reform
    round (engine/xla.py's replayed-round/stale-group branches) — the
    survivors must degrade again, take rank 6's relaunch back in, and
    re-form once more."""
    from rabit_tpu.tracker.launch_local import launch

    request.getfixturevalue("native_lib")
    code = launch(8, [sys.executable, "tests/workers/xla_restart.py"],
                  extra_env={"RABIT_INNER": "native",
                             "RABIT_XLA_DIE": "1:1",
                             "RABIT_XLA_DIE_ON_REFORM": "6"},
                  watchdog_sec=30)
    assert code == 0


def test_xla_world8_rank0_then_another_consecutive_checkpoints(request):
    """World 8: rank 0 (coordination-sensitive) dies at iteration 1 and
    rank 4 at iteration 2 — deaths in consecutive checkpoint spans, each
    recovered while the previous recovery's reform is still fresh."""
    from rabit_tpu.tracker.launch_local import launch

    request.getfixturevalue("native_lib")
    code = launch(8, [sys.executable, "tests/workers/xla_restart.py"],
                  extra_env={"RABIT_INNER": "native",
                             "RABIT_XLA_DIE": "0:1;4:2"},
                  watchdog_sec=30)
    assert code == 0


def test_xla_death_inside_group_formation(request):
    """The window the design admits is awkward: a worker finishes the
    tracker round but dies BEFORE the JAX group forms.  Survivors must
    surface the failed formation within the capped first-formation
    timeout (or be watchdog-recovered out of the blocked connect),
    start degraded, complete the run on the host transport, and the
    checkpoint boundary must re-form the device plane (reference
    analogue: death during recovery, the die-hard matrix of
    test/test.mk)."""
    from rabit_tpu.tracker.launch_local import launch

    request.getfixturevalue("native_lib")
    code = launch(3, [sys.executable, "tests/workers/xla_restart.py"],
                  extra_env={"RABIT_INNER": "native",
                             "RABIT_XLA_DIE": "none",
                             "RABIT_XLA_DIE_FORMATION": "1"},
                  watchdog_sec=20)
    assert code == 0


def _run_adopt_workers(world: int, mode: str) -> list:
    """Spawn ``world`` processes that self-initialize jax.distributed
    (CPU/Gloo) and then adopt it through init(rabit_engine="xla")."""
    import socket
    import subprocess

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    procs = []
    for r in range(world):
        env = dict(os.environ)
        env.update({"ADOPT_COORD": f"127.0.0.1:{port}",
                    "ADOPT_RANK": str(r), "ADOPT_WORLD": str(world),
                    "ADOPT_MODE": mode})
        env.pop("RABIT_TRACKER_URI", None)
        procs.append(subprocess.Popen(
            [sys.executable, "tests/workers/adopt_worker.py"], env=env))
    return [p.wait(timeout=300) for p in procs]


def test_xla_adopt_mode_world3():
    """Pure adopt mode at world 3: rank/world adoption, numpy in-place
    via device reduction, object broadcast over
    _device_byte_broadcast — the pod path doc/scaling.md promises."""
    assert _run_adopt_workers(3, "ok") == [0, 0, 0]


def test_xla_adopt_mode_peer_death_raises():
    """Adopt mode has no host transport: a peer's death must surface as
    the documented RuntimeError on the survivors' next device
    collective (engine/xla.py _host_degrade), never hang or silently
    degrade."""
    codes = _run_adopt_workers(3, "peerdeath")
    assert codes[1] == 7           # the victim's own exit
    assert codes[0] == 0 and codes[2] == 0, codes


def _run_mixed_workers(world: int, mode: str, monkeypatch) -> list:
    """MIXED mode: a tracker control plane AND a worker-initialized
    jax.distributed world.  The tracker runs in-process with rank
    pinning on (it reads the env at assignment time)."""
    import socket
    import subprocess

    from rabit_tpu.tracker.tracker import Tracker

    monkeypatch.setenv("RABIT_TRACKER_PIN_RANKS", "1")
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    tracker = Tracker(world)
    tracker.start()
    try:
        procs = []
        for r in range(world):
            env = dict(os.environ)
            env.update(tracker.worker_env(task_id=""))
            env.pop("RABIT_TASK_ID", None)  # the engine must self-register
            env.update({"MIXED_COORD": f"127.0.0.1:{port}",
                        "MIXED_RANK": str(r), "MIXED_WORLD": str(world),
                        "MIXED_MODE": mode})
            if mode == "relaunch":
                env["RABIT_RELAUNCH"] = "1"
            procs.append(subprocess.Popen(
                [sys.executable, "tests/workers/mixed_worker.py"], env=env))
        return [p.wait(timeout=300) for p in procs]
    finally:
        tracker.stop()


def test_xla_mixed_mode_world3(monkeypatch):
    """MIXED mode end-to-end: the engine adopts the external JAX world
    for the device plane, registers with task_id = jax.process_index(),
    and rank pinning aligns the control-plane rank with it — numpy ops
    and checkpoints ride the fault-tolerant host engine while jax.Array
    ops ride the device plane (the contract engine/xla.py documents for
    tracker + pre-initialized JAX)."""
    assert _run_mixed_workers(3, "ok", monkeypatch) == [0, 0, 0]


def test_xla_mixed_mode_rank_mismatch_degrades(monkeypatch):
    """Misaligned numberings (explicit task_ids reversed) must degrade
    EVERY rank to the host transport by consensus — including rank 1,
    whose own mesh check passes under the reversal — never crash some
    ranks or split-brain the collectives."""
    assert _run_mixed_workers(3, "mismatch", monkeypatch) == [0, 0, 0]


def test_xla_mixed_mode_relaunch_stays_adopted(monkeypatch):
    """A mixed-mode relaunch (RABIT_RELAUNCH set) must still be marked
    adopted — otherwise its checkpoint-time _maybe_reform would issue
    host-plane protocol ops the adopted survivors never pair with —
    and must run degraded permanently without joining the init-time
    mesh consensus (which only first-life ranks reach)."""
    assert _run_mixed_workers(3, "relaunch", monkeypatch) == [0, 0, 0]
