"""The reduction from a profiler trace to numbers, checked on the small
trace recorded on a v5e (perfbench/recorded/dense-chain8-x1.xplane.pb:
6 s of the dense chained cell, PR 23) against what that trace holds,
read off by hand with ``ProfileData`` — 42 programs ``jit_run`` summing
5932.718 ms, 336 calls of the stats kernel summing 5932.104 ms, 42
``perfbench:commit`` spans summing 4.03 ms — and on made-up intervals."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import trace_reduce as T  # noqa: E402

RECORDED = os.path.join(ROOT, "perfbench", "recorded",
                        "dense-chain8-x1.xplane.pb")
KERNEL_HLO = (
    "%_stats_call.3 = (f32[64,256]{1,0:T(8,128)S(1)}, f32[64,1]{1,0:T(8,128)"
    "S(1)}) custom-call(bf16[24117248,256]{1,0:T(8,128)(2,1)} %get-tuple-"
    "element.90, f32[1,24117248]{1,0:T(1,128)} %bitcast.3), "
    "custom_call_target=\"tpu_custom_call\"")


@pytest.fixture(scope="module")
def reduced():
    return T.reduce_file(RECORDED, "perfbench:")


def test_recorded_busy_share(reduced):
    assert reduced["devices"] == 1
    assert reduced["busy_s"] == pytest.approx(5.932718, rel=1e-5)
    assert reduced["window_s"] == pytest.approx(5.998382, rel=1e-5)
    idle = 100 * (1 - reduced["busy_s"] / reduced["window_s"])
    assert idle == pytest.approx(1.0947, rel=1e-3)


def test_recorded_kernel_time_and_calls(reduced):
    seconds, calls = reduced["ops"]["run/_stats_call:custom-call"]
    assert calls == 336                       # 42 chains of 8
    assert seconds == pytest.approx(5.932104, rel=1e-6)
    assert seconds / calls == pytest.approx(17.655e-3, rel=1e-3)


def test_recorded_containers_are_left_out(reduced):
    assert not any(name.endswith(":while") for name in reduced["ops"])
    total = sum(s for s, _ in reduced["ops"].values())
    assert total <= reduced["busy_s"] * (1 + 1e-9)


def test_recorded_gaps_by_host_span(reduced):
    gaps = reduced["gaps"]
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    # 41 commits lie in a gap between programs, about 0.1 ms each
    assert gaps["commit"] == pytest.approx(3.95e-3, rel=0.05)
    assert gaps[T.HOST_CODE] > 10 * gaps["commit"]
    assert reduced["collective_s"] == 0.0


def test_recorded_clock_shift_puts_no_program_before_its_enqueue():
    import jax

    profile = jax.profiler.ProfileData.from_file(RECORDED)
    (ops, starts), = T.device_ops(profile).values()
    shift = T.clock_shift(profile, starts)
    assert 1.0e6 < shift < 3.0e6              # 1.2 ms seen, in ns
    assert T.clock_shift(profile, starts[:-1]) == 0.0


@pytest.mark.parametrize("text,want", [
    (KERNEL_HLO, ("_stats_call", "custom-call")),
    ("%all-reduce.7 = f32[64,257]{1,0:T(8,128)} all-reduce(f32[64,257]{1,0} "
     "%p), replica_groups={{0,1,2,3}}", ("all-reduce", "all-reduce")),
    ("%while = (s32[]{:T(128)}, f32[64,256]{1,0:T(8,128)S(1)}) while((s32[]"
     "{:T(128)}, f32[64,256]{1,0}) %tuple), body=%b", ("while", "while")),
    ("%copy.11 = f32[64,256]{1,0:T(8,128)S(1)} copy(f32[64,256]{1,0} %cent.1)",
     ("copy", "copy")),
    ("dot_general.1", ("dot_general", "dot_general")),
])
def test_op_name(text, want):
    assert T.op_name(text) == want


def test_interval_arithmetic():
    busy = T._union([[0, 4], [3, 6], [10, 12], [12, 13]])
    assert busy == [[0, 6], [10, 13]]
    assert T._length(busy) == 9
    idle = T._subtract([[0, 20]], busy)
    assert idle == [[6, 10], [13, 20]]
    assert T._overlap(idle, [[5, 7], [9, 15]]) == 1 + 1 + 2
    assert T._subtract([[0, 5]], []) == [[0, 5]]
    assert T._subtract([[0, 5]], [[0, 5]]) == []


class Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = []


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class Profile:
    def __init__(self, planes):
        self.planes = planes


def test_collective_exposed_is_the_part_no_compute_covers():
    ar = "%all-reduce.1 = f32[4]{0} all-reduce(f32[4]{0} %x)"
    mm = "%fusion.2 = f32[4]{0} fusion(f32[4]{0} %x), kind=kLoop"
    profile = Profile([
        Plane("/device:TPU:0", [
            Line("XLA Modules", [Ev("jit_step(1)", 0, 100)]),
            Line("XLA Ops", [Ev(mm, 0, 50), Ev(ar, 40, 30), Ev(mm, 90, 10)]),
        ]),
        Plane("/host:CPU", [Line("python3", [
            Ev("perfbench:commit", 72, 10)])])])
    out = T.reduce_profile(profile, "perfbench:")
    assert out["collective_s"] == pytest.approx(30e-9)
    assert out["collective_exposed_s"] == pytest.approx(20e-9)   # 50..70
    assert out["busy_s"] == pytest.approx(80e-9)
    assert out["gaps"]["commit"] == pytest.approx(10e-9)         # 72..82
    assert out["gaps"][T.HOST_CODE] == pytest.approx(10e-9)
    assert out["ops"]["step/all-reduce:all-reduce"] == pytest.approx([30e-9, 1])


def test_several_devices_are_averaged():
    op = "%fusion.2 = f32[4]{0} fusion(f32[4]{0} %x), kind=kLoop"
    planes = [Plane(f"/device:TPU:{i}", [
        Line("XLA Modules", [Ev("jit_f(1)", 0, 100)]),
        Line("XLA Ops", [Ev(op, 0, busy), Ev(op, 90, 10)])])
        for i, busy in enumerate((20, 60))]
    out = T.reduce_profile(Profile(planes), "perfbench:")
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx(50e-9)
    assert out["window_s"] == pytest.approx(100e-9)


def test_a_trace_without_device_operations_reduces_to_zero_busy():
    out = T.reduce_profile(Profile([Plane("/device:TPU:0", [])]),
                           "perfbench:")
    assert out["busy_s"] == 0.0 and out["devices"] == 0


def test_a_trace_without_a_tpu_plane_is_refused_not_read_from_the_host():
    host = Plane("/host:CPU", [Line("main", [Ev("fusion", 0, 10)])])
    with pytest.raises(ValueError, match="no /device:TPU"):
        T.reduce_profile(Profile([host]), "perfbench:")


def test_find_xplane_says_when_there_is_none(tmp_path):
    with pytest.raises(FileNotFoundError):
        T.find_xplane(str(tmp_path))


def test_readers_on_the_recorded_trace(reduced):
    """The per-layer readers keyed on the step kernel, on the chip's
    own trace: their files, patterns and arithmetic."""
    from perfbench import harness, readers

    bench = os.path.join(ROOT, "perfbench")
    obs = readers.Observed.__new__(readers.Observed)
    cfg = harness.read_json(os.path.join(
        bench, "configs", "kmeans-dense-d256-k64.json"))
    obs.loaded, obs.bench_dir, obs._peaks = {"bench_dir": bench,
                                             "cfg": cfg}, bench, None
    obs.ranks = [{"device": {"kind": "TPU v5 lite"}, "trace": reduced,
                  "kernel_shape": {"rows": 24117248, "k": 64, "nnz": 32,
                                   "dim_staged": 256, "row_itemsize": 2,
                                   "ops_dtype": "bfloat16"}}]

    def read(name):
        return obs.read({"name": name}, "per_layer")

    assert read("kernel_per_step_s") == pytest.approx(17.655e-3, rel=1e-3)
    assert read("host_gap_per_step_s") == pytest.approx(
        (5.998382 - 5.932718) / 336, rel=1e-3)
    assert read("device_idle_pct") == pytest.approx(1.0947, rel=1e-3)
    assert read("kmeans_stats_fused_roofline") == pytest.approx(86.06, rel=2e-3)
    assert read("kmeans_ell_stats_fused_roofline") is None
    assert read("collective_exposed_per_step_s") == 0.0
    # a configuration that names no step kernel: nothing to read
    obs.loaded["cfg"] = {}
    assert read("kernel_per_step_s") is None
    assert read("host_gap_per_step_s") is None
