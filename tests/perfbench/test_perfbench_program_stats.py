"""The readers of the program's own spans and counters on a hand-made
``path_stats``: the mean without the longest call, set-up spans whole,
counters, and nothing to read from a program that has no such table
(the parent of the PR that added it)."""
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

MANIFEST = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
LAYERS = os.path.join(ROOT, "perfbench", "layers")


def span(name, n, total, longest):
    return {name + ".n": n, name + ".total_s": total,
            name + ".max_s": longest}


# two ranks of an x4-like run: 11 calls of every loop span, the first
# the longest; rank 1 a little slower everywhere
def rank(slow: float) -> dict:
    stats = {"init.n": 1, "init.total_s": 30.0, "init.max_s": 30.0,
             "device_ops": 10, "host_ops": 0,
             "learn.iterations": 88, "learn.versions": 11,
             "compile.seconds": 1.5 * slow, "compile.hits": 9,
             # a request counts as a miss until its hit is reported
             "compile.misses": int(4 * slow) - 4}
    for name, each in (("commit.serialize", 1e-4), ("commit.barrier", 1e-3),
                       ("commit.ack", 8e-4), ("commit.reform_flags", 6e-4),
                       ("allreduce.stage", 5e-4),
                       ("allreduce.dispatch", 3e-4),
                       ("learn.update", 2e-4), ("learn.dispatch", 4e-4)):
        stats.update(span(name, 11, (10 * each + 0.5) * slow, 0.5 * slow))
    for name, seconds in (("stage.to_ell", 0.4), ("stage.clamp", 2.0),
                          ("stage.put", 9.0), ("init.group", 20.0)):
        stats.update(span(name, 1, seconds * slow, seconds * slow))
    return {"path_stats": stats}


OBSERVED = SimpleNamespace(ranks=[rank(1.0), rank(1.5)])
WANT = {
    "commit_serialize_s": 1.25e-4, "commit_barrier_s": 1.25e-3,
    "commit_ack_s": 1e-3, "commit_reform_flags_s": 7.5e-4,
    "allreduce_stage_s": 6.25e-4, "allreduce_dispatch_s": 3.75e-4,
    "loop_update_s": 2.5e-4, "loop_dispatch_s": 5e-4,      # mean of ranks
    "loop_iterations_per_version": 8.0,
    "stage_to_ell_s": 0.6, "stage_clamp_s": 3.0, "stage_put_s": 13.5,
    "init_group_s": 30.0, "compile_s": 2.25,               # slowest rank
    "compile_misses": 2,                                   # worst rank
}


def read(name: str, observed):
    return harness.load_module(os.path.join(LAYERS, name + ".py")).read(
        observed)


def test_every_reader_of_the_table_is_in_the_manifest_and_here():
    listed = {m["name"] for m in MANIFEST["per_layer"]}
    assert set(WANT) <= listed
    in_code = {f[:-3] for f in os.listdir(LAYERS) if f.endswith(".py")}
    assert in_code == set(WANT) | {"program_stats"}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_hand_made_table(name):
    assert read(name, OBSERVED) == pytest.approx(WANT[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_in_a_program_without_the_table(name):
    parent = SimpleNamespace(ranks=[
        {"path_stats": {"device_ops": 10, "host_ops": 0}},
        {"path_stats": {}}, {}])
    assert read(name, parent) is None


def test_a_span_called_once_has_no_mean_without_its_longest_call():
    once = SimpleNamespace(ranks=[{"path_stats": span(
        "learn.update", 1, 0.3, 0.3)}])
    assert read("loop_update_s", once) is None
