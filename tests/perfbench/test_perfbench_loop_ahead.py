"""``loop_ahead_per_version``: the share of a job's versions whose stats
program the distributed loop enqueued before it committed the version
before (``learn.ahead`` over ``learn.versions``, in per cent).  Its
reader is data (``layers/loop_ahead_per_version.json``); here on
hand-made tables, and in the four-rank cell rehearsed on the CPU."""
import json
import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearsal  # noqa: E402

sys.path.insert(0, rehearsal.ROOT)

from perfbench import harness, readers  # noqa: E402

NAME = "loop_ahead_per_version"
CELL = "kmeans-dense-periter-x4"
SPEC = harness.read_json(os.path.join(
    rehearsal.ROOT, "perfbench", "layers", NAME + ".json"))


def ranks(*tables):
    return SimpleNamespace(ranks=[{"path_stats": t} for t in tables])


@pytest.mark.parametrize("tables,want", [
    # every version but the first of a run(), on both ranks
    (({"learn.versions": 200, "learn.ahead": 199},
      {"learn.versions": 200, "learn.ahead": 199}), 99.5),
    # a program whose loop commits before it dispatches (the parent of
    # the PR that moved the dispatch): no version was enqueued ahead
    (({"learn.versions": 50, "learn.iterations": 50},), 0.0),
])
def test_reader_on_a_hand_made_table(tables, want):
    got = readers.KINDS[SPEC["kind"]](ranks(*tables), SPEC)
    assert got == pytest.approx(want, rel=1e-12)


def test_the_metric_is_the_x4_cells_alone_and_moves_its_rate():
    manifest = json.load(open(os.path.join(rehearsal.ROOT, "BENCHMARK.json")))
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "rows_per_s" and entry["better"] == "higher"
    assert entry["source"] == "program_counter"
    assert entry["layer"] == SPEC["layer"] == "learner loop"


def test_four_ranks_enqueue_every_version_but_the_first_ahead():
    """The traced cell rehearsed: four processes under the tracker on the
    XLA engine.  Every version of the timed ``run()`` but its first was
    enqueued ahead, on every rank, and none was dropped."""
    proc, line = rehearsal.run(rehearsal.cell_args(CELL, 1, seed=2 ** 31 + 25))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] and line["failed"] == 0
    value = line["metrics"][NAME]["value"]
    # the table holds the warm-up versions too, and the last one counted
    # may be the one the window closed in
    versions = line["attempted"] + 3
    assert 100.0 * (versions - 1) / (versions + 1) <= value < 100.0
