"""The metrics that read the span table's ``self_s`` and ``exposed_s``
columns, the split fetch and the phases of ``init.group`` (PR 35): each
is data (``layers/<name>.json``, kind ``counter_share``) and is read
here off a hand-made ``path_stats``, off the table of a program that has
the spans but not the columns (its parent: every share reads 0), and off
no table at all (nothing to read)."""
import json
import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearsal  # noqa: E402

sys.path.insert(0, rehearsal.ROOT)

from perfbench import harness, readers  # noqa: E402

MANIFEST = json.load(open(os.path.join(rehearsal.ROOT, "BENCHMARK.json")))
ENTRY = {m["name"]: m for m in MANIFEST["per_layer"]}
CELLS = [w["name"] for w in MANIFEST["workloads"]]
KMEANS = [c for c in CELLS if c.startswith("kmeans-")]
X4 = ["kmeans-dense-periter-x4"]
BOOSTING = ["gbdt-hist-round-x1", "gbdt-bosch-round-x1"]
LBFGS = ["lbfgs-logreg-iter-x1"]
LOOP, HOST, ENGINE = "learner loop", "host control plane", "engine dispatch"


def span(name, n, total, own=None, exposed=None):
    out = {name + ".n": n, name + ".total_s": total, name + ".max_s": total}
    if own is not None:
        out.update({name + ".self_s": own, name + ".exposed_s": exposed})
    return out


# the spans the PR added; its parent has the others
NEW_SPANS = {"learn.fetch.wait", "learn.fetch.copy", "init.group.service",
             "init.group.barrier", "init.group.connect", "init.group.mesh"}


def table(columns: bool, slow: float = 1.0) -> dict:
    """One rank's table after 100 steps of 0.1 s in which every learner's
    spans ran once a step; ``columns``: with the two new columns.  The
    second rank (``slow`` 2) was exposed twice as long in every span."""
    def cols(own, exposed):
        return (own, exposed * slow) if columns else (None, None)

    stats = {"learn.versions": 100}
    loop = (("learn.step", 10.0, 0.5, 0.4),
            ("learn.fetch", 8.6, 0.01, 0.1),
            ("learn.fetch.wait", 8.5, 8.5, 0.0),
            ("learn.fetch.copy", 0.09, 0.09, 0.08),
            ("learn.dispatch", 0.3, 0.3, 0.03),
            ("learn.update", 0.2, 0.2, 0.02),
            ("commit", 1.4, 0.1, 0.015),
            ("allreduce", 0.5, 0.1, 0.005),
            ("gbdt.split", 0.06, 0.06, 0.06),
            ("gbdt.partition", 0.01, 0.01, 0.001),
            ("lbfgs.direction", 0.36, 0.01, 0.35),
            ("lbfgs.linesearch", 4.7, 0.07, 0.05))
    setup = (("init.group", 20.0 * slow),
             ("init.group.service", 3.0 * slow),
             ("init.group.barrier", 0.02),
             ("init.group.connect", 1.0),
             ("init.group.mesh", 15.0 * slow))
    for name, total, own, exposed in loop:
        if columns or name not in NEW_SPANS:
            stats.update(span(name, 100, total, *cols(own, exposed)))
    for name, total in setup:
        if columns or name not in NEW_SPANS:
            stats.update(span(name, 1, total, *cols(total, total)))
    return stats


# name -> (value on the two-rank table, cells, layer, moves, better)
WANT = {
    "loop_exposed_share_pct": (6.0, CELLS, LOOP, "rows_per_s", "lower"),
    "loop_wait_share_pct": (85.0, CELLS, LOOP, "rows_per_s", "higher"),
    "loop_fetch_copy_exposed_share_pct": (
        1.2, CELLS, LOOP, "rows_per_s", "lower"),
    "loop_dispatch_exposed_share_pct": (
        0.45, CELLS, LOOP, "rows_per_s", "lower"),
    "loop_update_exposed_share_pct": (
        0.3, KMEANS, LOOP, "rows_per_s", "lower"),
    "commit_exposed_share_pct": (0.225, CELLS, HOST, "rows_per_s", "lower"),
    "allreduce_exposed_share_pct": (
        0.075, X4 + LBFGS + BOOSTING, ENGINE, "rows_per_s", "lower"),
    "gbdt_split_exposed_share_pct": (
        0.9, BOOSTING, LOOP, "rows_per_s", "lower"),
    "gbdt_partition_exposed_share_pct": (
        0.015, BOOSTING, LOOP, "rows_per_s", "lower"),
    "lbfgs_direction_exposed_share_pct": (
        5.25, LBFGS, LOOP, "rows_per_s", "lower"),
    "lbfgs_linesearch_self_share_pct": (
        0.7, LBFGS, LOOP, "rows_per_s", "lower"),
    # set-up: the rank on which a phase took longest, over that rank's
    # whole (the second rank's service round and mesh took twice as long)
    "init_group_service_share_pct": (15.0, X4, HOST, "setup_s", "lower"),
    "init_group_barrier_share_pct": (0.1, X4, HOST, "setup_s", "lower"),
    "init_group_connect_share_pct": (5.0, X4, HOST, "setup_s", "lower"),
    "init_group_mesh_share_pct": (75.0, X4, HOST, "setup_s", "lower"),
}


def read(name: str, *tables):
    spec = harness.read_json(os.path.join(
        rehearsal.ROOT, "perfbench", "layers", name + ".json"))
    assert spec["kind"] == "counter_share"
    return readers.counter_share(SimpleNamespace(
        ranks=[{"path_stats": t} for t in tables]), spec)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_hand_made_table(name):
    got = read(name, table(True), table(True, slow=2.0))
    assert got == pytest.approx(WANT[name][0], rel=1e-9)


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_program_without_the_columns_reads_zero_and_none_without_a_table(
        name):
    """The parent of PR 35 has ``learn.step`` and ``init.group`` but no
    ``exposed_s``, no ``self_s``, no split fetch and no phase of
    ``init.group``: a ``counter_share`` whose part is absent reads 0.
    ``loop_wait_share_pct`` reads 0 there too."""
    assert read(name, table(False), table(False, slow=2.0)) == 0.0
    assert read(name, {"device_ops": 10, "host_ops": 0}, {}) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_the_entry_names_its_cells_its_layer_and_what_it_moves(name):
    _value, cells, layer, moves, better = WANT[name]
    entry = ENTRY[name]
    assert entry["workloads"] == cells
    assert entry["layer"] == layer and entry["moves"] == moves
    assert entry["better"] == better and entry["unit"] == "%"
    assert entry["source"] == "program_span"
    assert not os.path.exists(os.path.join(
        rehearsal.ROOT, "perfbench", "layers", name + ".py"))


def test_every_cell_prints_the_loops_exposed_share():
    for cell in CELLS:
        names = {m["name"] for m in harness.metrics_of(
            harness.load_cell(cell), "per_layer")}
        assert {"loop_exposed_share_pct", "loop_wait_share_pct",
                "commit_exposed_share_pct", "device_idle_pct"} <= names
