"""Telemetry subsystem tests (rabit_tpu.obs + tracker aggregation).

Fast unit coverage for the metrics registry (counters / gauges /
log2-bucket histograms), the bounded event trace (eviction, JSONL and
Chrome-trace round trips) and the structured logger gating — plus
distributed gates: a 4-rank fixed-op
job must report identical op counts and byte totals on every rank
(pysocket and pyrobust), and a soak round with an injected kill must
produce a tracker-aggregated report with per-op latency percentiles and
the documented recovery timeline, renderable by tools/obs_report.py.
"""
import json
import sys
import threading

import numpy as np
import pytest

from rabit_tpu import obs

pytestmark = pytest.mark.obs


# ---------------------------------------------------------------- metrics
def test_counter_and_gauge():
    m = obs.Metrics()
    m.counter("c").inc()
    m.counter("c").inc(4)
    m.gauge("g").set(2.5)
    snap = m.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == 2.5


def test_counter_thread_safety():
    m = obs.Metrics()

    def work():
        for _ in range(10000):
            m.counter("n").inc()

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert m.counter("n").value == 80000


def test_histogram_welford_matches_numpy():
    h = obs.Histogram()
    rng = np.random.default_rng(0)
    vals = rng.uniform(1e-5, 1e-1, 500)
    for v in vals:
        h.observe(float(v))
    assert h.count == 500
    assert h.mean == pytest.approx(vals.mean(), rel=1e-12)
    assert h.std == pytest.approx(vals.std(), rel=1e-9)
    assert h.max == vals.max()
    assert h.min == vals.min()


def test_histogram_log2_buckets_and_percentiles():
    h = obs.Histogram()
    # one value per octave: percentile estimates must stay within one
    # bucket (factor of 2) of the true order statistics
    for e in range(-10, 0):
        h.observe(1.5 * 2.0 ** e)
    snap = h.snapshot()
    assert sum(snap["buckets"].values()) == 10
    assert len(snap["buckets"]) == 10  # one bucket per octave
    p50 = h.percentile(50)
    true_p50 = 1.5 * 2.0 ** -6
    assert true_p50 / 2 <= p50 <= true_p50 * 2
    assert h.percentile(100) == h.max
    # percentiles never escape the observed range
    assert h.min <= h.percentile(1) <= h.max


def test_histogram_empty():
    h = obs.Histogram()
    assert h.mean == 0.0 and h.std == 0.0 and h.percentile(99) == 0.0
    snap = h.snapshot()
    assert snap["count"] == 0 and snap["min"] == 0.0 and snap["max"] == 0.0


def test_flatten_and_aggregate():
    a, b = obs.Metrics(), obs.Metrics()
    a.counter("op.x.count").inc(3)
    b.counter("op.x.count").inc(5)
    a.histogram("lat").observe(0.5)
    b.histogram("lat").observe(1.5)
    agg = obs.aggregate_snapshots([a.snapshot(), b.snapshot()])
    assert agg["op.x.count"] == {"min": 3.0, "mean": 4.0, "max": 5.0}
    assert agg["lat.mean"]["min"] == 0.5
    assert agg["lat.mean"]["max"] == 1.5


# ------------------------------------------------------------ event trace
def test_ring_buffer_eviction():
    tr = obs.EventTrace(capacity=8)
    for i in range(20):
        tr.emit("op", seqno=i)
    assert len(tr) == 8 and tr.capacity == 8
    assert [e["seqno"] for e in tr.events()] == list(range(12, 20))


def test_trace_jsonl_round_trip():
    tr = obs.EventTrace()
    tr.emit("op", kind="allreduce", nbytes=4096, seqno=1, version=2,
            dur=0.001)
    tr.emit("recovery", phase="link_error", rank=3)
    lines = tr.to_jsonl().splitlines()
    parsed = [json.loads(ln) for ln in lines]
    assert parsed == tr.events()
    assert parsed[0]["kind"] == "allreduce" and parsed[0]["nbytes"] == 4096
    # dur-carrying events are stamped at their START
    assert parsed[0]["ts"] <= parsed[1]["ts"]
    # None-valued fields are dropped, not serialized
    tr2 = obs.EventTrace()
    tr2.emit("op", kind=None, seqno=0)
    assert "kind" not in tr2.events()[0]


def test_chrome_trace_format():
    tr = obs.EventTrace()
    tr.emit("op", kind="allreduce", nbytes=8, dur=0.002, rank=1)
    tr.emit("recovery", phase="rendezvous", rank=0)
    entries = obs.chrome_trace(tr.events())
    spans = [e for e in entries if e["ph"] == "X"]
    instants = [e for e in entries if e["ph"] == "i"]
    assert len(spans) == 1 and len(instants) == 1
    assert spans[0]["dur"] == pytest.approx(2000.0)  # microseconds
    assert spans[0]["pid"] == 1 and instants[0]["pid"] == 0
    assert all(e["ts"] >= 0 for e in entries)


# ---------------------------------------------------------------- logging
def test_logger_debug_gated(capsys):
    log = obs.log.Logger("test", lambda: {"rank": 7})
    obs.log.set_debug(False)
    log.debug("hidden %d", 1)
    log.info("shown %d", 2)
    err = capsys.readouterr().err
    assert "hidden" not in err
    assert "[rabit][test] [rank=7] [INFO] shown 2" in err
    try:
        obs.log.set_debug(True)
        log.debug("now visible")
        assert "now visible" in capsys.readouterr().err
    finally:
        obs.log.set_debug(False)


def test_obs_configure_defaults(monkeypatch):
    monkeypatch.delenv("RABIT_OBS", raising=False)
    monkeypatch.delenv("RABIT_OBS_DIR", raising=False)
    cfg = obs.configure({})
    assert not cfg.enabled and cfg.obs_dir is None
    assert obs.configure({"rabit_obs": "1"}).enabled
    assert not obs.configure({"rabit_obs": "off"}).enabled
    assert obs.configure({"rabit_obs_events": 0}).trace_capacity == 0
    cfg = obs.configure({"rabit_obs_dir": "/tmp/x", "rabit_obs_events": 16})
    assert cfg.enabled and cfg.obs_dir == "/tmp/x"
    assert cfg.trace_capacity == 16


def test_engine_stats_default_empty(empty_engine):
    from rabit_tpu import engine as _em

    eng = _em.get_engine()
    assert eng.stats() == {}
    assert eng.events() == []


def test_tracker_merges_same_rank_summaries(tmp_path):
    """A layered engine ships TWO summaries per rank (the XLA engine's
    device-plane instruments + its host inner's): the tracker must merge
    them section-wise, not overwrite."""
    from rabit_tpu.tracker.tracker import Tracker

    t = Tracker(1, obs_dir=str(tmp_path))
    try:
        t._obs_ingest(json.dumps(
            {"rank": 0, "engine": "PyRobustEngine",
             "metrics": {"counters": {"op.allreduce.count": 3}},
             "recovery": [{"ts": 1.0, "phase": "link_error"}]}))
        t._obs_ingest(json.dumps(
            {"rank": 0, "engine": "XLAEngine",
             "metrics": {"gauges": {"xla.device_ops": 5.0}},
             "recovery": [{"ts": 2.0, "phase": "reform"}]}))
        merged = t._obs_reports[0]
        assert merged["metrics"]["counters"]["op.allreduce.count"] == 3
        assert merged["metrics"]["gauges"]["xla.device_ops"] == 5.0
        assert [e["phase"] for e in merged["recovery"]] == \
            ["link_error", "reform"]
        t._write_obs_report()
        report = json.loads((tmp_path / "obs_report.json").read_text())
        assert report["aggregate"]["xla.device_ops"]["max"] == 5.0
    finally:
        t.stop()


# ------------------------------------------------------------ distributed
@pytest.mark.parametrize("engine", ["pysocket", "pyrobust"])
def test_distributed_counts_agree(engine, tmp_path):
    """A 4-rank fixed-op job must report IDENTICAL op counts and byte
    totals on every rank, and the tracker must aggregate them into the
    per-job report (min == max for every count)."""
    from rabit_tpu.tracker.launch_local import launch

    world, ndata, niter = 4, 600, 3
    code = launch(world, [sys.executable, "tests/workers/obs_worker.py",
                          str(ndata), str(niter)],
                  extra_env={"RABIT_ENGINE": engine},
                  obs_dir=str(tmp_path))
    assert code == 0
    snaps = []
    for r in range(world):
        f = tmp_path / f"stats.rank{r}.json"
        assert f.exists(), f"rank {r} never dumped stats"
        snaps.append(json.loads(f.read_text()))
    counts = [s["counters"]["op.allreduce.count"] for s in snaps]
    byts = [s["counters"]["op.allreduce.bytes"] for s in snaps]
    assert counts == [niter] * world
    assert byts == [niter * ndata * 4] * world
    bcounts = [s["counters"]["op.broadcast.count"] for s in snaps]
    bbytes = [s["counters"]["op.broadcast.bytes"] for s in snaps]
    assert bcounts == [niter] * world
    assert len(set(bbytes)) == 1  # same payload bytes on every rank
    # latency histograms carry percentiles
    lat = snaps[0]["histograms"]["op.allreduce.seconds"]
    assert lat["count"] == niter and 0 < lat["p50"] <= lat["p99"]
    # per-rank event files + the tracker-aggregated report
    for r in range(world):
        assert (tmp_path / f"events.rank{r}.jsonl").exists()
    report = json.loads((tmp_path / "obs_report.json").read_text())
    assert report["ranks_reported"] == list(range(world))
    agg = report["aggregate"]["op.allreduce.count"]
    assert agg["min"] == agg["max"] == niter
    # summed bytes across ranks
    total = sum(json.loads((tmp_path / f"stats.rank{r}.json").read_text())
                ["counters"]["op.allreduce.bytes"] for r in range(world))
    assert total == world * niter * ndata * 4


def test_soak_obs_report_with_kill(tmp_path):
    """Acceptance gate: a 4-rank pyrobust soak round with one injected
    kill writes a tracker-aggregated report containing per-op
    count/bytes/latency percentiles for all ranks AND a recovery
    timeline matching the documented phase sequence; obs_report renders
    it (and a Chrome trace) without error."""
    from rabit_tpu.tools import obs_report, soak

    # seed 3 -> kill point 1,2,1,0 (rank 1 dies at v2 seq1): fires
    # MID-span, so the relaunched rank must be REPLAYED the cached
    # seq-0 result and the timeline shows the full documented arc.
    rc = soak.main(["--world", "4", "--rounds", "1", "--seed", "3",
                    "--kills", "1", "--engine", "pyrobust",
                    "--ndata", "400", "--niter", "3",
                    "--obs-dir", str(tmp_path)])
    assert rc == 0
    round_dir = tmp_path / "round0"
    report = json.loads((round_dir / "obs_report.json").read_text())
    assert report["ranks_reported"] == [0, 1, 2, 3]
    for rank in "0123":
        hists = report["ranks"][rank]["metrics"]["histograms"]
        lat = hists["op.allreduce.seconds"]
        assert lat["count"] > 0 and lat["p50"] > 0 and lat["p99"] > 0
        assert report["ranks"][rank]["metrics"]["counters"][
            "op.allreduce.bytes"] > 0
    phases = [e["phase"] for e in report["recovery_timeline"]]
    # the documented protocol order, as a subsequence of the merged
    # timeline (doc/observability.md)
    it = iter(phases)
    assert all(p in it for p in
               ["link_error", "rendezvous", "replay", "resume"]), phases
    # the report and the per-rank event dumps render cleanly
    assert obs_report.main([str(round_dir),
                            "--chrome", str(tmp_path / "trace.json")]) == 0
    trace = json.loads((tmp_path / "trace.json").read_text())
    assert trace["traceEvents"], "Chrome trace is empty"
    assert {e["ph"] for e in trace["traceEvents"]} <= {"X", "i"}
