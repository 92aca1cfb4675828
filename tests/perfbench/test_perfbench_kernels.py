"""The yardstick's arithmetic: operations and bytes of each kernel from
the cells' shapes, against values worked by hand; the table of peaks;
which bound a roofline share is held to."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness, readers  # noqa: E402

BENCH = os.path.join(ROOT, "perfbench")
PEAKS = harness.read_json(os.path.join(BENCH, "peaks.json"))
V5E = PEAKS["TPU v5 lite"]
DENSE = {"rows": 24117248, "k": 64, "nnz": 32, "dim_staged": 256,
         "row_itemsize": 2, "ops_dtype": "bfloat16"}
SPARSE = {"rows": 33554432, "k": 64, "nnz": 32, "dim_staged": 512,
          "row_itemsize": 4, "ops_dtype": "bfloat16"}


def cost(kernel, shape):
    return harness.load_module(
        os.path.join(BENCH, "kernels", kernel + ".py")).cost(shape)


def test_v5e_peaks_are_the_published_ones():
    assert V5E["flops_per_s"]["bfloat16"] == 197e12
    assert V5E["hbm_bytes_per_s"] == 819e9
    assert V5E["ici_bits_per_s"] == 1600e9
    assert V5E["source"]


def test_dense_kernel_cost_by_hand():
    c = cost("kmeans_stats_fused", DENSE)
    # rows x (similarity 2*256*64 + one add of the row, 256)
    assert c["ops"] == 24117248 * (32768 + 256) == 796447997952
    # bf16 rows + f32 validity + centroids in and stats out (64 x 257 x 4)
    assert c["bytes"] == 24117248 * 512 + 24117248 * 4 + 2 * 64 * 257 * 4
    assert c["bytes"] == 12444631552


def test_dense_kernel_is_hbm_bound_at_15_ms():
    c = cost("kmeans_stats_fused", DENSE)
    by_ops = c["ops"] / 197e12
    by_bytes = c["bytes"] / 819e9
    assert by_ops == pytest.approx(4.04e-3, rel=1e-2)
    assert by_bytes == pytest.approx(15.19e-3, rel=1e-2)
    assert readers.bound_of(BENCH, "kmeans_stats_fused", DENSE, V5E) == "hbm"


def test_ell_kernel_cost_by_hand():
    c = cost("kmeans_ell_stats_fused", SPARSE)
    # the SPARSE algorithm: 32 stored values x 64 centroids x 2, + 32 adds
    assert c["ops"] == 33554432 * (4096 + 32) == 138512695296
    # 32 x (int32 + f32) a row, validity, centroids in and stats out
    assert c["bytes"] == 33554432 * 256 + 33554432 * 4 + 2 * 64 * 513 * 4
    assert readers.bound_of(BENCH, "kmeans_ell_stats_fused", SPARSE,
                            V5E) == "hbm"
    assert c["bytes"] / 819e9 == pytest.approx(10.65e-3, rel=1e-2)


def test_the_rebuild_is_not_counted_as_needed_work():
    """Counting the kernel's dense rebuild (4*d*k a row) would put the
    ELL kernel's floor at 22 ms and flatter its share threefold."""
    c = cost("kmeans_ell_stats_fused", SPARSE)
    assert c["ops"] < 0.05 * 4.0 * 33554432 * 512 * 64


class FakeObserved(readers.Observed):
    def __init__(self, kind, ranks):
        self.loaded = {"bench_dir": BENCH}
        self.bench_dir = BENCH
        self.ranks = ranks
        self._peaks = None


def rank(seconds, calls, name, shape, kind="TPU v5 lite"):
    return {"device": {"kind": kind}, "kernel_shape": shape,
            "trace": {"ops": {name: [seconds, calls]}}}


def test_roofline_reader_dense_at_the_measured_kernel_time():
    # 336 calls in 5.932 s (my chip run, PR 23): 17.65 ms a call
    obs = FakeObserved("TPU v5 lite", [rank(
        5.932103735, 336, "run/_stats_call:custom-call", DENSE)])
    share = readers.roofline(obs, {
        "kernel": "kmeans_stats_fused",
        "pattern": "/_stats_call:custom-call"})
    assert share == pytest.approx(100 * 15.195e-3 / 17.655e-3, rel=2e-3)
    assert share < 100


def test_roofline_reader_keeps_the_two_kernels_apart():
    obs = FakeObserved("TPU v5 lite", [rank(
        1.0, 10, "run/_ell_stats_call:custom-call", SPARSE)])
    assert readers.roofline(obs, {
        "kernel": "kmeans_stats_fused",
        "pattern": "/_stats_call:custom-call"}) is None
    assert readers.roofline(obs, {
        "kernel": "kmeans_ell_stats_fused",
        "pattern": "/_ell_stats_call:custom-call"}) == pytest.approx(
            100 * 10 * 10.654e-3, rel=2e-3)


def test_an_unknown_device_is_an_error_not_a_default():
    obs = FakeObserved("TPU v9", [rank(1.0, 1, "x", DENSE, kind="TPU v9")])
    with pytest.raises(KeyError, match="no peaks for device kind"):
        obs.peaks()


def test_a_reader_with_nothing_to_read_returns_nothing():
    obs = FakeObserved("TPU v5 lite", [{
        "device": {"kind": "TPU v5 lite"}, "kernel_shape": DENSE,
        "trace": None, "spans": {}, "commit_s": [], "path_stats": {},
        "memory": {}, "resume_s": None, "version_gaps": [0.1] * 5}])
    assert readers.roofline(obs, {"kernel": "kmeans_stats_fused",
                                  "pattern": "x"}) is None
    assert readers.span(obs, {"span": "stage", "reduce": "first"}) is None
    assert readers.counter_share(obs, {
        "part": "host_ops", "whole": ["host_ops", "device_ops"]}) is None
    assert readers.memory(obs, {"key": "peak_bytes_in_use"}) is None
    assert readers.trace_idle_pct(obs, {}) is None
    assert readers.version_gap(obs, {"reduce": "p95",
                                     "min_samples": 200}) is None
    assert readers.field(obs, {"field": "resume_s", "over": "max"}) is None
