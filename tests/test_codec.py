"""Quantized wire codecs (doc/performance.md "Quantized wire codecs").

The contracts pinned here:

* quantize/dequantize round-trips — ``deq(encode(x)) + residual == x``
  bitwise for int8/int4 at ragged sizes (padding tail, zero blocks,
  constant blocks), the bf16 codec byte-identical to the historical
  ``rabit_wire_dtype=bf16`` cast, and ``wire_nbytes`` reporting the
  TRUE encoded size (the honest dispatch accounting that replaced the
  hardcoded ``nbytes //= 2`` special case);
* the hop-path merge is symmetric (both sides of an exchange-schedule
  pairing produce identical bits) and the error-feedback buffer is
  transactional + bounded;
* parameter resolution — the ``rabit_wire_codec`` vocabulary, the
  deprecated ``rabit_wire_dtype=bf16`` alias, block/floor validation;
* the TuningCache codec dimension: rows keyed per codec never answer
  another codec's lookups (mirroring the transport dimension);
* accuracy gates per codec across worlds {2,4,5}: parity vs an in-run
  ``codec=False`` f32 oracle within the documented envelope on every
  schedule, bit-exactness below the size floor and for opted-out ops,
  error-feedback convergence on a repeated-allreduce stream (no
  drift), fused/async buckets with a mixed opt-in/opt-out stream;
* pyrobust kill-point replay with a codec armed: the replayed op is
  bit-identical to the cached result on every rank.
"""
import os
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.codec

CODEC_WORLDS = [2, 4, 5]


def _launch(worker, world, extra_env=None, args=(), tracker_groups=None):
    from rabit_tpu.tracker.launch_local import launch

    saved = os.environ.get("RABIT_TRACKER_GROUPS")
    try:
        if tracker_groups is not None:
            os.environ["RABIT_TRACKER_GROUPS"] = tracker_groups
        else:
            os.environ.pop("RABIT_TRACKER_GROUPS", None)
        return launch(world, [sys.executable,
                              f"tests/workers/{worker}.py",
                              *map(str, args)], extra_env=extra_env or {})
    finally:
        if saved is None:
            os.environ.pop("RABIT_TRACKER_GROUPS", None)
        else:
            os.environ["RABIT_TRACKER_GROUPS"] = saved


# ------------------------------------------------------------ round trips
@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("n", [1, 63, 64, 65, 1000, 4096])
def test_blockscale_roundtrip_exact(bits, n):
    """``deq(wire) + enc_res == x`` BITWISE: the residual is computed
    from the same f32 products the dequantize produces, so error
    feedback carries exactly what the wire dropped."""
    from rabit_tpu.codec.blockscale import BlockScaleCodec
    from rabit_tpu.codec.feedback import FeedbackBuffer

    c = BlockScaleCodec(bits, 64, 0)
    rng = np.random.default_rng(n * bits)
    x = rng.standard_normal(n).astype(np.float32)
    st = c.begin(x.copy(), FeedbackBuffer())
    recon = c._deq(st.wire).reshape(-1)[:n] + st.enc_res.reshape(-1)[:n]
    np.testing.assert_array_equal(recon, x)


@pytest.mark.parametrize("bits", [8, 4])
def test_blockscale_edge_blocks(bits):
    """Zero blocks (scale 0) and constant blocks survive exactly-ish:
    a zero block decodes to exact zeros, a constant block to within
    one quantization step."""
    from rabit_tpu.codec.blockscale import BlockScaleCodec
    from rabit_tpu.codec.feedback import FeedbackBuffer

    c = BlockScaleCodec(bits, 64, 0)
    x = np.zeros(128, np.float32)
    st = c.begin(x.copy(), FeedbackBuffer())
    assert not np.any(c._deq(st.wire))
    x = np.full(128, 3.25, np.float32)
    st = c.begin(x.copy(), FeedbackBuffer())
    step = 3.25 / c.qmax
    assert np.abs(c._deq(st.wire).reshape(-1) - 3.25).max() <= step


def test_wire_nbytes_honest():
    """``wire_nbytes`` must equal the ACTUAL encoded byte count — it is
    what schedule selection and the adaptive controller account."""
    from rabit_tpu.codec.base import Bf16Codec
    from rabit_tpu.codec.blockscale import BlockScaleCodec
    from rabit_tpu.codec.feedback import FeedbackBuffer

    assert Bf16Codec().wire_nbytes(1024) == 512  # the historical //= 2
    for bits in (8, 4):
        c = BlockScaleCodec(bits, 64, 0)
        for n in (1, 64, 65, 1000):
            st = c.begin(np.ones(n, np.float32), FeedbackBuffer())
            assert c.wire_nbytes(n * 4) == st.wire.nbytes, (bits, n)
    # int8: 64 payload + 4 scale per 64 f32 = 68/256 ≈ 0.27x
    assert BlockScaleCodec(8, 64, 0).wire_nbytes(256 << 10) \
        == (256 << 10) * 68 // 256


def test_bf16_codec_matches_historical_cast():
    """The refactored Bf16Codec must produce the byte stream of the
    old inline cast: astype(bfloat16).view(uint16)."""
    import ml_dtypes

    from rabit_tpu.codec.base import Bf16Codec

    x = np.random.default_rng(0).standard_normal(257).astype(np.float32)
    w, red = Bf16Codec().encode(x)
    assert red == np.dtype(ml_dtypes.bfloat16)
    expect = x.astype(ml_dtypes.bfloat16).view(np.uint16)
    np.testing.assert_array_equal(w, expect)
    back = Bf16Codec().decode(w, red)
    np.testing.assert_array_equal(
        back, x.astype(ml_dtypes.bfloat16).astype(np.float32))


@pytest.mark.parametrize("bits", [8, 4])
def test_merge_symmetric(bits):
    """Exchange schedules (halving, swing) requantize the SAME
    accumulated values on both sides of a pairing: the merged wire
    blocks must be bit-identical, or cross-rank parity would break."""
    from rabit_tpu.codec.blockscale import BlockScaleCodec
    from rabit_tpu.codec.feedback import FeedbackBuffer

    c = BlockScaleCodec(bits, 64, 0)
    rng = np.random.default_rng(bits)
    x = rng.standard_normal(1000).astype(np.float32)
    y = rng.standard_normal(1000).astype(np.float32)
    sa = c.begin(x.copy(), FeedbackBuffer())
    sb = c.begin(y.copy(), FeedbackBuffer())
    # side A merges B's wire into its own; side B merges A's into its
    # own — both must land on identical bits.
    a, b = sa.wire.copy(), sb.wire.copy()
    c.merge(sa, a, 0, len(a), sb.wire)
    c.merge(sb, b, 0, len(b), sa.wire)
    assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("bits", [8, 4])
def test_merge_record_flag_skips_ledger_only(bits):
    """``record=False`` (swing's non-recording side of a replicated
    pairing) must merge IDENTICAL bytes while leaving the hop ledger
    untouched — one quantization event, one ledger entry, never two."""
    from rabit_tpu.codec.blockscale import BlockScaleCodec
    from rabit_tpu.codec.feedback import FeedbackBuffer

    c = BlockScaleCodec(bits, 64, 0)
    rng = np.random.default_rng(bits)
    x = rng.standard_normal(500).astype(np.float32)
    y = rng.standard_normal(500).astype(np.float32)
    sa = c.begin(x.copy(), FeedbackBuffer())
    sb = c.begin(x.copy(), FeedbackBuffer())
    src = c.begin(y.copy(), FeedbackBuffer()).wire
    a, b = sa.wire.copy(), sb.wire.copy()
    c.merge(sa, a, 0, len(a), src, True)
    c.merge(sb, b, 0, len(b), src, False)
    assert a.tobytes() == b.tobytes()
    assert np.any(sa.hop) and not np.any(sb.hop)


# ------------------------------------------------------- error feedback
def test_feedback_buffer_transactional_and_bounded():
    from rabit_tpu.codec.feedback import FeedbackBuffer

    fb = FeedbackBuffer(max_streams=2)
    assert fb.residual(("int8", 10)) is None
    r = np.ones(10, np.float32)
    fb.commit(("int8", 10), r)
    np.testing.assert_array_equal(fb.residual(("int8", 10)), r)
    # LRU bound: a third stream evicts the least-recently-used.
    fb.commit(("int8", 20), np.ones(20, np.float32))
    fb.residual(("int8", 10))  # touch: 20 is now LRU
    fb.commit(("int8", 30), np.ones(30, np.float32))
    assert fb.residual(("int8", 20)) is None
    assert fb.residual(("int8", 10)) is not None
    assert len(fb) == 2


def test_begin_never_mutates_feedback():
    """``begin`` reads the carried residual but must not advance it —
    pyrobust retries re-encode identical wire bytes."""
    from rabit_tpu.codec.blockscale import BlockScaleCodec
    from rabit_tpu.codec.feedback import FeedbackBuffer

    c = BlockScaleCodec(8, 64, 0)
    fb = FeedbackBuffer()
    x = np.random.default_rng(3).standard_normal(500).astype(np.float32)
    fb.commit(("int8", 500), np.full(500, 0.01, np.float32))
    before = fb.residual(("int8", 500)).copy()
    s1 = c.begin(x.copy(), fb)
    s2 = c.begin(x.copy(), fb)
    np.testing.assert_array_equal(fb.residual(("int8", 500)), before)
    assert s1.wire.tobytes() == s2.wire.tobytes()


# ------------------------------------------------------------- resolution
def test_factory_vocabulary_and_alias():
    from rabit_tpu import codec as codec_mod
    from rabit_tpu.utils.checks import RabitError

    assert codec_mod.resolve(None, "native", None, 4096) is None
    assert codec_mod.resolve("none", "bf16", None, 4096) is None
    assert codec_mod.resolve(None, "bf16", None, 4096).name == "bf16"
    c = codec_mod.resolve("int8", "native", 128, 1 << 20)
    assert (c.name, c.block, c.min_bytes) == ("int8", 128, 1 << 20)
    assert codec_mod.resolve("int4", "bf16", None, 0).name == "int4"
    # fp8 family: canonical names plus the short alias
    assert codec_mod.resolve("fp8e4m3", "native", None, 0).name == "fp8e4m3"
    assert codec_mod.resolve("fp8e5m2", "native", None, 0).name == "fp8e5m2"
    assert codec_mod.make("fp8").name == "fp8e4m3"
    with pytest.raises(RabitError):
        codec_mod.make("fp7")
    with pytest.raises(RabitError):
        codec_mod.make("int8", block=3)  # odd
    with pytest.raises(RabitError):
        codec_mod.make("int8", block=8192)  # too large
    with pytest.raises(RabitError):
        codec_mod.make("int8", min_bytes=-1)


def test_eligibility_is_replicated_config():
    """Eligibility sees only replicated inputs: dtype, op, size, the
    uniform codec config — f64/MAX/sub-floor payloads ride classic."""
    from rabit_tpu import codec as codec_mod
    from rabit_tpu.ops import MAX, SUM

    c = codec_mod.make("int8")
    assert c.eligible(np.float32, SUM, 1 << 20)
    assert not c.eligible(np.float64, SUM, 1 << 20)
    assert not c.eligible(np.float32, MAX, 1 << 20)
    assert not c.eligible(np.float32, SUM, 100)  # under the floor
    b = codec_mod.make("bf16")
    assert b.eligible(np.float32, SUM, 4)  # bf16 has no floor


# ------------------------------------------------------ tuner dimension
def test_tuning_cache_codec_dimension(tmp_path):
    """Codec-keyed rows are isolated per codec — picks never bleed
    across wire formats."""
    from rabit_tpu.sched.tuner import TuningCache

    assert TuningCache.table_kind("allreduce") == "allreduce"
    assert TuningCache.table_kind("allreduce", "none") == "allreduce"
    assert TuningCache.table_kind("allreduce", "int8") \
        == "allreduce+int8"
    f32 = TuningCache.from_bench({"4096": {"tree": 100.0, "ring": 10.0}},
                                 4, candidates={"tree", "ring"})
    q = TuningCache.from_bench({"4096": {"tree": 10.0, "ring": 100.0}},
                               4, candidates={"tree", "ring"},
                               codec="int8")
    f32.table.update(q.table)
    f32.save(str(tmp_path))
    cache = TuningCache.load(str(tmp_path))
    assert cache.pick("allreduce", 4096, 4) == "tree"
    assert cache.pick("allreduce", 4096, 4, codec="none") == "tree"
    assert cache.pick("allreduce", 4096, 4, codec="int8") == "ring"
    assert cache.pick("allreduce", 4096, 4, codec="int4") is None
    cache.merge_online("allreduce", 6, 8192, "swing", codec="int4")
    assert cache.pick("allreduce", 8192, 6, codec="int4") == "swing"
    # The none-codec pick at world 6 must NOT see int4's world-6 row:
    # it takes the nearest-world fallback to the f32 rows instead.
    assert cache.pick("allreduce", 8192, 6) == "tree"
    assert cache.pick("allreduce", 8192, 6, codec="bf16") is None


def test_span_costs_scoped_by_wire_format():
    """The controller's schedule evidence is scoped per wire format:
    full-width spans (per-op opt-outs, ineligible dtypes, pre-codec
    8-field emitters) never feed the codec-keyed cost windows, and
    vice versa."""
    from rabit_tpu.obs.span import SpanMerger

    m = SpanMerger()
    # int8-wire op (seq 0) and a full-width opt-out op (seq 1), plus a
    # legacy 8-field span (seq 2) from a pre-codec emitter.
    for rank, d in ((0, 0.0), (1, 0.1)):
        m.add(rank, [[0, 0, 0, "allreduce", "ring", 1 << 20,
                      10.0 + d, 11.0 + d, "int8"]], 2)
        m.add(rank, [[1, 0, 0, "allreduce", "ring", 1 << 20,
                      12.0 + d, 15.0 + d, "none"]], 2)
        m.add(rank, [[2, 0, 0, "allreduce", "ring", 1 << 20,
                      16.0 + d, 19.0 + d]], 2)
    int8 = m.sched_costs("int8")
    none = m.sched_costs("none")
    assert int8[("ring", 1 << 20)]["n"] == 1
    assert none[("ring", 1 << 20)]["n"] == 2  # opt-out + legacy span
    assert abs(int8[("ring", 1 << 20)]["mean_sec"] - 1.0) < 1e-6
    assert abs(none[("ring", 1 << 20)]["mean_sec"] - 3.0) < 1e-6
    assert m.sched_costs("int4") == {}


# ------------------------------------------------- the accuracy matrix
# Tier-1 budget (ISSUE 15 satellite): bf16 + int8 (the headline wire)
# are the fast codec-axis representatives; the int4 end-to-end cell
# rides `-m slow` with the worlds matrix — its quantize/merge
# exactness stays covered by the fast round-trip units above.
@pytest.mark.parametrize("codec", [
    "bf16", "int8",
    pytest.param("int4", marks=pytest.mark.slow),
    "fp8e4m3",
    pytest.param("fp8e5m2", marks=pytest.mark.slow)])
def test_codec_accuracy_world4(codec):
    """The flagship world: every schedule (incl. hier via a two-host
    group handout), the EF stream, fused/async and the mixed
    opt-in/opt-out bucket — all against the in-run f32 oracle."""
    assert _launch("codec_worker", 4,
                   extra_env={"RABIT_ENGINE": "pysocket",
                              "RABIT_WIRE_CODEC": codec},
                   tracker_groups="0,0,1,1") == 0


@pytest.mark.slow
@pytest.mark.parametrize("codec", ["bf16", "int8", "int4",
                                   "fp8e4m3", "fp8e5m2"])
@pytest.mark.parametrize("world", [2, 5])
def test_codec_accuracy_worlds(codec, world):
    """The rest of the {2,4,5} worlds matrix (world 4 runs fast above):
    odd worlds hit the ragged block partitions, world 2 the static
    tree-only dispatch."""
    assert _launch("codec_worker", world,
                   extra_env={"RABIT_ENGINE": "pysocket",
                              "RABIT_WIRE_CODEC": codec}) == 0


def test_codec_robust_replay_bit_identical():
    """Kill-point replay with int8 armed: the relaunched rank's
    replayed op must serve the EXACT cached bytes (fingerprinted,
    cross-rank agreed) — the codec composes below the cache."""
    assert _launch("codec_replay", 3,
                   extra_env={"RABIT_ENGINE": "pyrobust",
                              "RABIT_WIRE_CODEC": "int8",
                              "RABIT_MOCK": "1,0,1,0"}) == 0


# ------------------------------------------------- learn end-to-end
def _learn_workers_runnable() -> bool:
    """The learn workers pin ``jax_num_cpu_devices`` at import; on jax
    versions without that option they cannot start at all (the same
    environmental condition that fails test_boosting/test_learn_dist's
    distributed cases).  These gates run exactly where those do."""
    import subprocess

    probe = ("import jax; "
             "jax.config.update('jax_num_cpu_devices', 1)")
    return subprocess.run(
        [sys.executable, "-c", probe], capture_output=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}).returncode == 0


def test_boosting_histogram_int8_end_to_end(tmp_path):
    """Boosting trains over int8-quantized histogram allreduces (the
    bulk traffic the codec targets, deliberately opted IN): split
    decisions taken on the quantized sums still learn the function to
    the same accuracy gate as the f32 run, and the model is identical
    on every rank (the quantized wire is deterministic + replicated —
    the worker's allgather parity check pins it)."""
    if not _learn_workers_runnable():
        pytest.skip("learn workers cannot start on this jax "
                    "(jax_num_cpu_devices unsupported)")
    rng = np.random.default_rng(0)
    X = rng.uniform(-1, 1, (600, 2)).astype(np.float32)
    y = ((X[:, 0] > 0) ^ (X[:, 1] > 0)).astype(np.float32)
    np.save(tmp_path / "X.npy", X)
    np.save(tmp_path / "y.npy", y)
    assert _launch("boosting_dist", 2, args=(str(tmp_path),),
                   extra_env={"RABIT_ENGINE": "pysocket",
                              "RABIT_WIRE_CODEC": "int8",
                              # quantize EVERY histogram level, not
                              # just the ones over the default floor
                              "RABIT_CODEC_MIN_BYTES": "0"}) == 0


def test_lbfgs_opt_out_bit_exact_with_codec(tmp_path):
    """The L-BFGS solver opts every collective out (``codec=False``):
    training with int8 armed must produce a BYTE-identical model to
    the codec-free run — the opt-out keeps the solver on the exact
    classic wire."""
    if not _learn_workers_runnable():
        pytest.skip("learn workers cannot start on this jax "
                    "(jax_num_cpu_devices unsupported)")

    def write_libsvm(path, Xs, ys):
        with open(path, "w") as f:
            for row, label in zip(Xs, ys):
                feats = " ".join(f"{j + 1}:{v:.6f}"
                                 for j, v in enumerate(row))
                f.write(f"{int(label)} {feats}\n")

    world = 2
    rng = np.random.default_rng(7)
    X = rng.standard_normal((160, 6)).astype(np.float32)
    w_true = rng.standard_normal(6)
    y = (1 / (1 + np.exp(-(X @ w_true))) > rng.random(160)).astype(
        np.float32)
    for r in range(world):
        write_libsvm(tmp_path / f"part{r}.libsvm", X[r::world],
                     y[r::world])
    pattern = str(tmp_path / "part%d.libsvm")
    models = {}
    for codec in ("none", "int8"):
        out = str(tmp_path / f"model.{codec}")
        assert _launch("linear_dist", world,
                       args=(pattern, "logistic", out,
                             "reg_L2=0.1", "max_lbfgs_iter=8"),
                       extra_env={"RABIT_ENGINE": "pyrobust",
                                  "RABIT_WIRE_CODEC": codec,
                                  "RABIT_CODEC_MIN_BYTES": "0"}) == 0
        with open(out, "rb") as f:
            models[codec] = f.read()
    assert models["none"] == models["int8"], \
        "lbfgs model changed under an armed codec — opt-out leaked"


def test_codec_counters_surface_in_report():
    """The codec telemetry (ops, logical vs wire bytes, ratio) lands in
    the obs aggregate and obs_report renders the table."""
    import io
    import json

    import rabit_tpu
    from rabit_tpu.tools import obs_report

    if rabit_tpu.initialized():
        rabit_tpu.finalize()
    agg = {"codec.ops": {"min": 4, "mean": 4, "max": 4},
           "codec.ops.int8": {"min": 4, "mean": 4, "max": 4},
           "codec.bytes.logical": {"min": 4e6, "mean": 4e6, "max": 4e6},
           "codec.bytes.wire": {"min": 1.1e6, "mean": 1.1e6,
                                "max": 1.1e6},
           "codec.bytes_saved": {"min": 2.9e6, "mean": 2.9e6,
                                 "max": 2.9e6},
           "codec.feedback.norm.mean": {"min": 0.001, "mean": 0.001,
                                        "max": 0.002}}
    out = io.StringIO()
    obs_report.render_codec(agg, out)
    text = out.getvalue()
    assert "wire codec" in text and "int8" in text
    assert "0.275" in text  # wire/logical ratio
    assert "error-feedback" in text
    json.dumps(agg)  # the shape is the report's aggregate shape
