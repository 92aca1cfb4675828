"""Compiled codec kernels (rabit_tpu/native/src/codec_kernels.c) —
the native<->numpy bit-identity contract behind ``rabit_codec_impl``.

The contracts pinned here:

* the ctypes seam (codec/kernel.py) degrades gracefully: ``numpy``
  forces the reference, ``native`` is LOUD when the library is
  missing, ``auto`` falls back with exactly one obs-visible warning
  and never an ImportError — a toolchain-free box stays green;
* hop math is BIT-identical across the seam for every block format
  (int8 / int4 / fp8e4m3 / fp8e5m2), block size and merge depth —
  wire bytes, hop-residual ledgers, decoded outputs and committed
  feedback residuals all compare bitwise, including the unrecorded
  (swing-style) merge side and adversarial payloads (all-zero and
  mixed-sign-zero blocks, 1e30 / 1e-38 magnitudes);
* the native bf16 elementwise merge matches the ml_dtypes reference
  bit for bit (subnormals, overflow-to-inf, rounding ties, NaN);
* fp8 formats decode exhaustively (all 256 codes) to the ml_dtypes
  ground truth and round-trip within the half-ulp + subnormal-quantum
  error envelope, with honest wire-byte accounting;
* end to end, per-rank result digests are identical for native vs
  numpy vs MIXED worlds across pipeline depths (the impl is not a
  collective decision), and pyrobust kill-point replay with the
  native kernels armed still serves bit-exact cached payloads;
* the resolved backend label reaches the live plane (/status rows,
  rabit_top's codec column) so a silent fallback is visible.

``make -C rabit_tpu/native smoke`` builds the library and runs this
file under ``-m "not slow"``.
"""
import io
import os
import pathlib
import sys

import numpy as np
import pytest

pytestmark = [pytest.mark.codec, pytest.mark.native_codec]

REPO = pathlib.Path(__file__).resolve().parent.parent

FMTS = ["int8", "int4", "fp8e4m3", "fp8e5m2"]


def _kernel():
    from rabit_tpu import codec

    return codec.load()


requires_native = pytest.mark.skipif(
    _kernel() is None,
    reason="librabit_codec.so not built (make -C rabit_tpu/native codec)")


def _launch(worker, world, extra_env=None, args=()):
    from rabit_tpu.tracker.launch_local import launch

    saved = os.environ.pop("RABIT_TRACKER_GROUPS", None)
    try:
        return launch(world, [sys.executable,
                              f"tests/workers/{worker}.py",
                              *map(str, args)], extra_env=extra_env or {})
    finally:
        if saved is not None:
            os.environ["RABIT_TRACKER_GROUPS"] = saved


def _payload(rng, n: int) -> np.ndarray:
    """Adversarial f32 payload: normals salted with exact zeros, signed
    zeros and extreme magnitudes — the cases where C-vs-numpy semantic
    drift (fmaxf vs np.maximum on ±0/NaN, rounding mode) would show."""
    v = rng.standard_normal(n).astype(np.float32)
    v[rng.random(n) < 0.10] = 0.0
    v[rng.random(n) < 0.05] = -0.0
    big = rng.random(n) < 0.05
    v[big] *= np.float32(1e30)
    v[rng.random(n) < 0.05] *= np.float32(1e-38)
    return v


# ----------------------------------------------------------- the seam
def test_resolve_impl_vocabulary():
    from rabit_tpu import codec
    from rabit_tpu.utils import RabitError

    assert codec.resolve_impl("numpy") == (None, "numpy")
    with pytest.raises(RabitError, match="rabit_codec_impl"):
        codec.resolve_impl("fortran")


def test_native_request_is_loud_or_loads():
    from rabit_tpu import codec
    from rabit_tpu.utils import RabitError

    if _kernel() is None:
        # explicit native on a toolchain-free box: a config error that
        # names the build command, never a silent numpy downgrade
        with pytest.raises(RabitError, match="make -C rabit_tpu/native"):
            codec.resolve_impl("native")
        assert codec.load_error()
    else:
        k, label = codec.resolve_impl("native")
        assert k is not None and label == "native"
        k, label = codec.resolve_impl("auto")
        assert k is not None and label == "native"


def test_auto_fallback_warns_exactly_once(monkeypatch):
    from rabit_tpu.codec import kernel as kernel_mod

    # Simulate the toolchain-free box regardless of the real build.
    monkeypatch.setattr(kernel_mod, "_loaded", True)
    monkeypatch.setattr(kernel_mod, "_kernel", None)
    monkeypatch.setattr(kernel_mod, "_load_error", "no lib (simulated)")
    monkeypatch.setattr(kernel_mod, "_warned", False)
    from rabit_tpu.obs.log import Logger

    warnings = []

    class Log(Logger):
        # the engines' real logger class: a method it does not have
        # (this path once called ``log.warning``) must fail here, not
        # at the first engine init on a box without the built library
        def _emit(self, level, fmt, *a):
            warnings.append(fmt % a if a else fmt)

    for _ in range(3):
        k, label = kernel_mod.resolve_impl("auto", log=Log("test"))
        assert k is None and label == "numpy-fallback"
    assert len(warnings) == 1, warnings
    assert "numpy" in warnings[0]


def test_bogus_lib_path_never_imports_error(monkeypatch):
    from rabit_tpu.codec import kernel as kernel_mod

    monkeypatch.setenv("RABIT_CODEC_LIB", "/nonexistent/librabit.so")
    monkeypatch.setattr(kernel_mod, "_loaded", False)
    monkeypatch.setattr(kernel_mod, "_kernel", None)
    monkeypatch.setattr(kernel_mod, "_load_error", None)
    assert kernel_mod.load() is None
    assert "/nonexistent/librabit.so" in kernel_mod.load_error()


# --------------------------------------- bit-identity: the hop math
@requires_native
@pytest.mark.parametrize("block", [2, 8, 64])
@pytest.mark.parametrize("fmt", FMTS)
def test_hop_math_bit_identical(fmt, block):
    """Native and numpy run the same op stream — encode, a chain of
    recorded AND unrecorded merges at ragged chunk offsets, decode,
    residual commit — over a 3-op feedback stream.  Every artifact
    compares bitwise at every step: this is the contract that makes
    ``rabit_codec_impl`` a non-collective knob."""
    from rabit_tpu import codec as codec_mod

    k = _kernel()
    cn = codec_mod.make(fmt, block=block, min_bytes=0, kernel=k)
    cp = codec_mod.make(fmt, block=block, min_bytes=0)
    assert cn.wire_nbytes(4 * 10 * block) == cp.wire_nbytes(4 * 10 * block)
    fbn, fbp = codec_mod.FeedbackBuffer(), codec_mod.FeedbackBuffer()
    rng = np.random.default_rng(5)
    n = 5 * block + block // 2 + 1  # ragged: zero-padded tail block
    base = _payload(rng, n)
    for rnd in range(3):  # the feedback stream advances across ops
        v = base * np.float32(rnd + 1)
        with np.errstate(over="ignore"):
            sn = cn.begin(v.copy(), fbn)
            sp = cp.begin(v.copy(), fbp)
        assert sn.wire.tobytes() == sp.wire.tobytes(), (fmt, block, rnd)
        nblocks = sn.wire.size
        for hop in range(4):  # merge depth: chained peer contributions
            u = _payload(rng, n) * np.float32(hop + 1)
            with np.errstate(over="ignore"):
                pn = cn.begin(u.copy(), codec_mod.FeedbackBuffer())
                pp = cp.begin(u.copy(), codec_mod.FeedbackBuffer())
            assert pn.wire.tobytes() == pp.wire.tobytes()
            e0 = hop % nblocks
            ne = max(1, (nblocks - e0) // (1 + hop % 2))
            record = hop % 2 == 0  # the swing-style unrecorded side too
            with np.errstate(over="ignore"):
                cn.merge(sn, sn.wire, e0, ne, pn.wire[e0:e0 + ne], record)
                cp.merge(sp, sp.wire, e0, ne, pp.wire[e0:e0 + ne], record)
            assert sn.wire.tobytes() == sp.wire.tobytes(), \
                (fmt, block, rnd, hop, record)
            assert np.array_equal(sn.hop, sp.hop), (fmt, block, rnd, hop)
        outn = np.empty(n, np.float32)
        outp = np.empty(n, np.float32)
        rn = cn.finish(sn, outn, fbn)
        rp = cp.finish(sp, outp, fbp)
        assert outn.tobytes() == outp.tobytes(), (fmt, block, rnd)
        assert rn.tobytes() == rp.tobytes(), (fmt, block, rnd)


@requires_native
def test_bf16_elementwise_merge_bit_identical():
    """The native bf16 merge vs the ml_dtypes reference the engine's
    numpy path uses: add in bf16, bit for bit — subnormals, ties,
    overflow-to-inf and NaN quieting included."""
    import ml_dtypes

    from rabit_tpu.codec import kernel as kernel_mod

    bf = np.dtype(ml_dtypes.bfloat16)
    rng = np.random.default_rng(9)
    with np.errstate(over="ignore"):  # 1e38-scale: bf16 overflow cases
        vals = np.concatenate([
            rng.standard_normal(4096).astype(np.float32),
            (rng.standard_normal(4096) * 1e38).astype(np.float32),
            (rng.standard_normal(4096) * 1e-40).astype(np.float32),
            np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 1.0, -1.0],
                     np.float32),
        ])
    a = vals.astype(bf)
    b = vals[::-1].copy().astype(bf)
    with np.errstate(over="ignore"):  # overflow-to-inf is a test case
        want = (a + b).view(np.uint16)
    dst = a.view(np.uint16).copy()
    src = b.view(np.uint16).copy()
    _kernel().bf16_merge(kernel_mod.pu16(dst), kernel_mod.pu16(src),
                         dst.size)
    assert np.array_equal(dst, want)


# ------------------------------------------------------- fp8 formats
@pytest.mark.parametrize("fmt", ["fp8e4m3", "fp8e5m2"])
def test_fp8_decode_exhaustive_all_codes(fmt):
    """Every one of the 256 fp8 codes, at two scales: the numpy path
    IS the ml_dtypes view, and the native path must match it bitwise
    (finite codes) / NaN-for-NaN."""
    from rabit_tpu import codec as codec_mod

    cp = codec_mod.make(fmt, block=256, min_bytes=0)
    wire = np.zeros(2, dtype=cp.block_dtype)
    wire["s"] = [1.0, 0.5]
    wire["q"] = np.arange(256, dtype=np.uint8)
    ref = cp._deq(wire)
    # ground truth straight from ml_dtypes
    truth = wire["q"].view(np.dtype(getattr(
        __import__("ml_dtypes"), codec_mod.FP8_FORMATS[fmt][0]))).astype(
        np.float32) * wire["s"][..., None]
    nan_ref = np.isnan(ref)
    assert np.array_equal(nan_ref, np.isnan(truth))
    assert np.array_equal(ref[~nan_ref], truth[~np.isnan(truth)])
    if _kernel() is not None:
        cn = codec_mod.make(fmt, block=256, min_bytes=0, kernel=_kernel())
        got = cn._deq(wire)
        nan_got = np.isnan(got)
        assert np.array_equal(nan_got, nan_ref)
        assert np.array_equal(
            got.reshape(-1).view(np.uint32)[~nan_got.reshape(-1)],
            ref.reshape(-1).view(np.uint32)[~nan_ref.reshape(-1)])


@pytest.mark.parametrize("fmt,man", [("fp8e4m3", 3), ("fp8e5m2", 2)])
def test_fp8_roundtrip_error_bounds(fmt, man):
    """One encode/decode round trip per magnitude decade: per-element
    error within the half-ulp envelope (2^-(man+1) relative) plus the
    block's subnormal quantum, and the committed residual is exactly
    ``v - decoded`` — the error-feedback invariant."""
    import ml_dtypes

    from rabit_tpu import codec as codec_mod

    mld = np.dtype(getattr(ml_dtypes, codec_mod.FP8_FORMATS[fmt][0]))
    sub = float(ml_dtypes.finfo(mld).smallest_subnormal)
    block = 64
    c = codec_mod.make(fmt, block=block, min_bytes=0)
    rng = np.random.default_rng(11)
    for decade in (1e-3, 1.0, 1e4):
        n = 10 * block + 7
        v = (rng.standard_normal(n) * decade).astype(np.float32)
        st = c.begin(v.copy(), codec_mod.FeedbackBuffer())
        out = np.empty(n, np.float32)
        res = c.finish(st, out, codec_mod.FeedbackBuffer())
        assert np.array_equal(res, v - out)
        scale = np.repeat(st.wire["s"], block)[:n].astype(np.float64)
        err = np.abs(out.astype(np.float64) - v.astype(np.float64))
        bound = np.maximum(np.abs(v) * 2.0 ** -(man + 1) * 1.001,
                           scale * sub)
        assert (err <= bound).all(), (
            fmt, decade, float(err.max()), float(bound[err.argmax()]))


def test_fp8_wire_bytes_honest():
    """fp8's claimed wire size is the structured layout's true size:
    4-byte scale + block bytes per block, ragged tail rounded up — and
    it matches the array the encode actually produces."""
    from rabit_tpu import codec as codec_mod

    c = codec_mod.make("fp8e4m3", block=64, min_bytes=0)
    for n in (1, 63, 64, 65, 1000):
        want = -(-n // 64) * (4 + 64)
        assert c.wire_nbytes(4 * n) == want
        st = c.begin(np.ones(n, np.float32), codec_mod.FeedbackBuffer())
        assert st.wire.nbytes == want


# ---------------------------------------------- end-to-end digest A/B
@requires_native
@pytest.mark.parametrize("codec", [
    "int8", "fp8e4m3",
    pytest.param("int4", marks=pytest.mark.slow),
    pytest.param("fp8e5m2", marks=pytest.mark.slow)])
def test_e2e_digest_parity_native_numpy_mixed(tmp_path, codec):
    """The whole stack, three ways — all-numpy (serial hops), all-native
    (pipelined hops), and a MIXED world (even ranks native, odd numpy)
    — must produce identical per-rank result digests: implementation
    and pipeline depth both leave the byte stream invariant."""
    runs = {"numpy": {"RABIT_CODEC_IMPL": "numpy",
                      "RABIT_PIPELINE_DEPTH": "1"},
            "native": {"RABIT_CODEC_IMPL": "native",
                       "RABIT_PIPELINE_DEPTH": "4"},
            "mixed": {"RABIT_CODEC_IMPL": "numpy",
                      "RABIT_CODEC_IMPL_MIXED": "1",
                      "RABIT_PIPELINE_DEPTH": "4"}}
    world, digests = 2, {}
    for tag, env in runs.items():
        out = tmp_path / f"d-{tag}"
        assert _launch("pipeline_parity", world,
                       {"RABIT_ENGINE": "pysocket", "RABIT_SCHED": "ring",
                        "RABIT_WIRE_CODEC": codec,
                        "RABIT_PIPELINE_CHUNK": "16KB",
                        "RABIT_REDUCE_BUFFER": "64KB", **env},
                       args=[str(out)]) == 0
        digests[tag] = [(tmp_path / f"d-{tag}.r{r}").read_text()
                        for r in range(world)]
    assert digests["native"] == digests["numpy"], "native != numpy"
    assert digests["mixed"] == digests["numpy"], "mixed != numpy"


@requires_native
@pytest.mark.recovery
@pytest.mark.parametrize("codec", [
    "int8", pytest.param("fp8e4m3", marks=pytest.mark.slow)])
def test_replay_after_crash_native_bit_identical(codec):
    """Kill-point replay with the native kernels armed: the relaunched
    rank must be served the EXACT cached wire bytes — encode
    determinism (feedback read-not-mutate + bit-identical requant)
    holds across the seam."""
    assert _launch("codec_replay", 3,
                   {"RABIT_ENGINE": "pyrobust",
                    "RABIT_WIRE_CODEC": codec,
                    "RABIT_CODEC_IMPL": "native",
                    "RABIT_MOCK": "1,0,1,0"}) == 0


# --------------------------------------------------- live-plane label
def test_status_and_rabit_top_surface_backend():
    """The resolved impl label flows frame -> LiveTable -> /status row
    -> rabit_top's codec column, with the mean per-op kernel time."""
    from rabit_tpu.obs.export import LiveTable
    from rabit_tpu.tools.rabit_top import render

    lt = LiveTable()
    lt.ingest(0, 1.0, {"engine": "pysocket", "codec_impl": "native",
                       "counters": {"op.allreduce.count": 3},
                       "gauges": {"codec.kernel.seconds.mean": 4.2e-4}})
    lt.ingest(1, 1.0, {"engine": "pysocket",
                       "codec_impl": "numpy-fallback", "counters": {}})
    rep = lt.report()
    assert rep["0"]["codec_impl"] == "native"
    assert rep["0"]["codec_kernel_ms"] == pytest.approx(0.42)
    assert rep["1"]["codec_impl"] == "numpy-fallback"
    assert dict(lt.rows())[0]["codec_impl"] == "native"
    buf = io.StringIO()
    render({"ts": 2.0, "jobs": {"j": {"world": 2, "live": rep}}},
           None, out=buf)
    text = buf.getvalue()
    assert "native 0.42ms" in text
    assert "numpy-fallback" in text
