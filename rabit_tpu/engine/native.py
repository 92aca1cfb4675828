"""ctypes binding to the native C++ engine (librabit_tpu.so).

TPU-native equivalent of the reference's Python wrapper
(reference: wrapper/rabit.py:54-306 loading librabit_wrapper*.so via
ctypes).  One shared library serves every variant; the variant is chosen
at Init time via the ``rabit_engine`` parameter (base | robust | mock)
rather than by loading a differently-built .so.
"""
from __future__ import annotations

import ctypes
import os
import time
from typing import Callable, Optional

import numpy as np

from rabit_tpu import obs
from rabit_tpu.engine.interface import Engine
from rabit_tpu.ops import ReduceOp
from rabit_tpu.ops.reduce_ops import dtype_to_enum
from rabit_tpu.utils.checks import check, error

_LIB_PATHS = [
    os.path.join(os.path.dirname(__file__), "..", "native", "lib",
                 "librabit_tpu.so"),
    "librabit_tpu.so",
]

_PREPARE_CB = ctypes.CFUNCTYPE(None, ctypes.c_void_p)
_REDUCER_CB = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_void_p,
                               ctypes.c_size_t, ctypes.c_void_p)
_SERIALIZE_CB = ctypes.CFUNCTYPE(ctypes.c_void_p, ctypes.c_void_p,
                                 ctypes.c_void_p)


def _load_lib() -> ctypes.CDLL:
    last = None
    for path in _LIB_PATHS:
        try:
            lib = ctypes.CDLL(os.path.abspath(path)
                              if os.path.sep in path else path)
            break
        except OSError as e:
            last = e
    else:
        raise ImportError(f"librabit_tpu.so not found "
                          f"(build with make -C rabit_tpu/native): {last}")
    lib.RbtTpuInit.argtypes = [ctypes.c_int, ctypes.POINTER(ctypes.c_char_p)]
    lib.RbtTpuGetLastError.restype = ctypes.c_char_p
    lib.RbtTpuDebugRoutedBytes.restype = ctypes.c_ulonglong
    lib.RbtTpuDebugScratchPeakBytes.restype = ctypes.c_ulonglong
    lib.RbtTpuGetProcessorName.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    lib.RbtTpuTrackerPrint.argtypes = [ctypes.c_char_p]
    lib.RbtTpuAllreduce.argtypes = [
        ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int, ctypes.c_int,
        _PREPARE_CB, ctypes.c_void_p]
    lib.RbtTpuAllreduceCustom.argtypes = [
        ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t,
        _REDUCER_CB, ctypes.c_void_p, _PREPARE_CB, ctypes.c_void_p]
    lib.RbtTpuBroadcastBlob.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int,
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_size_t)]
    lib.RbtTpuAllgather.argtypes = [
        ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p]
    lib.RbtTpuLoadCheckPoint.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_size_t),
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_size_t)]
    lib.RbtTpuCheckPoint.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t]
    lib.RbtTpuLazyCheckPoint.argtypes = [
        _SERIALIZE_CB, ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t]
    return lib


_lib: Optional[ctypes.CDLL] = None


def _get_lib() -> ctypes.CDLL:
    global _lib
    if _lib is None:
        _lib = _load_lib()
    return _lib


def native_available() -> bool:
    try:
        _get_lib()
        return True
    except ImportError:
        return False


class NativeEngine(Engine):
    """Python face of the C++ engine."""

    def __init__(self, variant: str = "base"):
        self._variant = variant
        self._lib = _get_lib()
        # Keep a live reference to the lazily-stashed local model for the
        # lazy_checkpoint contract (serialization stays Python-side).
        self._shutdown_done = False
        # Telemetry: the C++ engine is opaque, so ops are timed/counted
        # at this binding layer (doc/observability.md).
        self._obs_on = False
        self._obs_dir: Optional[str] = None
        self._metrics: Optional[obs.Metrics] = None
        self._trace: Optional[obs.EventTrace] = None
        self._log = obs.log.Logger("native", lambda: {"rank": self.rank})

    def _raise_last(self, what: str):
        msg = self._lib.RbtTpuGetLastError().decode("utf-8", "replace")
        error("%s failed: %s", what, msg)

    def init(self, params: dict) -> None:
        args = [f"rabit_engine={self._variant}"]
        for key, val in params.items():
            if key.startswith("rabit_") or key.startswith("mock"):
                args.append(f"{key}={val}")
        argv = (ctypes.c_char_p * len(args))(
            *[a.encode("utf-8") for a in args])
        cfg = obs.configure(params)
        self._obs_on = cfg.enabled
        self._obs_dir = cfg.obs_dir
        self._metrics = obs.Metrics()
        self._trace = obs.EventTrace(capacity=cfg.trace_capacity)
        if self._lib.RbtTpuInit(len(args), argv) != 0:
            self._raise_last("init")

    def shutdown(self) -> None:
        if not self._shutdown_done:
            self._obs_flush()
            self._lib.RbtTpuFinalize()
            self._shutdown_done = True

    # ------------------------------------------------------------------
    # telemetry (rabit_tpu.obs) — binding-layer instrumentation
    # ------------------------------------------------------------------
    def stats(self) -> dict:
        if not self._obs_on or self._metrics is None:
            return {}  # disabled telemetry reports nothing (interface.py)
        # Native debug counters surfaced as gauges so they aggregate
        # like everything else.
        try:
            self._metrics.gauge("native.routed_bytes").set(
                self.debug_routed_bytes())
            self._metrics.gauge("native.scratch_peak_bytes").set(
                self.debug_scratch_peak_bytes())
        except (OSError, AttributeError):  # pragma: no cover
            pass
        return self._metrics.snapshot()

    def events(self) -> list[dict]:
        return self._trace.events() if self._trace is not None else []

    def _op_done(self, kind: str, nbytes: int, t0: float) -> None:
        obs.record_op(self._metrics, self._trace, kind, nbytes,
                      time.perf_counter() - t0, self.rank,
                      replayed=bool(self.last_op_replayed))

    def _obs_flush(self) -> None:
        """Ship the rank summary over the tracker print channel and dump
        the event trace — same contract as the Python engines."""
        if not self._obs_on:
            return
        rank, world = self.rank, self.world_size
        if world > 1:
            obs.ship_summary(
                self.tracker_print, self._log, type(self).__name__,
                rank, world, self.stats(),
                [e for e in self._trace.events()
                 if e.get("name") not in ("op", "span")])
        if self._obs_dir:
            obs.dump_events(self._log, self._obs_dir, rank,
                            self._trace.events())

    @property
    def rank(self) -> int:
        return self._lib.RbtTpuGetRank()

    @property
    def world_size(self) -> int:
        return self._lib.RbtTpuGetWorldSize()

    @property
    def host(self) -> str:
        buf = ctypes.create_string_buffer(256)
        self._lib.RbtTpuGetProcessorName(buf, 256)
        return buf.value.decode("utf-8", "replace")

    def tracker_print(self, msg: str) -> None:
        if self._lib.RbtTpuTrackerPrint(msg.encode("utf-8")) != 0:
            self._raise_last("tracker_print")

    def allreduce(
        self,
        buf: np.ndarray,
        op: ReduceOp,
        prepare_fun: Optional[Callable[[], None]] = None,
        codec: bool = True,
    ) -> np.ndarray:
        # ``codec`` accepted for interface parity; the native wire has
        # no Python-side codec layer (full-width bytes always).
        check(isinstance(buf, np.ndarray),
              "native engine: device arrays route via the xla engine")
        cb = _PREPARE_CB()
        if prepare_fun is not None:
            cb = _PREPARE_CB(lambda _arg: prepare_fun())
        t0 = time.perf_counter() if self._obs_on else 0.0
        rc = self._lib.RbtTpuAllreduce(
            buf.ctypes.data_as(ctypes.c_void_p), buf.size,
            int(dtype_to_enum(buf.dtype)), int(op), cb, None)
        if rc != 0:
            self._raise_last("allreduce")
        if self._obs_on:
            self._op_done("allreduce", buf.nbytes, t0)
        return buf

    def allreduce_custom(
        self,
        buf: np.ndarray,
        reducer: Callable[[np.ndarray, np.ndarray], None],
        prepare_fun: Optional[Callable[[], None]] = None,
    ) -> np.ndarray:
        """Custom reduction through the native robust path: the C++
        engine runs the tree/recovery protocol and calls back into the
        Python ``reducer(dst, src)`` with numpy views per merge
        (reference: ReduceHandle, include/rabit/engine.h:215-253 —
        the reference never exposed this to Python)."""
        check(isinstance(buf, np.ndarray),
              "native engine: allreduce_custom expects a numpy array")
        count = buf.shape[0] if buf.ndim > 0 else buf.size
        check(count > 0, "allreduce_custom: empty buffer")
        item_size = buf.nbytes // count  # bytes per axis-0 row
        shape_tail = buf.shape[1:] if buf.ndim > 1 else ()

        # ctypes swallows exceptions raised inside callbacks (it prints
        # and returns normally) — capture the first one and re-raise
        # after the collective so the caller never sees unmerged data
        # reported as success.
        failure: list[BaseException] = []

        def c_reducer(dst_p, src_p, n, _arg):
            if failure:
                return  # already failed; don't cascade
            try:
                n = int(n)
                dst = np.ctypeslib.as_array(
                    ctypes.cast(dst_p, ctypes.POINTER(ctypes.c_uint8)),
                    shape=(n * item_size,)).view(buf.dtype
                                                 ).reshape((n,) + shape_tail)
                src = np.ctypeslib.as_array(
                    ctypes.cast(src_p, ctypes.POINTER(ctypes.c_uint8)),
                    shape=(n * item_size,)).view(buf.dtype
                                                 ).reshape((n,) + shape_tail)
                reducer(dst, src)
            except BaseException as e:  # noqa: BLE001 — re-raised below
                failure.append(e)

        rcb = _REDUCER_CB(c_reducer)
        pcb = _PREPARE_CB()
        if prepare_fun is not None:
            pcb = _PREPARE_CB(lambda _arg: prepare_fun())
        t0 = time.perf_counter() if self._obs_on else 0.0
        rc = self._lib.RbtTpuAllreduceCustom(
            buf.ctypes.data_as(ctypes.c_void_p), count, item_size,
            rcb, None, pcb, None)
        if failure:
            raise RuntimeError(
                "allreduce_custom: reducer raised during the collective; "
                "results on all ranks are unusable") from failure[0]
        if rc != 0:
            self._raise_last("allreduce_custom")
        if self._obs_on:
            self._op_done("allreduce_custom", buf.nbytes, t0)
        return buf

    def broadcast(self, data: Optional[bytes], root: int) -> bytes:
        payload = data if data is not None else b""
        t0 = time.perf_counter() if self._obs_on else 0.0
        out = ctypes.c_char_p()
        out_len = ctypes.c_size_t()
        rc = self._lib.RbtTpuBroadcastBlob(
            payload, len(payload), root,
            ctypes.byref(out), ctypes.byref(out_len))
        if rc != 0:
            self._raise_last("broadcast")
        result = ctypes.string_at(out, out_len.value)
        if self._obs_on:
            self._op_done("broadcast", len(result), t0)
        return result

    def allgather(self, buf: np.ndarray) -> np.ndarray:
        world = self.world_size
        t0 = time.perf_counter() if self._obs_on else 0.0
        out = np.empty((world,) + buf.shape, dtype=buf.dtype)
        rc = self._lib.RbtTpuAllgather(
            buf.ctypes.data_as(ctypes.c_void_p), buf.nbytes,
            out.ctypes.data_as(ctypes.c_void_p))
        if rc != 0:
            self._raise_last("allgather")
        if self._obs_on:
            self._op_done("allgather", out.nbytes, t0)
        return out

    def load_checkpoint(self):
        gptr = ctypes.c_char_p()
        glen = ctypes.c_size_t()
        lptr = ctypes.c_char_p()
        llen = ctypes.c_size_t()
        version = self._lib.RbtTpuLoadCheckPoint(
            ctypes.byref(gptr), ctypes.byref(glen),
            ctypes.byref(lptr), ctypes.byref(llen))
        if version < 0:
            self._raise_last("load_checkpoint")
        if version == 0:
            return (0, None, None)
        g = ctypes.string_at(gptr, glen.value) if glen.value else None
        l = ctypes.string_at(lptr, llen.value) if llen.value else None
        return (version, g, l)

    def checkpoint(self, global_model, local_model=None, lazy_global=None):
        if global_model is None and lazy_global is not None:
            return self._lazy_checkpoint(lazy_global, local_model)
        g = global_model or b""
        # NOTE: the previous lazy callback must stay alive THROUGH this
        # native call — CheckPointImpl can run RecoverExec ->
        # ServeCheckpointLoad -> MaterializeGlobal (a rank rejoining
        # mid-checkpoint) before CommitCheckPoint swaps the model, which
        # invokes the old trampoline.  Clear it only after return.
        if local_model is not None:
            rc = self._lib.RbtTpuCheckPoint(g, len(g), local_model,
                                            len(local_model))
        else:
            rc = self._lib.RbtTpuCheckPoint(g, len(g), None, 0)
        if rc != 0:
            # keep the old trampoline: a failed barrier leaves the C++
            # lazy_global_ untouched and it may still be invoked later
            self._raise_last("checkpoint")
        self._lazy_cb = None  # a real checkpoint supersedes any lazy fn

    def _lazy_checkpoint(self, lazy_global, local_model) -> None:
        """True LazyCheckPoint: the C++ engine calls back for the bytes
        only when a peer (or a local load) needs them — zero
        serialization cost in the steady state (reference:
        src/allreduce_robust.cc:744-751)."""

        def c_serialize(len_out, _arg):
            # keep the payload alive on self: the C++ side copies it
            # during this call, but ctypes needs the pointer valid on
            # return
            self._lazy_payload = lazy_global()
            ctypes.cast(len_out, ctypes.POINTER(ctypes.c_size_t)
                        )[0] = len(self._lazy_payload)
            return ctypes.cast(ctypes.c_char_p(self._lazy_payload),
                               ctypes.c_void_p).value

        # the callback must outlive this call: the engine may invoke it
        # during any later collective's recovery, until the next
        # checkpoint.  The PREVIOUS callback must also survive until the
        # native call returns — recovery during LazyCheckPoint can still
        # materialize the old version's model — so keep self._lazy_cb
        # bound to it and swap in the new trampoline only afterwards.
        cb = _SERIALIZE_CB(c_serialize)
        if local_model is not None:
            rc = self._lib.RbtTpuLazyCheckPoint(cb, None,
                                                local_model,
                                                len(local_model))
        else:
            rc = self._lib.RbtTpuLazyCheckPoint(cb, None,
                                                None, 0)
        if rc != 0:
            # keep the OLD trampoline referenced: on failure the C++
            # engine may still hold the previous lazy_global_
            self._raise_last("lazy_checkpoint")
        self._lazy_cb = cb

    @property
    def version_number(self) -> int:
        return self._lib.RbtTpuVersionNumber()

    def debug_routed_bytes(self) -> int:
        """Payload bytes this rank has sent through the requester-routed
        recovery broadcast (tests assert recovery traffic scales with
        requesters, not world size)."""
        return int(self._lib.RbtTpuDebugRoutedBytes())

    def debug_scratch_peak_bytes(self) -> int:
        """Largest per-op collective scratch allocation so far (tests
        assert it stays within the rabit_reduce_buffer budget)."""
        return int(self._lib.RbtTpuDebugScratchPeakBytes())

    @property
    def was_relaunched(self) -> bool:
        return bool(self._lib.RbtTpuWasRelaunched())

    @property
    def last_op_replayed(self) -> bool:
        return bool(self._lib.RbtTpuLastReplayed())
