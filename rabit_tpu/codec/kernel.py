"""ctypes seam to the compiled codec kernels (librabit_codec.so).

``rabit_codec_impl`` picks the hop-math implementation behind the ONE
Codec seam:

* ``auto`` (default) — use the compiled kernels when the shared
  library loads, else fall back to the numpy reference with a single
  obs-visible warning (never an ImportError: a toolchain-free box must
  stay green on the numpy path);
* ``native`` — require the kernels; a missing/stale library is a
  loud config error (an explicit request deserves honesty, not a
  silent 10x slowdown);
* ``numpy`` — force the reference path (the A/B baseline).

The choice is IMPLEMENTATION ONLY: both paths are contractually
bit-identical (the C side mirrors numpy's ufunc inner-loop semantics,
see native/src/codec_kernels.c), so it is NOT a collective decision —
ranks may mix implementations freely and replay/retry, sched parity
and cross-rank result parity all hold.  tests/test_native_codec.py
enforces the contract.

Library search order: ``RABIT_CODEC_LIB`` (explicit path), then the
package's ``native/lib/librabit_codec.so`` (built by ``make -C
rabit_tpu/native codec``, best-effort at install time via setup.py).
"""
from __future__ import annotations

import ctypes
import os
import threading
from typing import Optional

from rabit_tpu.utils.checks import check

#: the ``rabit_codec_impl`` vocabulary
IMPLS = ("auto", "native", "numpy")

#: must match RABIT_CODEC_ABI in native/src/codec_kernels.c
ABI = 1

#: block-format codes shared with the C side (enum in codec_kernels.c)
FMT = {"int8": 0, "int4": 1, "fp8e4m3": 2, "fp8e5m2": 3}

_u8p = ctypes.POINTER(ctypes.c_uint8)
_f32p = ctypes.POINTER(ctypes.c_float)
_u16p = ctypes.POINTER(ctypes.c_uint16)


def p8(a) -> "ctypes._Pointer":
    """Byte pointer to a (contiguous) numpy array's data."""
    return ctypes.cast(a.ctypes.data, _u8p)


def pf32(a) -> "ctypes._Pointer":
    return ctypes.cast(a.ctypes.data, _f32p)


def pu16(a) -> "ctypes._Pointer":
    return ctypes.cast(a.ctypes.data, _u16p)


class CodecKernel:
    """Typed handle over one loaded librabit_codec.so."""

    def __init__(self, lib: ctypes.CDLL, path: str) -> None:
        self.path = path
        lib.rabit_codec_abi.restype = ctypes.c_int
        lib.rabit_codec_abi.argtypes = ()
        lib.rabit_bs_merge.restype = None
        lib.rabit_bs_merge.argtypes = (
            _u8p, _u8p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_int32, _f32p)
        lib.rabit_bs_encode.restype = None
        lib.rabit_bs_encode.argtypes = (
            _u8p, _f32p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32)
        lib.rabit_bs_decode.restype = None
        lib.rabit_bs_decode.argtypes = (
            _u8p, _f32p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32)
        lib.rabit_bf16_merge.restype = None
        lib.rabit_bf16_merge.argtypes = (_u16p, _u16p, ctypes.c_int64)
        self._lib = lib

    # thin forwarding wrappers: callers hand raw ctypes pointers (the
    # codec owns the numpy-array -> pointer mapping, one place)
    def bs_merge(self, dst, src, nblocks: int, block: int, fmt: int,
                 record: bool, hop) -> None:
        self._lib.rabit_bs_merge(dst, src, nblocks, block, fmt,
                                 1 if record else 0, hop)

    def bs_encode(self, blocks, acc, nblocks: int, block: int,
                  fmt: int) -> None:
        self._lib.rabit_bs_encode(blocks, acc, nblocks, block, fmt)

    def bs_decode(self, blocks, out, nblocks: int, block: int,
                  fmt: int) -> None:
        self._lib.rabit_bs_decode(blocks, out, nblocks, block, fmt)

    def bf16_merge(self, dst, src, n: int) -> None:
        self._lib.rabit_bf16_merge(dst, src, n)


def _lib_path() -> str:
    override = os.environ.get("RABIT_CODEC_LIB", "").strip()
    if override:
        return override
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "native", "lib", "librabit_codec.so")


_lock = threading.Lock()
_loaded = False
_kernel: Optional[CodecKernel] = None
_load_error: Optional[str] = None
_warned = False


def load() -> Optional[CodecKernel]:
    """Load (once) and return the kernel handle, or None with the
    failure recorded in :func:`load_error`.  Never raises: the caller
    decides whether a missing library is fatal (``native``) or a
    fallback (``auto``)."""
    global _loaded, _kernel, _load_error
    with _lock:
        if _loaded:
            return _kernel
        _loaded = True
        path = _lib_path()
        try:
            lib = ctypes.CDLL(path)
            k = CodecKernel(lib, path)
            abi = lib.rabit_codec_abi()
            if abi != ABI:
                _load_error = ("%s speaks codec ABI %d, this build needs "
                               "%d (rebuild: make -C rabit_tpu/native "
                               "codec)" % (path, abi, ABI))
                return None
            _kernel = k
        except (OSError, AttributeError) as e:
            _load_error = "%s: %s" % (path, e)
        return _kernel


def load_error() -> Optional[str]:
    return _load_error


def resolve_impl(impl_raw, log=None) -> tuple[Optional[CodecKernel], str]:
    """Resolve ``rabit_codec_impl`` into ``(kernel-or-None, label)``.

    The label is what the obs plane surfaces (``native`` / ``numpy`` /
    ``numpy-fallback``) so a silent degrade is visible in one glance
    (rabit_top, /status).  The fallback warning fires ONCE per process,
    not per engine."""
    global _warned
    impl = (str(impl_raw).strip().lower()
            if impl_raw not in (None, "") else "auto")
    check(impl in IMPLS, "rabit_codec_impl must be one of %s, got %r",
          "/".join(IMPLS), impl)
    if impl == "numpy":
        return None, "numpy"
    k = load()
    if k is not None:
        return k, "native"
    check(impl != "native",
          "rabit_codec_impl=native but the codec kernel library did not "
          "load (%s); build it with `make -C rabit_tpu/native codec` or "
          "use rabit_codec_impl=auto", load_error())
    if log is not None and not _warned:
        _warned = True
        log.warn("codec kernels unavailable (%s); falling back to "
                 "the numpy wire path (rabit_codec_impl=auto)",
                 load_error())
    return None, "numpy-fallback"
