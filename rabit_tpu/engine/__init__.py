"""Engine layer: the pluggable collective backends.

TPU-native equivalent of the reference's engine selection layer
(reference: src/engine.cc:20-48 — a compile-time singleton choosing between
base/robust/mock/empty/MPI library variants).  We select at *runtime* by
name instead: ``empty`` (world=1 no-op), ``pysocket`` (pure-Python TCP,
non-fault-tolerant), ``pyrobust`` (pure-Python TCP with the full
cache/replay recovery protocol — no compiled library needed), ``native``
(C++ TCP engine, robust by default; ``base`` selects the
non-fault-tolerant variant), ``mock`` (native engine with fault-injection
kill points), ``xla`` (JAX/XLA collectives over the device mesh) and
``mpi`` (mpi4py, when installed).
"""
from __future__ import annotations

from rabit_tpu.engine.interface import Engine
from rabit_tpu.obs import program
from rabit_tpu.utils.checks import check

_engine: Engine | None = None


def _make_engine(name: str, params: dict) -> Engine:
    if name == "empty":
        from rabit_tpu.engine.empty import EmptyEngine

        return EmptyEngine()
    if name == "pysocket":
        from rabit_tpu.engine.pysocket import PySocketEngine

        return PySocketEngine()
    if name == "pyrobust":
        from rabit_tpu.engine.robust import PyRobustEngine

        return PyRobustEngine()
    if name in ("native", "base", "robust", "mock"):
        try:
            from rabit_tpu.engine.native import NativeEngine
        except ImportError as e:
            raise RuntimeError(
                f"engine {name!r} needs the native library "
                "(make -C rabit_tpu/native)") from e

        # "native" defaults to the fault-tolerant robust variant.
        return NativeEngine(variant=name if name != "native" else "robust")
    if name == "xla":
        from rabit_tpu.engine.xla import XLAEngine

        return XLAEngine()
    if name == "mpi":
        from rabit_tpu.engine.mpi import MPIEngine

        return MPIEngine()
    raise ValueError(f"unknown engine: {name!r}")


def init(params: dict | None = None) -> Engine:
    """Create and initialise the global engine singleton.

    Reference: engine::Init (src/engine.cc:31-39) — parses name=value
    parameters and forwards them to the engine's SetParam.
    """
    global _engine
    check(_engine is None, "engine already initialised; call finalize() first")
    params = dict(params or {})
    name = params.pop("rabit_engine", None) or _autodetect(params)
    eng = _make_engine(name, params)
    eng.init(params)
    _engine = eng
    program.attach(eng)     # program spans follow the engine's rabit_obs
    return eng


def _autodetect(params: dict) -> str:
    """Pick an engine: tracker configured → native (falling back to the
    pure-Python robust engine when the library isn't built, so fault
    tolerance never silently disappears with the ``.so``), else empty."""
    import os

    if "rabit_tracker_uri" in params or "RABIT_TRACKER_URI" in os.environ:
        try:
            from rabit_tpu.engine.native import native_available

            if native_available():
                return "native"
        except ImportError:
            pass
        return "pyrobust"
    return "empty"


def get_engine() -> Engine:
    check(_engine is not None, "rabit_tpu is not initialised; call init() first")
    return _engine


def initialized() -> bool:
    return _engine is not None


def _is_xla_engine() -> bool:
    try:
        from rabit_tpu.engine.xla import XLAEngine

        return isinstance(_engine, XLAEngine)
    except ImportError:  # pragma: no cover
        return False


def is_device_plane() -> bool:
    """True when the active engine reduces ``jax.Array`` payloads over
    the device data plane (the XLA engine in a multi-process world) —
    apps keep such payloads on device instead of converting to numpy."""
    if _engine is None or not _engine.is_distributed():
        return False
    return _is_xla_engine()


def keeps_device_payloads() -> bool:
    """True when ``allreduce`` of a ``jax.Array`` gives a ``jax.Array``
    back, so that an app's reduced payload can stay on the device for
    what it does next: the XLA engine (the device plane, and its world
    of one) and ``empty``, which hands its argument back.  The host
    engines take numpy alone."""
    from rabit_tpu.engine.empty import EmptyEngine

    return isinstance(_engine, EmptyEngine) or _is_xla_engine()


def finalize() -> None:
    global _engine
    if _engine is not None:
        _engine.shutdown()
        _engine = None
        program.detach()
