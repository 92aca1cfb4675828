"""Public user-facing API.

TPU-native equivalent of the reference's user API surface
(reference: include/rabit.h:58-326 — Init/Finalize/GetRank/GetWorldSize/
Allreduce/Broadcast/LoadCheckPoint/CheckPoint/LazyCheckPoint/VersionNumber/
TrackerPrint; Python mirror wrapper/rabit.py:54-306).

Arrays: numpy arrays are reduced in place (like the reference's ``void*``
buffers); ``jax.Array`` inputs are routed through the engine's
device-resident path and a new array is returned (JAX arrays are
immutable).  Python objects use pickle for broadcast/checkpoint, matching
the reference wrapper.
"""
from __future__ import annotations

import pickle
from typing import Any, Callable, Optional

import numpy as np

from rabit_tpu import engine as _engine_mod
from rabit_tpu.obs import program
from rabit_tpu.ops import ReduceOp, SUM
from rabit_tpu.utils.checks import check
from rabit_tpu.utils.serial import deserialize_model, serialize_model


def init(args: Optional[list[str]] = None, **params: Any) -> None:
    """Initialise the framework.

    ``args`` accepts reference-style ``name=value`` strings
    (reference: src/engine.cc:31-39); keyword params win on conflict.
    Recognised keys include ``rabit_engine``
    (empty|pysocket|pyrobust|native|mock|xla),
    ``rabit_tracker_uri``, ``rabit_tracker_port``, ``rabit_task_id``,
    ``rabit_reduce_buffer``, ``rabit_global_replica``,
    ``rabit_local_replica``, ``rabit_ckpt_dir`` (durable checkpoint
    tier) and ``rabit_heartbeat_sec`` (proactive liveness) — the full
    catalogue is doc/parameters.md.
    Environment variables prefixed ``RABIT_`` are read as defaults.
    """
    import os

    from rabit_tpu.utils import compile_cache

    merged: dict[str, Any] = {}
    for key, val in os.environ.items():
        if key.startswith("RABIT_"):
            merged[key.lower()] = val
    for a in args or []:
        if "=" in a:
            k, v = a.split("=", 1)
            merged[k] = v
    merged.update(params)
    with program.span("init"):
        # before the engine for a process that already has JAX, after
        # it for one whose engine brought JAX in
        compile_cache.count_compiles()
        _engine_mod.init(merged)
        compile_cache.count_compiles()


def finalize() -> None:
    """Shut down the engine (reference: rabit::Finalize)."""
    _engine_mod.finalize()


def initialized() -> bool:
    return _engine_mod.initialized()


def get_rank() -> int:
    return _engine_mod.get_engine().rank


def get_world_size() -> int:
    # Note: the reference Python wrapper's get_world_size was broken by a
    # typo'd symbol name (reference: wrapper/rabit.py:90) — parity not kept.
    return _engine_mod.get_engine().world_size


def get_processor_name() -> str:
    return _engine_mod.get_engine().host


def is_distributed() -> bool:
    return _engine_mod.get_engine().is_distributed()


def tracker_print(msg: str) -> None:
    _engine_mod.get_engine().tracker_print(str(msg))


def allreduce(
    data,
    op: ReduceOp = SUM,
    prepare_fun: Optional[Callable[[], None]] = None,
    codec: bool = True,
):
    """Allreduce an array across all ranks.

    numpy input: reduced **in place** and returned (matching the reference's
    in-place Allreduce, include/rabit.h:134-137).  jax input: returns a new
    device-resident array.  ``prepare_fun`` is the lazy-preparation hook,
    skipped when a cached result is replayed during recovery.

    ``codec=False`` opts this op out of an armed lossy wire codec
    (``rabit_wire_codec=bf16|int8|int4`` — doc/performance.md
    "Quantized wire codecs"): a precision-critical op (an optimizer
    direction, a convergence test) keeps exact full-width bytes while
    the bulk traffic stays quantized.  Program order, hence
    deterministic across ranks — like ``fuse`` on the async face.
    """
    eng = _engine_mod.get_engine()
    with program.span("allreduce"):
        if isinstance(data, np.ndarray):
            check(data.flags.c_contiguous,
                  "allreduce: array must be C-contiguous")
            return eng.allreduce(data, op, prepare_fun, codec)
        try:
            import jax
        except ImportError:  # pragma: no cover
            jax = None
        if jax is not None and isinstance(data, jax.Array):
            return eng.allreduce(data, op, prepare_fun, codec)
        # scalars / lists: round-trip through numpy
        arr = np.asarray(data)
        scalar = arr.ndim == 0
        arr = np.atleast_1d(arr).copy()
        out = eng.allreduce(arr, op, prepare_fun, codec)
        return out[0] if scalar else out


def allreduce_async(
    data: np.ndarray,
    op: ReduceOp = SUM,
    prepare_fun: Optional[Callable[[], None]] = None,
    fuse: bool = True,
    codec: bool = True,
):
    """Issue an allreduce without blocking; returns a
    :class:`~rabit_tpu.engine.interface.CollectiveHandle` whose
    ``wait()`` yields the reduced array (the same in-place semantics as
    :func:`allreduce`).

    On the socket engines the op is driven by a background progress
    thread, so host compute overlaps the wire; small same-op/same-dtype
    payloads issued back to back coalesce into one fused wire op
    (``rabit_bucket_bytes`` — doc/performance.md).  A bucketed op only
    reaches the wire when its bucket flushes, so pass ``fuse=False``
    for a lone latency-sensitive op with no stream behind it — it
    dispatches eagerly and genuinely overlaps the caller's compute.
    Handles must be waited in issue order; the array must not be read
    or written between issue and ``wait()``.  Engines without an async
    path run the op synchronously and return a resolved handle, so
    callers never need a capability check.  ``codec=False`` opts the
    op out of an armed lossy wire codec (see :func:`allreduce`).
    """
    eng = _engine_mod.get_engine()
    check(isinstance(data, np.ndarray) and data.flags.c_contiguous,
          "allreduce_async: need a C-contiguous numpy array")
    return eng.allreduce_async(data, op, prepare_fun, fuse=fuse,
                               codec=codec)


def allgather_async(data: np.ndarray):
    """Issue an allgather without blocking; ``wait()`` returns the
    (world, *shape) stacked array (see :func:`allreduce_async` for the
    ordering and aliasing rules)."""
    eng = _engine_mod.get_engine()
    check(isinstance(data, np.ndarray) and data.flags.c_contiguous,
          "allgather_async: need a C-contiguous numpy array")
    return eng.allgather_async(data)


def allreduce_many(arrays, op: ReduceOp = SUM) -> list:
    """Allreduce a batch of independent arrays as one fused operation.

    Blocking-API face of the bucket coalescer: every array is issued
    async, the engine fuses eligible ones into shared wire ops, and the
    results come back in order — bit-identical to reducing each array
    with :func:`allreduce` separately, but with one wire op per
    ``rabit_bucket_bytes`` of payload instead of one per array.
    """
    eng = _engine_mod.get_engine()
    check(len(arrays) > 0, "allreduce_many: need at least one array")
    for a in arrays:
        check(isinstance(a, np.ndarray) and a.flags.c_contiguous,
              "allreduce_many: need C-contiguous numpy arrays")
    handles = [eng.allreduce_async(a, op) for a in arrays]
    return [h.wait() for h in handles]


def allreduce_custom(
    data: np.ndarray,
    reducer: Callable[[np.ndarray, np.ndarray], None],
    prepare_fun: Optional[Callable[[], None]] = None,
) -> np.ndarray:
    """Allreduce with a user-defined reduction function.

    ``reducer(dst, src)`` folds ``src`` into ``dst`` in place, row-wise
    over axis 0, and must be associative.  The Python face of the
    reference's C++-only custom-reducer surface
    (reference: rabit::Reducer, include/rabit.h:236-276); on the native
    engine the C++ robust protocol runs the tree and calls back per
    merge, with full cache/replay recovery semantics.
    """
    eng = _engine_mod.get_engine()
    check(isinstance(data, np.ndarray) and data.flags.c_contiguous,
          "allreduce_custom: need a C-contiguous numpy array")
    return eng.allreduce_custom(data, reducer, prepare_fun)


def broadcast(data: Any, root: int) -> Any:
    """Broadcast an arbitrary picklable object from ``root`` to all ranks.

    Two-phase (length, then payload), matching the reference wrapper
    (reference: wrapper/rabit.py:117-168).  At this layer both phases fold
    into one length-prefixed engine broadcast.
    """
    eng = _engine_mod.get_engine()
    check(0 <= root < eng.world_size, "broadcast: invalid root %d", root)
    payload = pickle.dumps(data) if eng.rank == root else None
    raw = eng.broadcast(payload, root)
    return pickle.loads(raw)


def allgather(data) -> np.ndarray:
    """Gather each rank's array; returns shape (world, *data.shape).

    jax inputs keep the device-resident path (engines with a device data
    plane gather over ICI); everything else goes through numpy.
    """
    eng = _engine_mod.get_engine()
    try:
        import jax
    except ImportError:  # pragma: no cover
        jax = None
    if jax is not None and isinstance(data, jax.Array):
        return eng.allgather(data)
    return eng.allgather(np.ascontiguousarray(data))


def load_checkpoint(with_local: bool = False, into_global: Any = None,
                    into_local: Any = None):
    """Load the latest in-memory checkpoint.

    Returns ``(version, global_model)`` or ``(version, global_model,
    local_model)`` when ``with_local``; version 0 means fresh start
    (reference: wrapper/rabit.py:232-266, src/allreduce_robust.cc:159-196).

    Models checkpointed through a custom :class:`Serializable` must be
    restored into an instance: pass it as ``into_global``/``into_local``
    (mirroring the reference's LoadCheckPoint(ISerializable*) contract).
    """
    eng = _engine_mod.get_engine()
    with program.span("load_checkpoint"):
        version, g, l = eng.load_checkpoint()
        gobj = (deserialize_model(g, into_global)
                if (g is not None and version > 0) else None)
        if with_local:
            lobj = (deserialize_model(l, into_local)
                    if (l is not None and version > 0) else None)
            return version, gobj, lobj
        return version, gobj


def checkpoint(global_model: Any, local_model: Any = None) -> None:
    """Commit a checkpoint of the model(s); bumps the version
    (reference: rabit::CheckPoint, src/allreduce_robust.cc:242-295)."""
    eng = _engine_mod.get_engine()
    with program.span("commit", version=eng.version_number + 1):
        with program.span("commit.serialize"):
            g = serialize_model(global_model)
            l = (serialize_model(local_model)
                 if local_model is not None else None)
        eng.checkpoint(g, l)


def lazy_checkpoint(global_model: Any) -> None:
    """Checkpoint that defers serialization until a peer needs the payload
    (reference: rabit::LazyCheckPoint, src/allreduce_robust.h:125-127)."""
    eng = _engine_mod.get_engine()
    eng.checkpoint(None, None, lazy_global=lambda: serialize_model(global_model))


def version_number() -> int:
    return _engine_mod.get_engine().version_number


def device_epoch() -> int:
    """Device-plane epoch: bumped when the XLA engine re-forms the
    device mesh after a failure (engines without a device plane always
    report 0).  Device arrays created under an older epoch are dead —
    apps that keep shards resident re-upload when this moves, then
    continue from their last checkpoint state."""
    return getattr(_engine_mod.get_engine(), "device_epoch", 0)
