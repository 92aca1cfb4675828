"""Reduction operators and dtype tables.

TPU-native equivalent of the reference's op/dtype enums
(reference: include/rabit/rabit-inl.h:17-92 — dtype→enum map and the
op::Max/Min/Sum/BitOR reducer structs; include/rabit/engine.h:169-186).
"""
from rabit_tpu.ops.reduce_ops import (
    ReduceOp,
    MAX,
    MIN,
    SUM,
    PROD,
    BITOR,
    BITAND,
    BITXOR,
    DataType,
    dtype_to_enum,
    enum_to_dtype,
    apply_op_numpy,
    apply_op_jax,
    apply_op_pairwise,
)



def on_tpu() -> bool:
    """THE place that decides "the chip's compiler, or not".

    Every Pallas kernel's ``interpret=None`` default, every learner's
    ``use_pallas=None`` default and the sparse staging tier ask this one
    function, so a run that must be on the chip (``chip_smoke.py``,
    ``bench.py``) asserts it once up front and knows that no path below
    silently took the interpreter or the XLA formulation instead.
    Tests steer explicitly (``interpret=True`` / ``use_pallas=...``)."""
    import jax

    return jax.default_backend() == "tpu"


__all__ = [
    "on_tpu",
    "ReduceOp",
    "MAX",
    "MIN",
    "SUM",
    "PROD",
    "BITOR",
    "BITAND",
    "BITXOR",
    "DataType",
    "dtype_to_enum",
    "enum_to_dtype",
    "apply_op_numpy",
    "apply_op_jax",
    "apply_op_pairwise",
]
