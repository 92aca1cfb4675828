"""The Codec interface — lossy wire formats as pluggable data.

A :class:`Codec` is the ONE wire-format seam between the engine's
reduction path and the transport frame layer: it decides how an
eligible allreduce payload is represented on every link, independent of
WHICH schedule moves the bytes (tree/ring/halving/swing/hier), of
bucket fusion, of the async pump, of pyrobust replay and of the
link underneath (with or without integrity framing).
``rabit_wire_codec`` selects one per job (doc/performance.md
"Quantized wire codecs"); the classic full-width wire stays the
default, and the PR-3 bf16 cast is now simply the first codec
(:class:`Bf16Codec`) instead of a special case.

Two codec shapes exist, distinguished by :attr:`Codec.elementwise`:

* **elementwise** (bf16): the wire array's elements reduce directly
  with ``apply_op_numpy`` in a decoupled ``red_dtype`` — exactly the
  transport/merge-dtype split the schedules already speak.  Composes
  with the fused segmented ring (members cast independently).
* **block-scaled** (int8/int4, blockscale.py): each block of
  ``block`` f32 elements travels as ``f32 scale + quantized payload``
  packed into ONE structured wire element, so every schedule's
  item-aligned chunking moves whole blocks by construction.  Hop-path
  reductions dequantize→accumulate→requantize through the engine's
  ``_wire_merge`` seam, carrying the requantization residual in the
  error-feedback accumulator (feedback.py; EQuARX's dual-sided scheme
  is the reference).

Eligibility is a pure function of replicated inputs (dtype, op,
payload size, the uniform codec config), so every rank agrees whether
an op rides the codec — a collective decision, like schedule choice.
"""
from __future__ import annotations

import numpy as np

from rabit_tpu.ops import ReduceOp


class Codec:
    """One lossy wire format; subclasses override the hooks below."""

    #: registry key: the ``rabit_wire_codec`` value, the TuningCache
    #: codec dimension and the ``codec.ops.<name>`` obs counter suffix
    name = "?"

    #: True: wire elements reduce via ``apply_op_numpy`` in
    #: :meth:`red_dtype` (the bf16 shape); False: block-scaled — the
    #: engine routes merges through :meth:`merge` instead.
    elementwise = True

    def eligible(self, dtype, op: ReduceOp, nbytes: int) -> bool:
        """Does this codec apply to the given op?  Must be
        deterministic across ranks (it sees only replicated inputs)."""
        raise NotImplementedError

    def wire_nbytes(self, nbytes: int) -> int:
        """TRUE wire bytes for a logical payload of ``nbytes`` — the
        quantity schedule selection and dispatch-size accounting must
        see (replaces the historical hardcoded ``nbytes //= 2`` bf16
        special case)."""
        raise NotImplementedError


class Bf16Codec(Codec):
    """f32 sum-allreduces travel as bf16: half the bytes on every
    link, accumulation in bf16 too (the PR-3 ``rabit_wire_dtype=bf16``
    path, byte-identical — enable only where ~3 significant digits
    suffice; doc/performance.md has the accuracy bound)."""

    name = "bf16"
    elementwise = True

    def eligible(self, dtype, op: ReduceOp, nbytes: int) -> bool:
        # No size floor: the historical bf16 cast applied at every
        # size, and the wire bytes must stay byte-identical to it.
        return op == ReduceOp.SUM and dtype == np.float32

    def wire_nbytes(self, nbytes: int) -> int:
        return nbytes // 2

    @staticmethod
    def red_dtype():
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)

    def encode(self, flat: np.ndarray):
        """Return the ``(transport_u16_array, reduce_dtype)`` pair.
        Transport rides as uint16 (ml_dtypes arrays don't export a
        buffer); the element merges run in bf16 via views."""
        red = self.red_dtype()
        return flat.reshape(-1).astype(red).view(np.uint16), red

    def decode(self, wire: np.ndarray, red) -> np.ndarray:
        return wire.view(red).astype(np.float32)
