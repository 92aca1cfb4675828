"""Streams and the serialization contract used by checkpoints.

TPU-native equivalent of the reference's serialization layer
(reference: include/rabit_serializable.h:17-106 IStream/ISerializable;
include/rabit/io.h:29-117 MemoryFixSizeBuffer/MemoryBufferStream;
rabit-learn/utils/base64.h base64 streams for text-safe model transport).

The checkpoint protocol works on *bytes*: a model is anything that can
serialize itself into a stream and restore itself from one.  Python objects
get a default pickle-based implementation (:class:`PickleSerializable`),
matching the reference Python wrapper's pickled checkpoints
(reference: wrapper/rabit.py:232-297).
"""
from __future__ import annotations

import base64
import io
import pickle
import struct
from abc import ABC, abstractmethod
from typing import Any, BinaryIO

from rabit_tpu.utils.checks import check


class Stream(ABC):
    """Minimal byte-stream interface for serialization.

    Reference: include/rabit_serializable.h:17-92 (IStream), including the
    convenience vector/string helpers which here become length-prefixed
    ``write_bytes``/``read_bytes``.
    """

    @abstractmethod
    def read(self, nbytes: int) -> bytes:
        """Read up to ``nbytes``; returns b'' at end of stream."""

    @abstractmethod
    def write(self, data: bytes) -> None:
        """Write all of ``data``."""

    # -- structured helpers (length-prefixed, little-endian) ---------------
    def write_u64(self, value: int) -> None:
        self.write(struct.pack("<Q", value))

    def read_u64(self) -> int:
        raw = self.read(8)
        check(len(raw) == 8, "stream: truncated u64")
        return struct.unpack("<Q", raw)[0]

    def write_bytes(self, data: bytes) -> None:
        self.write_u64(len(data))
        if data:
            self.write(data)

    def read_bytes(self) -> bytes:
        n = self.read_u64()
        data = self.read(n) if n else b""
        check(len(data) == n, "stream: truncated payload (%d != %d)", len(data), n)
        return data

    def write_str(self, s: str) -> None:
        self.write_bytes(s.encode("utf-8"))

    def read_str(self) -> str:
        return self.read_bytes().decode("utf-8")


class MemoryFixSizeBuffer(Stream):
    """Read/write over a fixed, pre-allocated buffer.

    Reference: include/rabit/io.h:29-74.  Backed by a ``memoryview`` so
    writes mutate the caller's buffer in place.
    """

    def __init__(self, buf: bytearray | memoryview):
        self._view = memoryview(buf)
        self._pos = 0

    def read(self, nbytes: int) -> bytes:
        n = min(nbytes, len(self._view) - self._pos)
        out = bytes(self._view[self._pos : self._pos + n])
        self._pos += n
        return out

    def write(self, data: bytes) -> None:
        n = len(data)
        check(self._pos + n <= len(self._view), "MemoryFixSizeBuffer: overflow")
        self._view[self._pos : self._pos + n] = data
        self._pos += n

    def seek(self, pos: int) -> None:
        self._pos = pos

    def tell(self) -> int:
        return self._pos


class MemoryBufferStream(Stream):
    """Growable in-memory stream (reference: include/rabit/io.h:77-117)."""

    def __init__(self, init: bytes = b""):
        self._buf = io.BytesIO(init)

    def read(self, nbytes: int) -> bytes:
        return self._buf.read(nbytes)

    def write(self, data: bytes) -> None:
        self._buf.write(data)

    def seek(self, pos: int) -> None:
        self._buf.seek(pos)

    def getvalue(self) -> bytes:
        return self._buf.getvalue()


class FileStream(Stream):
    """Stream over an open binary file (reference: rabit-learn/utils/io.h)."""

    def __init__(self, fp: BinaryIO):
        self._fp = fp

    def read(self, nbytes: int) -> bytes:
        return self._fp.read(nbytes)

    def write(self, data: bytes) -> None:
        self._fp.write(data)


class Base64InStream(Stream):
    """Read a base64-encoded payload from an underlying text/byte stream.

    Reference: rabit-learn/utils/base64.h (used to pass binary models through
    text-only channels such as Hadoop streaming).  We decode the whole
    underlying payload eagerly — model blobs are small relative to data.
    """

    def __init__(self, fp: BinaryIO):
        raw = fp.read()
        if isinstance(raw, str):
            raw = raw.encode("ascii")
        # Tolerate whitespace/newlines in the encoded payload.
        raw = b"".join(raw.split())
        self._inner = io.BytesIO(base64.b64decode(raw))

    def read(self, nbytes: int) -> bytes:
        return self._inner.read(nbytes)

    def write(self, data: bytes) -> None:  # pragma: no cover - read-only
        raise NotImplementedError("Base64InStream is read-only")


class Base64OutStream(Stream):
    """Write bytes, emitting base64 text to the underlying stream on finish()."""

    def __init__(self, fp: BinaryIO):
        self._fp = fp
        self._pending = io.BytesIO()

    def read(self, nbytes: int) -> bytes:  # pragma: no cover - write-only
        raise NotImplementedError("Base64OutStream is write-only")

    def write(self, data: bytes) -> None:
        self._pending.write(data)

    def finish(self) -> None:
        encoded = base64.b64encode(self._pending.getvalue())
        out = self._fp
        try:
            out.write(encoded)
        except TypeError:
            out.write(encoded.decode("ascii"))


class Serializable(ABC):
    """Checkpointable object contract (reference: include/rabit_serializable.h:95-106)."""

    @abstractmethod
    def save(self, stream: Stream) -> None: ...

    @abstractmethod
    def load(self, stream: Stream) -> None: ...

    def to_bytes(self) -> bytes:
        s = MemoryBufferStream()
        self.save(s)
        return s.getvalue()

    def from_bytes(self, data: bytes) -> None:
        self.load(MemoryBufferStream(data))


class PickleSerializable(Serializable):
    """Wrap an arbitrary Python object as a Serializable via pickle.

    Mirrors the reference Python wrapper, where checkpointed models are
    pickled bytes shipped through the C ABI (reference: wrapper/rabit.py:232-297,
    wrapper/rabit_wrapper.cc:120-155).
    """

    def __init__(self, obj: Any = None):
        self.obj = obj

    def save(self, stream: Stream) -> None:
        stream.write_bytes(pickle.dumps(self.obj))

    def load(self, stream: Stream) -> None:
        self.obj = pickle.loads(stream.read_bytes())


# One-byte format tags so checkpoints round-trip regardless of how the
# model was serialized (custom Serializable, raw bytes, or pickle).
_TAG_PICKLE = b"P"
_TAG_SERIALIZABLE = b"S"
_TAG_BYTES = b"B"


def serialize_model(model: Any) -> bytes:
    """Serialize a checkpoint payload: Serializable, bytes, or picklable."""
    if isinstance(model, Serializable):
        return _TAG_SERIALIZABLE + model.to_bytes()
    if isinstance(model, (bytes, bytearray, memoryview)):
        return _TAG_BYTES + bytes(model)
    # straight into one buffer behind the tag, protocol 5: a numpy array
    # goes in as its own bytes, copied once (a history of 168 MB took
    # 0.6 s as `tag + pickle.dumps(model)`: protocol 4's copy of every
    # array, the pickle's, the concatenation's; PERF.md section 6, PR 31)
    out = io.BytesIO()
    out.write(_TAG_PICKLE)
    pickle.dump(model, out, protocol=5)
    return out.getvalue()


def deserialize_model(data: bytes, into: Any = None) -> Any:
    """Inverse of :func:`serialize_model`.

    If ``into`` is a Serializable it is restored in place and returned.
    Serializable-format payloads *require* ``into`` (the byte format is
    defined by the model class, mirroring the reference's
    LoadCheckPoint(ISerializable*) contract, include/rabit.h:214-233).
    """
    tag = data[:1]
    if isinstance(into, Serializable):
        from rabit_tpu.utils.checks import check

        check(tag == _TAG_SERIALIZABLE,
              "load_checkpoint: checkpoint was not saved from a Serializable")
        into.from_bytes(data[1:])
        return into
    if tag == _TAG_BYTES:
        return data[1:]
    if tag == _TAG_SERIALIZABLE:
        from rabit_tpu.utils.checks import error

        error("load_checkpoint: model was checkpointed via Serializable; "
              "pass the model instance to restore into")
    return pickle.loads(memoryview(data)[1:])
