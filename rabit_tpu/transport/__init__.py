"""rabit_tpu.transport — pluggable worker-worker link transports.

Factors every byte the engines move to a peer behind the
:class:`~rabit_tpu.transport.base.Link` interface: the classic TCP path
(``tcp.py``, byte-identical wire), link-level integrity framing
(``framing.py``), the link-generic progress pumps (``pump.py``) and
the negotiating link factory (``factory.py``).

Engine knob (doc/parameters.md "Transports"): ``rabit_wire_integrity``
(off/crc32/crc32c).  Off by default: the default-config wire is
byte-identical to pre-transport releases, and framing is negotiated
per link so mixed-config worlds degrade to the common subset instead
of diverging.

This layer is also the plug point for what comes next: an RDMA/ICI
link is one more Link subclass, and a quantized wire codec (EQuARX-
style, ROADMAP item 1) slots between the engine and the frame layer.
"""
from __future__ import annotations

from rabit_tpu.transport.base import (FRAME_MAX, INTEGRITY_MODES, Events,
                                      IntegrityError, Link, LinkError,
                                      NULL_EVENTS, TransportConfig,
                                      setup_stream_socket)
from rabit_tpu.transport.factory import XMAGIC, LinkFactory
from rabit_tpu.transport.framing import FrameDecoder, encode_frames
from rabit_tpu.transport.pump import HopPipeline, exchange, recv_all
from rabit_tpu.transport.tcp import TcpLink

__all__ = [
    "Link", "LinkError", "IntegrityError", "TransportConfig", "Events",
    "NULL_EVENTS", "LinkFactory", "TcpLink",
    "FrameDecoder", "encode_frames", "exchange", "recv_all",
    "HopPipeline",
    "setup_stream_socket", "XMAGIC", "FRAME_MAX", "INTEGRITY_MODES",
]
