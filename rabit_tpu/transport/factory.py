"""Link construction: handshake and feature negotiation.

The engine dials/accepts raw TCP sockets exactly as before (retry and
backoff stay engine-side); the factory turns each established socket
into a :class:`~rabit_tpu.transport.base.Link`:

* **Default config** sends the CLASSIC handshake — ``u32 MAGIC, u32
  rank`` each way — so the wire is byte-identical to every previous
  release and to old peers.
* A worker with ``rabit_wire_integrity`` configured opens with
  ``XMAGIC`` instead and appends one feature string ("crc32c").  A
  this-release acceptor MIRRORS whichever magic it received and answers
  with its OWN offer (possibly empty), and each feature activates only
  in the INTERSECTION of the two offers — so a featured worker and a
  default-config worker interoperate in both directions, each link
  degrading to the common subset.  (A featured worker dialing a
  pre-feature BINARY fails the peer's magic check — enabling the
  opt-in knob requires the world upgraded, which is the documented
  contract.)
"""
from __future__ import annotations

import socket
from typing import Optional

from rabit_tpu.tracker import protocol as P
from rabit_tpu.transport.base import (Events, Link, LinkPacer, NULL_EVENTS,
                                      TransportConfig,
                                      setup_stream_socket)
from rabit_tpu.transport.tcp import TcpLink
from rabit_tpu.utils.checks import check

#: feature-negotiating link hello (the classic hello is protocol.MAGIC)
XMAGIC = 0x7AB17912
#: feature-string length cap (a handshake read, so bounded like all of
#: them — see protocol.MAX_HELLO_STR for the rationale)
MAX_FEATURES = 256


def _parse_offer(raw: str) -> str:
    """The integrity mode a peer's feature string offers (``""`` for
    none): ``"crc32c,shm:1048576"`` → ``"crc32c"``.  Unknown tokens are
    IGNORED: a newer peer may offer features we cannot parse, and a
    peer of an older release may still offer the ``shm:<bytes>`` rings
    this release no longer has — the intersection simply excludes
    them."""
    crc = ""
    for tok in raw.split(","):
        tok = tok.strip()
        if tok in ("crc32", "crc32c"):
            crc = tok
    return crc


class LinkFactory:
    """Per-engine link builder; ``rank`` follows the engine across
    rendezvous rounds, the config never changes."""

    def __init__(self, cfg: TransportConfig, *,
                 timeout: Optional[float], sock_buf: int = 0,
                 wrap=None, events: Events = NULL_EVENTS,
                 log=None) -> None:
        self.cfg = cfg
        self.timeout = timeout
        self.sock_buf = sock_buf
        self.wrap = wrap             # chaos socket wrapper (data path)
        self.events = events
        self.log = log
        self.rank = 0

    def _offer(self) -> str:
        return self.cfg.integrity if self.cfg.wants_integrity else ""

    # ------------------------------------------------------------------
    # handshake
    # ------------------------------------------------------------------
    def dial(self, sock: socket.socket, peer: int) -> Link:
        """Upgrade an engine-dialed socket into a Link (dialer side of
        the link handshake)."""
        setup_stream_socket(sock, self.timeout, self.sock_buf)
        mine = self._offer()
        if not mine:
            # Classic bytes: identical to every pre-transport release.
            P.send_u32(sock, P.MAGIC)
            P.send_u32(sock, self.rank)
            check(P.recv_u32(sock) == P.MAGIC, "link handshake: bad magic")
            check(P.recv_u32(sock) == peer, "link handshake: rank mismatch")
            return self._tcp_link(sock, peer, frames=False)
        P.send_u32(sock, XMAGIC)
        P.send_u32(sock, self.rank)
        P.send_str(sock, mine)
        check(P.recv_u32(sock) == XMAGIC, "link handshake: bad magic "
              "(peer does not speak transport negotiation — upgrade it "
              "or clear rabit_wire_integrity)")
        check(P.recv_u32(sock) == peer, "link handshake: rank mismatch")
        theirs = _parse_offer(P.recv_str(sock, max_len=MAX_FEATURES))
        return self._tcp_link(sock, peer,
                              frames=self._crc_agreed(peer, theirs))

    def accept(self, sock: socket.socket) -> tuple[Link, int]:
        """Acceptor side; returns ``(link, peer_rank)``."""
        setup_stream_socket(sock, self.timeout, self.sock_buf)
        magic = P.recv_u32(sock)
        if magic == P.MAGIC:
            peer = P.recv_u32(sock)
            P.send_u32(sock, P.MAGIC)
            P.send_u32(sock, self.rank)
            return self._tcp_link(sock, peer, frames=False), peer
        check(magic == XMAGIC, "link handshake: bad magic")
        peer = P.recv_u32(sock)
        theirs = _parse_offer(P.recv_str(sock, max_len=MAX_FEATURES))
        P.send_u32(sock, XMAGIC)
        P.send_u32(sock, self.rank)
        P.send_str(sock, self._offer())
        return self._tcp_link(sock, peer,
                              frames=self._crc_agreed(peer, theirs)), peer

    def _crc_agreed(self, peer: int, theirs: str) -> bool:
        """Integrity activates only when both ends offered the SAME
        mode name: the two names are interchangeable today (both the
        stdlib CRC-32), but the moment ``crc32c`` becomes a real
        Castagnoli a mixed-mode link would reject every frame as
        corruption — so a mismatch deactivates framing (loudly) rather
        than arming a time bomb."""
        if not self.cfg.wants_integrity or not theirs:
            return False
        if self.cfg.integrity == theirs:
            return True
        if self.log is not None:
            self.log.warn(
                "integrity mode mismatch with rank %d (%s vs %s): "
                "framing DISABLED on this link — align "
                "rabit_wire_integrity across the world", peer,
                self.cfg.integrity, theirs)
        return False

    # ------------------------------------------------------------------
    # link construction
    # ------------------------------------------------------------------
    def _tcp_link(self, sock: socket.socket, peer: int,
                  frames: bool) -> Link:
        data_sock = self.wrap(sock, peer) if self.wrap is not None \
            else sock
        self.events.counter("transport.links.tcp")
        # One pacer per link (rabit_link_mbps, bench/test knob): each
        # direction of each peer pair paces independently, like per-NIC
        # egress queues on a real constrained hop.
        pacer = (LinkPacer(self.cfg.link_mbps)
                 if self.cfg.link_mbps > 0 else None)
        return TcpLink(data_sock, peer, self.timeout, self.events,
                       frames=frames, pacer=pacer)
