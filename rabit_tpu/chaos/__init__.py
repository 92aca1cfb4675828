"""rabit_tpu.chaos — deterministic network fault injection.

The paper's reliability claim is only as strong as the failure modes the
test harness can produce.  ``RABIT_MOCK`` kill-points exit cleanly at op
boundaries; this subsystem injects the faults real networks produce —
refused and timed-out connects, mid-stream connection resets, short
read/write splits, EINTR, and bounded latency stalls — at every socket
touchpoint of the pure-Python engines (tracker connects, peer link
dials/accepts, established-link IO in the exchange paths and the async
progress pump).  The schedule is **seeded and deterministic**: the same
seed driven through the same call sequence reproduces the same
injection log bit for bit, so a chaos failure found in CI replays
locally from one string.

Enable with the ``rabit_chaos`` parameter / ``RABIT_CHAOS`` env (same
spirit as the ``RABIT_MOCK`` tuple format):

    RABIT_CHAOS = "<seed>:<rule>[;<rule>...]"
    rule        = <kind>[@<site>]=<rate>[*<limit>]
                | stallms=<ms> | budget=<n> | partialmax=<bytes>
                | ranks=<r0|r1|...>

Kinds: ``refuse`` (ECONNREFUSED), ``cto`` (connect timeout), ``reset``
(mid-stream RST), ``partial`` (short read/write split), ``stall``
(bounded sleep), ``eintr`` (interrupted syscall), ``flip`` (one wire
bit XOR'd in a transferred byte) and ``corrupt`` (one transferred byte
overwritten) — the corruption kinds integrity framing
(``rabit_wire_integrity``) exists to catch.
Sites: ``tracker`` and ``connect`` (connect-stage kinds), ``accept``
and ``io`` (established worker-worker links; the default for
reset/partial/stall/eintr/flip/corrupt).  The ``accept`` site admits only
``stall`` — an accept has no retry path to absorb a refusal (the
dialing peer owns the retry).  Control-plane link sites (sharded
tracker, doc/fault_tolerance.md "Sharded tracker"): ``hello`` (the
worker→tracker registration exchange), ``hb`` (the heartbeat channel)
and ``scrape`` (the shard→aggregator obs scrape) admit only
``reset``/``stall``, must be named explicitly (no kind defaults to
them), and are direction-filtered — each fires on the side whose
detector the pairing gates read.  The replicated
directory adds ``dir_register`` / ``dir_poll`` / ``dir_resolve``
(same reset/stall vocabulary, consulted in ``DirectoryClient`` where
the bounded-retry / ride-the-cache detectors live).  The serving wire
adds ``serve_req`` / ``serve_reply`` (same vocabulary, consulted in
the loadgen sender where the reconnect-retry / deadline detectors
live — doc/serving.md "Chaos on the serving wire").
``rate`` is a per-touchpoint probability in [0, 1]; ``*limit`` caps a
rule's total fires; ``budget`` (default 256) caps the whole plan;
``ranks`` scopes the plan to specific worker identities (task ids —
equal to ranks under ``RABIT_TRACKER_PIN_RANKS=1``).  Example — one
mid-stream reset and flaky rendezvous dials, reproducible under seed 7:

    RABIT_CHAOS="7:reset@io=0.01*1;refuse@connect=0.3*4;partial@io=0.05"

See doc/fault_tolerance.md "Chaos testing" for the fault/recovery
pairing the obs timeline records, and ``tools/soak.py --chaos`` for the
randomized soak gate.  The chaos layer lives entirely in the Python
engines (pysocket/pyrobust and the XLA engine's host control plane);
the native C++ engine does not see it.
"""
from __future__ import annotations

import os
from typing import Callable, Optional

from rabit_tpu.chaos.plan import (CONNECT_KINDS, CONNECT_SITES,
                                  DEFAULT_BUDGET, DEFAULT_PARTIAL_MAX,
                                  DEFAULT_STALL_MS, DIRECTORY_SITES,
                                  IO_KINDS, KIND_CORRUPT,
                                  KIND_CTO, KIND_EINTR,
                                  KIND_FLIP, KIND_PARTIAL, KIND_REFUSE,
                                  KIND_RESET, KIND_STALL, KINDS,
                                  SERVE_SITES, SITE_ACCEPT,
                                  SITE_CONNECT,
                                  SITE_DIR_POLL, SITE_DIR_REGISTER,
                                  SITE_DIR_RESOLVE,
                                  SITE_HB, SITE_HELLO, SITE_IO, SITE_SCRAPE,
                                  SITE_SERVE_REPLY, SITE_SERVE_REQ,
                                  SITE_TRACKER, SITES,
                                  TRACKER_LINK_KINDS, TRACKER_LINK_SITES,
                                  ChaosPlan, ChaosRule, parse_plan)
from rabit_tpu.chaos.sock import ChaosSocket


def configure(params: dict, identity: str,
              on_inject: Optional[Callable[[str, str, int, str],
                                           None]] = None
              ) -> Optional[ChaosPlan]:
    """Resolve ``rabit_chaos`` / ``RABIT_CHAOS`` into a compiled
    :class:`ChaosPlan`, or None when chaos is off (the common case —
    the engines then skip every touchpoint with one ``is None`` check).
    Called from the Python engines' ``init()``."""
    spec = params.get("rabit_chaos")
    if spec is None:
        spec = os.environ.get("RABIT_CHAOS", "")
    spec = str(spec).strip()
    if not spec:
        return None
    return parse_plan(spec, identity, on_inject=on_inject)


__all__ = [
    "ChaosPlan", "ChaosRule", "ChaosSocket", "configure", "parse_plan",
    "KINDS", "SITES", "CONNECT_KINDS", "IO_KINDS", "CONNECT_SITES",
    "KIND_REFUSE", "KIND_CTO", "KIND_RESET", "KIND_PARTIAL", "KIND_STALL",
    "KIND_EINTR", "KIND_FLIP", "KIND_CORRUPT",
    "SITE_TRACKER", "SITE_CONNECT", "SITE_ACCEPT",
    "SITE_IO", "SITE_HELLO", "SITE_HB", "SITE_SCRAPE",
    "SITE_DIR_REGISTER", "SITE_DIR_POLL", "SITE_DIR_RESOLVE",
    "SITE_SERVE_REQ", "SITE_SERVE_REPLY", "SERVE_SITES",
    "TRACKER_LINK_KINDS", "TRACKER_LINK_SITES", "DIRECTORY_SITES",
    "DEFAULT_BUDGET", "DEFAULT_STALL_MS", "DEFAULT_PARTIAL_MAX",
]
