"""The seeded fault plan: deterministic Bernoulli schedules per rule.

A :class:`ChaosPlan` is compiled from a ``seed:SPEC`` string (grammar in
:mod:`rabit_tpu.chaos`) and consulted at every socket touchpoint.  Each
rule keeps its own consult counter, and the fire/skip decision for
consult ``n`` is a pure function of ``(seed, identity, kind, site, n)``
— a CRC32 hash mapped to [0, 1) and compared against the rule's rate.
Nothing in the schedule depends on wall-clock time, thread interleaving
or the behaviour of other rules, so the same seed driven through the
same call sequence reproduces the same injection log bit for bit.

Every fired injection is appended to :attr:`ChaosPlan.log` (the
determinism contract pinned by ``tests/test_chaos.py``) and reported
through the plan's ``on_inject`` callback, which the engines route into
the telemetry subsystem (``chaos.injected.*`` counters + ``chaos``
trace events).
"""
from __future__ import annotations

import socket
import time
import zlib
from typing import Callable, Optional

from rabit_tpu.utils.checks import check, error

# Fault kinds (the wire failure modes real networks produce).
KIND_REFUSE = "refuse"    # connect: ECONNREFUSED (nobody listening yet)
KIND_CTO = "cto"          # connect: SYN timeout (host unreachable / dropped)
KIND_RESET = "reset"      # established link: mid-stream RST
KIND_PARTIAL = "partial"  # established link: short read/write split
KIND_STALL = "stall"      # bounded latency stall (silent slow peer)
KIND_EINTR = "eintr"      # signal-interrupted syscall (EINTR)
# Wire CORRUPTION kinds (the faults integrity framing exists to catch —
# doc/fault_tolerance.md "Links & integrity").  Applied
# at the receive boundary of a transfer, so an injection always lands
# in the byte stream the peer actually produced (a send-side flip could
# fall in the unsent remainder of a partial write and never reach the
# wire, breaking the injected↔detected pairing the gates assert).
KIND_FLIP = "flip"        # one bit XOR'd in one transferred byte
KIND_CORRUPT = "corrupt"  # one transferred byte overwritten

CONNECT_KINDS = (KIND_REFUSE, KIND_CTO, KIND_STALL)
IO_KINDS = (KIND_RESET, KIND_PARTIAL, KIND_STALL, KIND_EINTR,
            KIND_FLIP, KIND_CORRUPT)
KINDS = (KIND_REFUSE, KIND_CTO, KIND_RESET, KIND_PARTIAL, KIND_STALL,
         KIND_EINTR, KIND_FLIP, KIND_CORRUPT)

# Injection sites.  Connect-stage sites see only CONNECT_KINDS; the
# "io" site (established worker-worker links) sees IO_KINDS.
SITE_TRACKER = "tracker"       # tracker command connects
SITE_CONNECT = "connect"       # peer link dials during rendezvous
SITE_ACCEPT = "accept"         # peer link accepts during rendezvous
SITE_IO = "io"                 # established link send/recv
# Control-plane link sites (the sharded tracker's fault surface —
# doc/fault_tolerance.md "Sharded tracker").  Direction-filtered: each
# site is consulted only on the side named here, so an injection always
# lands where its detector lives.
SITE_HELLO = "hello"           # worker→tracker registration exchange
SITE_HB = "hb"                 # worker→tracker heartbeat channel
SITE_SCRAPE = "scrape"         # shard→aggregator obs scrape
# Directory link sites (ISSUE 19: the replicated directory's fault
# surface).  Consulted client-side in DirectoryClient — a reset lands
# in the caller's existing retry/ride path (a shard's bounded register
# retry, a poll tick's failure count, a resolve riding the cached
# snapshot), so every injection pairs with a counted detection.
SITE_DIR_REGISTER = "dir_register"  # shard→directory registration
SITE_DIR_POLL = "dir_poll"          # shard→directory load report
SITE_DIR_RESOLVE = "dir_resolve"    # client→directory snapshot refresh
DIRECTORY_SITES = (SITE_DIR_REGISTER, SITE_DIR_POLL, SITE_DIR_RESOLVE)
# Serving wire sites (ISSUE 20: the prediction service's fault
# surface).  Consulted client-side in the loadgen sender — a reset
# lands in the sender's reconnect-and-retry path and a stall in its
# deadline budget, so every injection pairs with a counted detection
# in the same process that injected it.
SITE_SERVE_REQ = "serve_req"        # client→rank predict request send
SITE_SERVE_REPLY = "serve_reply"    # rank→client reply read
SERVE_SITES = (SITE_SERVE_REQ, SITE_SERVE_REPLY)
CONNECT_SITES = (SITE_TRACKER, SITE_CONNECT, SITE_ACCEPT)
TRACKER_LINK_SITES = (SITE_HELLO, SITE_HB, SITE_SCRAPE)
# Established control-plane links survive only bounded faults: a reset
# (the retry/failover paths must absorb it) or a stall (the deadline
# budgets must absorb it).  Connect-stage kinds already have their own
# site (tracker), and corruption is the data plane's problem.
TRACKER_LINK_KINDS = (KIND_RESET, KIND_STALL)
SITES = (CONNECT_SITES + (SITE_IO,) + TRACKER_LINK_SITES
         + DIRECTORY_SITES + SERVE_SITES)

# Kinds without an explicit @site apply here.
_DEFAULT_SITES = {
    KIND_REFUSE: (SITE_CONNECT, SITE_TRACKER),
    KIND_CTO: (SITE_CONNECT, SITE_TRACKER),
    KIND_RESET: (SITE_IO,),
    KIND_PARTIAL: (SITE_IO,),
    KIND_STALL: (SITE_IO,),
    KIND_EINTR: (SITE_IO,),
    KIND_FLIP: (SITE_IO,),
    KIND_CORRUPT: (SITE_IO,),
}

DEFAULT_BUDGET = 256      # total injections per process life
DEFAULT_STALL_MS = 50.0   # bounded stall duration
DEFAULT_PARTIAL_MAX = 7   # byte cap of a split read/write (odd on purpose)


class ChaosRule:
    """One ``kind@site=rate*limit`` rule with its own consult counter."""

    __slots__ = ("kind", "sites", "rate", "limit", "consults", "fired")

    def __init__(self, kind: str, sites: tuple[str, ...], rate: float,
                 limit: Optional[int]) -> None:
        self.kind = kind
        self.sites = sites
        self.rate = rate
        self.limit = limit      # None = bounded only by the global budget
        self.consults = 0
        self.fired = 0


class ChaosPlan:
    """Compiled fault plan for one worker process.

    ``identity`` is the worker's stable task id (known before the first
    rendezvous assigns a rank, and stable across restarts — under the
    local launcher it is the worker index, and with
    ``RABIT_TRACKER_PIN_RANKS=1`` it equals the rank).  ``on_inject``
    receives ``(kind, site, ordinal, detail)`` for every fired fault.
    """

    def __init__(self, seed: int, rules: list[ChaosRule], identity: str,
                 stall_ms: float = DEFAULT_STALL_MS,
                 budget: int = DEFAULT_BUDGET,
                 partial_max: int = DEFAULT_PARTIAL_MAX,
                 ranks: Optional[set[int]] = None,
                 on_inject: Optional[Callable[[str, str, int, str],
                                              None]] = None) -> None:
        self.seed = int(seed)
        self.identity = str(identity)
        self.stall_ms = float(stall_ms)
        self.budget = int(budget)
        self.partial_max = int(partial_max)
        self.on_inject = on_inject
        self.log: list[tuple[int, str, str, int]] = []  # (ord, kind, site, n)
        self.injected = 0
        self._rules = rules
        self._mutations = 0   # mutate() draw counter (see mutate)
        # Rank scoping: a plan whose ranks filter excludes this identity
        # is inert (parses, logs nothing, injects nothing).
        self.active = True
        if ranks is not None:
            try:
                me = int(self.identity)
            except ValueError:
                me = zlib.crc32(self.identity.encode())
            self.active = me in ranks

    # -- schedule ------------------------------------------------------
    def _draw(self, rule: ChaosRule, site: str) -> bool:
        """Deterministic Bernoulli: consult ``n`` of a rule fires iff
        H(seed, identity, kind, site, n) / 2^32 < rate."""
        rule.consults += 1
        key = (f"{self.seed}:{self.identity}:{rule.kind}:{site}:"
               f"{rule.consults}").encode()
        return (zlib.crc32(key) & 0xFFFFFFFF) / 4294967296.0 < rule.rate

    def _consult(self, site: str,
                 kinds: Optional[tuple[str, ...]] = None) -> Optional[str]:
        """One injection decision at ``site``; returns the fired kind or
        None.  Rules are evaluated in spec order; the first that fires
        wins (at most one fault per touchpoint).  ``kinds`` restricts
        which rules this touchpoint can draw (a link's send and
        receive touchpoints serve different fault kinds — see
        :meth:`io`); per-rule consult counters keep the schedule
        deterministic either way."""
        if not self.active or self.injected >= self.budget:
            return None
        for rule in self._rules:
            if site not in rule.sites:
                continue
            if kinds is not None and rule.kind not in kinds:
                continue
            if rule.limit is not None and rule.fired >= rule.limit:
                continue
            if self._draw(rule, site):
                rule.fired += 1
                self.injected += 1
                self.log.append((len(self.log), rule.kind, site,
                                 rule.consults))
                if self.on_inject is not None:
                    self.on_inject(rule.kind, site, len(self.log) - 1,
                                   f"consult={rule.consults}")
                return rule.kind
        return None

    # -- touchpoints ---------------------------------------------------
    def connect(self, site: str) -> None:
        """Consult before a connect/accept syscall; raises the injected
        connect failure (or sleeps through an injected stall)."""
        kind = self._consult(site)
        if kind is None:
            return
        if kind == KIND_STALL:
            time.sleep(self.stall_ms / 1000.0)
            return
        if kind == KIND_REFUSE:
            raise ConnectionRefusedError(
                f"[chaos] injected connection refusal at {site}")
        if kind == KIND_CTO:
            raise socket.timeout(
                f"[chaos] injected connect timeout at {site}")

    def io(self, kinds: Optional[tuple[str, ...]] = None
           ) -> Optional[str]:
        """Consult before one established-link send/recv syscall.
        Returns the fired kind (the socket wrapper applies it) or None.
        Stalls are served here — the wrapper then proceeds with the
        real, now-delayed syscall.  ``kinds`` restricts what this
        touchpoint can draw: send-side consults exclude flip/corrupt
        (corruption manifests in RECEIVED bytes, so firing it at a
        send could vanish into an unsent remainder and break the
        injected↔detected pairing the integrity gates assert)."""
        kind = self._consult(SITE_IO, kinds)
        if kind == KIND_STALL:
            time.sleep(self.stall_ms / 1000.0)
            return None
        return kind

    def link(self, site: str,
             kinds: Optional[tuple[str, ...]] = None) -> Optional[str]:
        """Consult at one control-plane link touchpoint (the hello
        exchange, a heartbeat send, an aggregator scrape — each names
        its site, so rules stay direction-filtered).  Same contract as
        :meth:`io`: stalls are served here and return None; a reset is
        returned for the caller to apply as its link failure (the
        worker raises ``ConnectionResetError`` into its existing
        retry path, the aggregator counts a failed scrape).  Only
        ``TRACKER_LINK_KINDS`` can fire, and only for rules that named
        this site explicitly — control-plane rules never perturb the
        data-plane schedules (per-rule consult counters)."""
        kind = self._consult(site, kinds if kinds is not None
                             else TRACKER_LINK_KINDS)
        if kind == KIND_STALL:
            time.sleep(self.stall_ms / 1000.0)
            return None
        return kind

    def mutate(self, mv, kind: str) -> None:
        """Deterministically damage ``mv`` in place for a fired
        flip/corrupt injection.  Position and bit ride the same
        hash family as the schedule itself (keyed by a dedicated
        mutation counter), so a replayed seed reproduces the identical
        damage whenever the transfer sizes line up.  XOR damage is
        never a no-op, so every fired corruption is a REAL corruption —
        the injected↔detected pairing gate depends on it."""
        n = len(mv)
        if n == 0:
            return
        self._mutations += 1
        h = zlib.crc32(f"{self.seed}:{self.identity}:mut:"
                       f"{self._mutations}".encode()) & 0xFFFFFFFF
        pos = h % n
        if kind == KIND_FLIP:
            mv[pos] ^= 1 << ((h >> 8) & 7)
        else:  # corrupt
            mv[pos] ^= ((h >> 8) & 0xFF) or 0xA5

    def summary(self) -> dict:
        """Per-rule fire counts (for logs and reproduce lines)."""
        return {f"{r.kind}@{'|'.join(r.sites)}": r.fired
                for r in self._rules}


def parse_plan(spec: str, identity: str,
               on_inject: Optional[Callable[[str, str, int, str],
                                            None]] = None) -> ChaosPlan:
    """Compile a ``seed:SPEC`` string (see the package docstring for the
    grammar) into a :class:`ChaosPlan`.  Malformed specs fail loudly —
    a chaos run with a silently-dropped rule would report vacuous green.
    """
    check(":" in spec, "rabit_chaos must be 'seed:SPEC', got %r", spec)
    seed_s, _, body = spec.partition(":")
    try:
        seed = int(seed_s)
    except ValueError:
        error("rabit_chaos seed must be an integer, got %r", seed_s)
    rules: list[ChaosRule] = []
    stall_ms = DEFAULT_STALL_MS
    budget = DEFAULT_BUDGET
    partial_max = DEFAULT_PARTIAL_MAX
    ranks: Optional[set[int]] = None
    for part in body.split(";"):
        part = part.strip()
        if not part:
            continue
        check("=" in part, "rabit_chaos rule %r: expected key=value", part)
        key, _, val = part.partition("=")
        key = key.strip()
        val = val.strip()
        if key == "stallms":
            stall_ms = float(val)
            check(stall_ms >= 0, "rabit_chaos: stallms must be >= 0")
            continue
        if key == "budget":
            budget = int(val)
            check(budget >= 0, "rabit_chaos: budget must be >= 0")
            continue
        if key == "partialmax":
            partial_max = int(val)
            check(partial_max >= 1, "rabit_chaos: partialmax must be >= 1")
            continue
        if key == "ranks":
            ranks = {int(r) for r in val.split("|") if r.strip() != ""}
            continue
        kind, _, site = key.partition("@")
        check(kind in KINDS, "rabit_chaos: unknown fault kind %r (one of "
              "%s)", kind, "/".join(KINDS))
        if site:
            check(site in SITES, "rabit_chaos: unknown site %r (one of "
                  "%s)", site, "/".join(SITES))
            if site == SITE_IO:
                allowed: tuple[str, ...] = IO_KINDS
            elif site == SITE_ACCEPT:
                # An accept has no retry path to absorb a refusal (the
                # dialing PEER owns the retry), so only stalls make a
                # survivable injection here.
                allowed = (KIND_STALL,)
            elif site in (TRACKER_LINK_SITES + DIRECTORY_SITES
                          + SERVE_SITES):
                allowed = TRACKER_LINK_KINDS
            else:
                allowed = CONNECT_KINDS
            check(kind in allowed, "rabit_chaos: kind %r cannot fire at "
                  "site %r", kind, site)
            sites: tuple[str, ...] = (site,)
        else:
            sites = _DEFAULT_SITES[kind]
        rate_s, _, limit_s = val.partition("*")
        try:
            rate = float(rate_s)
        except ValueError:
            error("rabit_chaos rule %r: rate %r is not a number",
                  part, rate_s)
        check(0.0 <= rate <= 1.0,
              "rabit_chaos rule %r: rate must be in [0, 1]", part)
        limit = None
        if limit_s:
            limit = int(limit_s)
            check(limit >= 0, "rabit_chaos rule %r: limit must be >= 0",
                  part)
        rules.append(ChaosRule(kind, sites, rate, limit))
    check(bool(rules), "rabit_chaos %r names no fault rules", spec)
    return ChaosPlan(seed, rules, identity, stall_ms=stall_ms,
                     budget=budget, partial_max=partial_max, ranks=ranks,
                     on_inject=on_inject)
