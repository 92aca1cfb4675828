"""The schedule auto-tuner's persisted measurement cache.

``bench.py --suite collectives`` measures per-(payload, world) MB/s for
every applicable schedule and — given ``--tune-dir`` — persists the
winners here, the way obs reports are persisted: a versioned JSON file
under a caller-chosen directory (an ``--obs-dir`` sibling), written
atomically (tmp + rename).  At runtime ``rabit_sched=auto`` loads the
cache once at ``init()`` and picks the measured winner for each
dispatch point (nearest benchmarked size in log space, exact world
match); any miss — no cache, schema drift, unknown schedule, world
never benchmarked — falls back to the static tree/ring crossover.

The cache MUST be identical on every rank (schedule choice is a
collective decision, like ``rabit_bucket_bytes``): point every rank at
the same file, e.g. a shared filesystem path or a per-host copy of the
same tuning run (doc/performance.md "Schedule selection").
"""
from __future__ import annotations

import json
import math
import os
import tempfile
from typing import Optional

from rabit_tpu.utils.checks import log

#: bump when the on-disk layout changes; readers reject other versions
SCHEMA_VERSION = 1
CACHE_FILENAME = "sched_cache.json"


class TuningCache:
    """In-memory form of the persisted tuning table.

    ``table`` maps op kind -> world (str) -> payload bytes (str) ->
    winning schedule name; ``meta`` carries provenance (schema, host,
    world, bench row) so a recorded cache explains itself.
    """

    def __init__(self, table: dict, meta: dict | None = None) -> None:
        self.table = table
        self.meta = dict(meta or {})
        # Nearest-world fallback memo: pick() sits on the per-collective
        # dispatch hot path, so the full-table scan runs once per
        # (kind, world) — every later miss is a dict hit — and the
        # structured-log note fires once with it.
        self._world_fallback: dict = {}

    # ------------------------------------------------------------- build
    @staticmethod
    def table_kind(kind: str, codec: str = "none") -> str:
        """Cache-table key for an op kind on a wire codec.  ``none``
        keeps the bare kind (every pre-existing cache keeps working);
        any other codec gets its own ``kind+codec`` rows, so a winner
        measured over a quantized wire (whose per-payload wire bytes —
        hence crossovers — genuinely differ) never answers a full-width
        job, or vice versa.  (A cache written by an older release may
        hold ``kind@<transport>`` rows: no lookup forms that key any
        more, so they load and answer nothing.)"""
        if codec not in ("", "none", None):
            kind = f"{kind}+{codec}"
        return kind

    @classmethod
    def from_bench(cls, per_size_mbps: dict, world: int, *,
                   host: str = "", candidates=None,
                   extra_meta: dict | None = None,
                   codec: str = "none") -> "TuningCache":
        """Build from the per-size MB/s table the collectives bench
        emits (``{"<bytes>": {"tree": MBps, "ring": ..., ...}}``).
        ``candidates`` restricts which columns may win (the bench also
        measures non-schedule paths like ``bucketed``); ``codec`` keys
        the rows to the wire format they were measured on."""
        best: dict[str, str] = {}
        for size, row in per_size_mbps.items():
            cand = {k: float(v) for k, v in row.items()
                    if candidates is None or k in candidates}
            if cand:
                best[str(int(size))] = max(cand, key=cand.get)
        meta = {"host": host, "world": int(world), "codec": codec}
        meta.update(extra_meta or {})
        return cls({cls.table_kind("allreduce", codec):
                    {str(int(world)): best}}, meta)

    # --------------------------------------------------------------- io
    def save(self, dir_path: str) -> str:
        """Atomic persist under ``dir_path`` (created if missing);
        returns the cache file path."""
        os.makedirs(dir_path, exist_ok=True)
        path = os.path.join(dir_path, CACHE_FILENAME)
        payload = {"schema": SCHEMA_VERSION, "meta": self.meta,
                   "table": self.table}
        fd, tmp = tempfile.mkstemp(dir=dir_path, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return path

    @classmethod
    def load(cls, path: str) -> Optional["TuningCache"]:
        """Load from a cache file or a directory holding one.  Returns
        None (never raises) on anything unusable — a missing file,
        corrupt JSON, or a schema version this reader does not speak —
        so ``auto`` degrades to the static crossover instead of
        refusing to start."""
        if os.path.isdir(path):
            path = os.path.join(path, CACHE_FILENAME)
        try:
            with open(path) as f:
                payload = json.load(f)
        except (OSError, ValueError):
            return None
        if not isinstance(payload, dict):
            return None
        if payload.get("schema") != SCHEMA_VERSION:
            return None
        table = payload.get("table")
        if not isinstance(table, dict):
            return None
        return cls(table, payload.get("meta") or {})

    # ---------------------------------------------------------- online
    def merge_online(self, kind: str, world: int, nbytes: int,
                     name: str, codec: str = "none") -> None:
        """Fold one LIVE measurement verdict into the table: the
        adaptive controller decided ``name`` wins ``(kind, world,
        payload bucket)`` from rolling span data (doc/performance.md
        "Online adaptation").  Widens the cache's world coverage — a
        bench'd cache learns worlds the bench never ran — and the next
        ``rabit_sched=auto`` job at this world starts on the learned
        schedule instead of re-discovering it.  ``codec`` keys the rows
        (:meth:`table_kind`): quantized-wire verdicts must never answer
        a full-width job, or vice versa."""
        rows = self.table.setdefault(
            self.table_kind(kind, codec), {}).setdefault(
            str(int(world)), {})
        rows[str(int(nbytes))] = str(name)
        self._world_fallback.clear()  # coverage changed: re-derive
        self.meta["online_merges"] = int(
            self.meta.get("online_merges", 0)) + 1

    # ------------------------------------------------------------- query
    def pick(self, kind: str, nbytes: int, world: int,
             codec: str = "none") -> Optional[str]:
        """Winning schedule name for the nearest benchmarked payload
        size (log-space distance), or None.  An exact world match wins;
        a world the cache never saw falls back to the NEAREST bench'd
        world in log space (noted once per world in the structured log)
        instead of silently dropping to static — peer patterns scale
        smoothly enough in log(world) that a neighboring world's winner
        beats no information at all.  ``codec`` scopes the lookup to
        rows measured on the same wire format (:meth:`table_kind`) — an
        int8 world with no matching rows misses to static rather than
        borrowing full-width numbers whose crossovers don't apply."""
        kind = self.table_kind(kind, codec)
        table = self.table.get(kind)
        if not table:
            return None
        key = str(int(world))
        rows = table.get(key)
        if not rows:
            # Miss: resolve (and memoize) the nearest bench'd world —
            # the scan runs once per (kind, world), not once per op.
            near = self._world_fallback.get((kind, key), "")
            if near == "":
                worlds = [w for w, r in table.items()
                          if r and str(w).isdigit()]
                if worlds:
                    wt = math.log(max(int(world), 1))
                    near = min(sorted(worlds),
                               key=lambda w: (abs(math.log(int(w)) - wt),
                                              int(w)))
                    log("tuner: no %s rows for world %d; falling back "
                        "to the nearest bench'd world %s", kind, world,
                        near)
                else:
                    near = None
                self._world_fallback[(kind, key)] = near
            if near is None:
                return None
            rows = table[near]
            # A neighbor world's coverage may be SPARSE (a single
            # online-merged bucket): compounding the world fallback
            # with unbounded size extrapolation would let that one row
            # answer every payload — e.g. a 64-byte op picking a
            # bandwidth schedule learned at 512KB.  On the fallback
            # path only, sizes further than two octaves from any
            # covered row miss to static (the exact-world pick keeps
            # its original unbounded nearest-size semantics).
            target = math.log(max(int(nbytes), 1))
            size = min(rows, key=lambda s: abs(
                math.log(max(int(s), 1)) - target))
            if abs(math.log(max(int(size), 1)) - target) > math.log(4.0):
                return None
            name = rows[size]
            return str(name) if name else None
        target = math.log(max(int(nbytes), 1))
        size = min(rows, key=lambda s: abs(
            math.log(max(int(s), 1)) - target))
        name = rows[size]
        return str(name) if name else None


# ---------------------------------------------------------------------
# live schedule directives (the adaptive controller's wire format)
# ---------------------------------------------------------------------
# A directive is a tiny per-payload-bucket override table the tracker's
# AdaptiveController pushes with the topology at a schedule-switch
# epoch (rabit_tpu/obs/adapt.py): "``bytes:name``" entries joined by
# commas, e.g. "524288:swing" or "262144:halving,4194304:hier".  The
# engine consults it like a one-job tuning cache (nearest bucket in
# log space) before the static/auto pick.  Encoded as a plain string
# so it rides the topology reply as one trailing field and tolerates
# version skew (an unknown entry is simply skipped).
#
# A directive entry may additionally carry a PER-OP CODEC OVERRIDE:
# "``bytes:name/codec``" (e.g. "4194304:ring/int8") asks the engine to
# run the dominant bucket's eligible ops on that wire codec regardless
# of the job's ``rabit_wire_codec`` — the schedule verdict and the wire
# format it was measured on travel together.  The old plain-name form
# parses unchanged in both directions, and on a pre-codec-directive
# engine the slashed name simply misses the schedule registry and falls
# through to the static/auto pick (the entry degrades, never deadlocks
# — which is also why a controller should only emit the slashed form to
# a world it knows speaks it).

def encode_directive(table: dict[int, str]) -> str:
    return ",".join(f"{int(b)}:{n}" for b, n in sorted(table.items()))


def decode_directive(raw: str) -> dict[int, str]:
    """Parse a directive string; malformed entries are skipped, never
    raised — the string arrives from the network."""
    out: dict[int, str] = {}
    for part in str(raw or "").split(","):
        if ":" not in part:
            continue
        b, name = part.split(":", 1)
        name = name.strip()
        try:
            bucket = int(b)
        except ValueError:
            continue
        if bucket > 0 and name:
            out[bucket] = name
    return out


def _directive_value(table: dict[int, str],
                     nbytes: int) -> Optional[str]:
    """Raw directive entry for one payload: nearest bucket in log
    space — capped at two octaves, like the cache's nearest-world
    fallback.  The controller only writes the DOMINANT bucket, so an
    uncapped nearest pick would steer every stray small op onto the
    dominant bucket's bandwidth schedule (a 4KB op has no business
    riding a directive learned at 512KB); out-of-range sizes fall
    through to the engine's static/auto pick instead."""
    if not table:
        return None
    target = math.log(max(int(nbytes), 1))
    bucket = min(table, key=lambda b: abs(math.log(max(b, 1)) - target))
    if abs(math.log(max(bucket, 1)) - target) > math.log(4.0):
        return None
    return table[bucket]


def directive_entry(table: dict[int, str],
                    nbytes: int) -> tuple[Optional[str], Optional[str]]:
    """``(schedule, codec)`` for one payload — the codec is None for
    the classic plain-name entry form ("use the job's codec") and a
    codec name for the slashed ``name/codec`` per-op override form."""
    raw = _directive_value(table, nbytes)
    if raw is None:
        return None, None
    if "/" in raw:
        name, codec = raw.split("/", 1)
        return (name.strip() or None), (codec.strip() or None)
    return raw, None


def directive_pick(table: dict[int, str], nbytes: int) -> Optional[str]:
    """The directive's SCHEDULE verdict for one payload (codec
    stripped; see :func:`directive_entry` for both halves)."""
    return directive_entry(table, nbytes)[0]


def directive_codec(table: dict[int, str],
                    nbytes: int) -> Optional[str]:
    """The directive's per-op CODEC override for one payload, or None
    when the entry keeps the job default."""
    return directive_entry(table, nbytes)[1]
