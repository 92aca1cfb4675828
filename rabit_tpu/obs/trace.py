"""Bounded structured event trace, causal hop tracing and the flight
recorder.

Four pieces (doc/observability.md "Causal tracing & postmortem"):

* :class:`EventTrace` — a fixed-capacity ring buffer of structured
  events — op begin/end (one complete event carrying ``ts``+``dur``),
  link errors, recovery phases, checkpoint commits — dumpable as JSON
  lines and as the Chrome trace format (`chrome://tracing` / Perfetto
  "Trace Event Format").  Bounded so a long job's trace memory is
  configuration (`rabit_obs_events`), not runtime; eviction drops the
  oldest events.
* :class:`HopBuffer` (worker side) — compact per-hop/per-chunk records
  from the sampled ops (``rabit_trace_sample``), drained into the
  streaming obs frames like spans;
* :class:`TraceAssembler` (tracker side) — folds every rank's hop
  records into one skew-corrected causal timeline per op (clock offsets
  calibrated from the heartbeat frame timestamps + the hb-RTT echo
  samples), names the binding (rank, link, hop) per collective, folds
  per-link cost tables and exports Chrome-trace/Perfetto JSON;
* :class:`FlightRecorder` — the always-on bounded crash ring: recent
  wire/engine events plus the op in flight, persisted atomically on
  every fault path (LinkError escalation, recovery budget exhaustion,
  SIGTERM, serve drain) for ``tools/postmortem.py`` to reconstruct a
  dead job's last seconds.

Timestamps are ``time.time()`` epoch seconds so traces from different
ranks merge on one timeline; durations are measured by the caller with
``perf_counter`` and events with a duration are stamped at their START
(``ts = now - dur``), which is what the Chrome ``"X"`` phase expects.
"""
from __future__ import annotations

import collections
import json
import os
import statistics
import threading
import time


class EventTrace:
    """Thread-safe ring buffer of event dicts."""

    def __init__(self, capacity: int = 4096) -> None:
        self._buf: collections.deque = collections.deque(maxlen=max(capacity, 1))
        self._lock = threading.Lock()
        # Eviction is silent by deque design; this counter is the
        # signal (shipped as ``obs.events_dropped``, rendered by
        # obs_report) that a trace window was too small for the job.
        self.dropped = 0

    @property
    def capacity(self) -> int:
        return self._buf.maxlen

    def emit(self, name: str, /, *, ts: float | None = None,
             dur: float | None = None, **fields) -> None:
        """Append one event.  ``name`` is the event family ("op",
        "recovery", "checkpoint", ...); ``fields`` carry the structured
        payload (kind/bytes/seqno/version/phase/...).  None-valued
        fields are dropped."""
        if ts is None:
            ts = time.time() - (dur or 0.0)
        ev = {"ts": ts, "name": name}
        if dur is not None:
            ev["dur"] = dur
        for k, v in fields.items():
            if v is not None:
                ev[k] = v
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1
            self._buf.append(ev)

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._buf)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()

    def __len__(self) -> int:
        return len(self._buf)

    def to_jsonl(self) -> str:
        """One JSON object per line (the on-disk ``events.rank*.jsonl``
        format the report tool consumes)."""
        return "".join(json.dumps(e) + "\n" for e in self.events())


def chrome_trace(events: list[dict], default_pid: int = 0) -> list[dict]:
    """Convert event dicts to Chrome "Trace Event Format" entries.

    Events with a duration become complete ("X") slices; the rest become
    instants ("i").  ``rank`` maps to the Chrome pid lane so a merged
    multi-rank dump renders one row per rank; times are microseconds
    relative to the earliest event.
    """
    if not events:
        return []
    t0 = min(e["ts"] for e in events)
    out = []
    for e in events:
        entry = {
            "name": str(e.get("phase") or e.get("kind") or e.get("name")),
            "cat": str(e.get("name", "event")),
            "pid": int(e.get("rank", default_pid)),
            "tid": 0,
            "ts": (e["ts"] - t0) * 1e6,
            "args": {k: v for k, v in e.items()
                     if k not in ("ts", "dur", "name")},
        }
        if e.get("dur") is not None:
            entry["ph"] = "X"
            entry["dur"] = e["dur"] * 1e6
        else:
            entry["ph"] = "i"
            entry["s"] = "p"  # process-scoped instant
        out.append(entry)
    return out


# ----------------------------------------------------------------------
# causal hop tracing (doc/observability.md "Causal tracing & postmortem")
# ----------------------------------------------------------------------

# One hop/chunk/codec-window record, shipped as a positional list like
# spans (span.py SPAN_FIELDS) so a frame full of them stays small.
# ``phase`` is "hop" (one _hop_exchange_merge call, or one tree phase
# on the tree schedule small worlds default to), "chunk" (one
# pipelined merge window inside a hop), "encode"/"decode" (the codec
# windows); ``hop`` is the op-local hop index and ``peer`` the send-side
# neighbour (the egress link the hop loaded; -1 for the codec windows,
# which touch no wire).  (t0, t1) are the emitting RANK's epoch-seconds
# clock — the assembler corrects them onto the tracker's timeline.
HOP_FIELDS = ("seq", "epoch", "version", "kind", "hop", "peer", "phase",
              "nbytes", "t0", "t1")

# "Default sampling" when tracing is armed without an explicit rate:
# trace every 64th op.  Coarse enough that the bench gate's <3%
# obs-overhead budget holds, fine enough that a minute of training
# yields dozens of assembled timelines.
DEFAULT_TRACE_SAMPLE = 64
# Flight-recorder ring capacity (rabit_flight_events).
DEFAULT_FLIGHT_EVENTS = 512


def trace_sampled(seq: int, sample: int) -> bool:
    """The per-op trace decision: deterministic in the op seqno, so all
    ranks trace the SAME ops and the tracker can assemble complete
    cross-rank timelines.  ``sample`` <= 0 never samples (tracing off —
    the engines additionally keep the entire arm/emit path behind one
    attribute check)."""
    return sample > 0 and seq % sample == 0


class HopBuffer:
    """Worker-side bounded buffer of hop records awaiting the next
    streaming flush (the hop analogue of span.SpanBuffer): ``add`` from
    the collective hot path, ``drain`` from the heartbeat thread.  Full
    buffer drops (and counts) rather than blocking or growing."""

    CAPACITY = 4096

    def __init__(self, capacity: int = CAPACITY) -> None:
        self._buf: list[list] = []
        self._cap = max(int(capacity), 1)
        self._lock = threading.Lock()
        self.dropped = 0

    def add(self, seq: int, epoch: int, version: int, kind: str, hop: int,
            peer: int, phase: str, nbytes: int, t0: float, t1: float) -> None:
        rec = [seq, epoch, version, kind, hop, peer, phase, nbytes,
               round(t0, 6), round(t1, 6)]
        with self._lock:
            if len(self._buf) >= self._cap:
                self.dropped += 1
                return
            self._buf.append(rec)

    def drain(self) -> list[list]:
        with self._lock:
            out, self._buf = self._buf, []
            return out

    def __len__(self) -> int:
        return len(self._buf)


def _hop_dict(rec) -> dict | None:
    """One wire hop record (positional list) → field dict; None for
    records that don't parse (garbage tolerated like span rows)."""
    try:
        d = dict(zip(HOP_FIELDS, rec))
        return {"seq": int(d["seq"]), "epoch": int(d["epoch"]),
                "version": int(d["version"]), "kind": str(d["kind"]),
                "hop": int(d["hop"]), "peer": int(d["peer"]),
                "phase": str(d["phase"]), "nbytes": int(d["nbytes"]),
                "t0": float(d["t0"]), "t1": float(d["t1"])}
    except (TypeError, ValueError, KeyError):
        return None


class TraceAssembler:
    """Tracker-side causal timeline assembly over streamed hop records.

    Records group by the span key (epoch, version, seq, kind); because
    sampling is deterministic in the seqno every rank contributes to the
    same groups, and a group holding hops from every live rank is one
    complete cross-rank causal timeline for that collective.  A bounded
    window of assembled ops is retained for exposition (``/trace``,
    ``/status``); per-link costs fold over everything ever assembled.

    Clock-skew calibration: each streamed frame carries the sender's
    ``time.time()`` and its hb-RTT estimate; ``note_offset`` folds
    ``recv_time - frame_ts - rtt/2`` samples into a rolling median
    offset per rank, and every exposed timestamp is corrected by it —
    cross-rank orderings survive multi-second clock skew."""

    MAX_OPS = 64
    OFFSET_WINDOW = 32

    def __init__(self, max_ops: int = MAX_OPS) -> None:
        self._lock = threading.Lock()
        self._ops: collections.OrderedDict = collections.OrderedDict()
        self._offsets: dict[int, collections.deque] = {}
        self._links: dict[str, dict] = {}
        self.assembled = 0   # op groups ever finalized into the window
        self.records = 0     # hop records ever ingested
        self._max_ops = max(int(max_ops), 1)

    # -- clock calibration -------------------------------------------
    def note_offset(self, rank: int, sample: float) -> None:
        """One ``tracker_clock - rank_clock`` estimate (from a frame's
        send timestamp and half its heartbeat RTT)."""
        with self._lock:
            dq = self._offsets.get(rank)
            if dq is None:
                dq = self._offsets[rank] = collections.deque(
                    maxlen=self.OFFSET_WINDOW)
            dq.append(float(sample))

    def offset(self, rank: int) -> float:
        """Current offset estimate for ``rank`` (median of the rolling
        window; 0 with no samples — uncorrected)."""
        with self._lock:
            dq = self._offsets.get(rank)
            return statistics.median(dq) if dq else 0.0

    # -- ingest --------------------------------------------------------
    def add(self, rank: int, hops: list, world: int = 0) -> None:
        """Fold one rank's drained hop records in.  ``world`` is advisory
        (groups are exposed as soon as they exist; completeness is a
        property of sampling determinism, not a gate — a dead rank must
        not hide the timeline that explains its death)."""
        if not isinstance(hops, list):
            return
        with self._lock:
            for rec in hops:
                d = _hop_dict(rec)
                if d is None:
                    continue
                d["rank"] = int(rank)
                self.records += 1
                key = (d["epoch"], d["version"], d["seq"], d["kind"])
                grp = self._ops.get(key)
                if grp is None:
                    grp = self._ops[key] = {"records": [], "ranks": set()}
                    self.assembled += 1
                    while len(self._ops) > self._max_ops:
                        self._ops.popitem(last=False)
                grp["records"].append(d)
                grp["ranks"].add(int(rank))
                if d["phase"] == "hop" and d["peer"] >= 0:
                    link = f"{d['rank']}->{d['peer']}"
                    row = self._links.get(link)
                    if row is None:
                        row = self._links[link] = {
                            "n": 0, "sec": 0.0, "bytes": 0}
                    row["n"] += 1
                    row["sec"] += max(d["t1"] - d["t0"], 0.0)
                    row["bytes"] += d["nbytes"]

    # -- analysis ------------------------------------------------------
    def ops(self) -> list[tuple]:
        with self._lock:
            return list(self._ops.keys())

    def timeline(self, key: tuple | None = None) -> list[dict]:
        """The skew-corrected records of one op (default: the newest),
        sorted by corrected start time."""
        with self._lock:
            if not self._ops:
                return []
            if key is None:
                key = next(reversed(self._ops))
            grp = self._ops.get(tuple(key))
            if grp is None:
                return []
            out = []
            for d in grp["records"]:
                dq = self._offsets.get(d["rank"])
                off = statistics.median(dq) if dq else 0.0
                c = dict(d)
                c["t0"] = round(d["t0"] + off, 6)
                c["t1"] = round(d["t1"] + off, 6)
                out.append(c)
        out.sort(key=lambda d: (d["t0"], d["rank"], d["hop"]))
        return out

    @staticmethod
    def _binding(records: list[dict]) -> dict | None:
        """The critical-path verdict for one assembled op: the single
        longest wire hop is what the collective's completion waited on
        — it names the binding (rank, link, hop)."""
        hops = [d for d in records if d["phase"] == "hop"] or records
        if not hops:
            return None
        worst = max(hops, key=lambda d: d["t1"] - d["t0"])
        return {"rank": worst["rank"], "peer": worst["peer"],
                "hop": worst["hop"],
                "link": f"{worst['rank']}->{worst['peer']}",
                "sec": round(max(worst["t1"] - worst["t0"], 0.0), 6),
                "nbytes": worst["nbytes"], "kind": worst["kind"],
                "seq": worst["seq"], "epoch": worst["epoch"],
                "version": worst["version"]}

    def critical_path(self, key: tuple | None = None) -> dict | None:
        return self._binding(self.timeline(key))

    def link_costs(self) -> dict:
        """Per-link cost fold over every hop ever ingested: the
        evidence table the adaptive controller / TuningCache side can
        consume (``tools/trace_report.py`` renders and exports it)."""
        with self._lock:
            return {link: {"n": row["n"],
                           "mean_sec": round(row["sec"] / row["n"], 6)
                           if row["n"] else 0.0,
                           "bytes": row["bytes"]}
                    for link, row in sorted(self._links.items())}

    def bound_by(self) -> str | None:
        """Modal binding link across the retained window — the one-line
        per-job verdict ``rabit_top`` renders."""
        votes: collections.Counter = collections.Counter()
        for key in self.ops():
            b = self.critical_path(key)
            if b is not None:
                votes[b["link"]] += 1
        if not votes:
            return None
        link, n = votes.most_common(1)[0]
        return f"link {link} ({n}/{sum(votes.values())} ops)"

    # -- exposition ------------------------------------------------------
    def chrome(self, key: tuple | None = None) -> dict:
        """Perfetto-loadable Chrome-trace JSON object for one op's
        timeline (default: the newest), one pid lane per rank."""
        recs = self.timeline(key)
        events: list[dict] = []
        for r in sorted({d["rank"] for d in recs}):
            events.append({"ph": "M", "pid": r, "tid": 0,
                           "name": "process_name",
                           "args": {"name": f"rank {r}"}})
        t0 = min((d["t0"] for d in recs), default=0.0)
        for d in recs:
            name = (f"{d['kind']} hop{d['hop']}" if d["phase"] == "hop"
                    else d["phase"])
            events.append({
                "name": name, "cat": d["phase"], "ph": "X",
                "pid": d["rank"], "tid": 0,
                "ts": round((d["t0"] - t0) * 1e6, 3),
                "dur": round(max(d["t1"] - d["t0"], 0.0) * 1e6, 3),
                "args": {k: d[k] for k in ("seq", "epoch", "version",
                                           "peer", "nbytes")}})
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def report(self) -> dict:
        """Compact JSON-safe summary for the ``/status`` per-job
        ``trace`` section (and hence for shard-level folding: the whole
        section rides the job row, and jobs are disjoint across
        shards)."""
        keys = self.ops()
        last = self.timeline(keys[-1]) if keys else []
        rep = {"ops_assembled": self.assembled,
               "records": self.records,
               "ops_held": len(keys),
               "links": self.link_costs()}
        bb = self.bound_by()
        if bb is not None:
            rep["bound_by"] = bb
        if last:
            rep["last_op"] = {"key": list(keys[-1]),
                              "critical": self._binding(last),
                              "records": last[-64:]}
        return rep


# ----------------------------------------------------------------------
# flight recorder (crash forensics)
# ----------------------------------------------------------------------

class FlightRecorder:
    """Always-on bounded crash ring for one rank.

    A small :class:`EventTrace` of recent wire/engine events (op
    markers, link errors, recovery phases) plus the op currently in
    flight, persisted ATOMICALLY (tmp + rename) on every fault path —
    LinkError escalation, recovery budget exhaustion, the SIGTERM
    handler, serve drain — so a dead job leaves
    ``<trace_dir>/flight.rank<N>.json`` files that
    ``tools/postmortem.py`` can reconstruct the last seconds from.
    Recording is independent of ``rabit_obs`` (the ring is a few dict
    appends per collective); persistence needs ``rabit_trace_dir``."""

    def __init__(self, capacity: int = DEFAULT_FLIGHT_EVENTS) -> None:
        self.ring = EventTrace(capacity=max(int(capacity), 8))
        self.inflight: dict | None = None
        self.persists = 0

    def op_begin(self, kind: str, seq: int, epoch: int, version: int,
                 nbytes: int) -> None:
        """Mark one collective entering the wire (cleared by
        :meth:`op_end` ONLY on success, so a fault-path persist always
        names the op that was in flight)."""
        self.inflight = {"kind": kind, "seq": seq, "epoch": epoch,
                         "version": version, "nbytes": nbytes}
        self.ring.emit("op_begin", kind=kind, seq=seq, epoch=epoch,
                       version=version, nbytes=nbytes)

    def op_end(self) -> None:
        self.inflight = None

    def note(self, name: str, **fields) -> None:
        self.ring.emit(name, **fields)

    def persist(self, trace_dir: str, rank: int, reason: str,
                **meta) -> str | None:
        """Atomically write this rank's flight record (last writer wins
        — the record closest to death is the interesting one).  Best
        effort: a fault path must never die in its own forensics."""
        doc = {"rank": int(rank), "reason": str(reason),
               "ts": round(time.time(), 6), "pid": os.getpid(),
               "inflight": self.inflight,
               "events": self.ring.events()}
        for k, v in meta.items():
            if v is not None:
                doc[k] = v
        path = os.path.join(trace_dir, f"flight.rank{int(rank)}.json")
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            os.makedirs(trace_dir, exist_ok=True)
            with open(tmp, "w") as f:
                json.dump(doc, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return None
        self.persists += 1
        return path


def load_flight_records(trace_dir: str) -> list[dict]:
    """Read every ``flight.rank*.json`` under ``trace_dir`` (malformed
    or half-written files skipped — postmortems run over whatever the
    crash left behind)."""
    out = []
    try:
        names = sorted(os.listdir(trace_dir))
    except OSError:
        return out
    for name in names:
        if not (name.startswith("flight.rank") and name.endswith(".json")):
            continue
        try:
            with open(os.path.join(trace_dir, name)) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(doc, dict):
            out.append(doc)
    return out
