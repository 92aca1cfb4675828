"""One cell's job under ``jax.profiler``, idle time by the program's spans.

Runs a cell of BENCHMARK.json the way the benchmark does (its
configuration, traffic, data and window, through ``perfbench``'s own
pieces), traces the window, and reduces the trace with
``perfbench.trace_reduce.reduce_file(path, "rabit:")``: the program's
own spans (``rabit_tpu/obs/program.py``) instead of the benchmark's
three names around public calls.  ``reduce_file`` gives a span every
idle interval it overlaps, so a parent holds its children's too; here
each idle interval also goes to the innermost span open on the host,
read off how the annotations nest in the trace.  Beside both it prints
the program's own table of the recorded session (the ``traced/`` keys of
``program.stats()``: the spans that began and ended inside the trace):
``exposed_s`` and ``unsure_s``, inclusive as the enclosing-span column
is, and ``exposed_s`` less its children's, where the trace shows a
span's children under no other parent, as the innermost column is.  A
row's verdict holds the trace's idle seconds under the span against the
program's bounds of them: ``exposed_s`` below; above, ``exposed_s +
unsure_s`` and what no column can see, (hand-overs under the span that
found the device idle) ``x`` (the launch lag's 95th percentile) ``+``
(waits under it that came back to an idle device) ``x`` (the notice
lag's), and what the span's annotation is wider than the table's
interval.  It says by how much a row lies outside.  The launch lag is
the end of an idle interval of the device less the first hand-over made
inside it (``rabit:enqueued``, the instant ``program.enqueued`` ran: the
runtime's own ``DoEnqueueProgram`` follows on another thread, a third
of a millisecond later, and stands in only where a trace has no such
marks); the notice lag the end of a ``*.wait`` span less the end of the
device's last operation inside it.

The two clocks are aligned here (:func:`clock_shift`): a device program
and the ``DoEnqueueProgram`` that handed it over carry one run id, so
each program is held to its own hand-over, also where the trace holds
more of the one than of the other (the boosting cells, where
``perfbench.trace_reduce`` pairs by index and shifts nothing).  Every
``rabit:allreduce.dispatch`` must begin before the collective's program
it enqueues starts on the device.

One chip:    python tools/span_trace.py --workload kmeans-dense-chain8-x1
Four chips:  python -m rabit_tpu.tracker.launch_local -n 4 \\
                 python tools/span_trace.py --workload kmeans-dense-periter-x4

Each rank prints its table and writes ``<out>/<cell>/rank<r>.json``.

``--cost-every N`` measures instead what the always-on spans cost the
job, inside one process: no profiler; the spans and counters are
switched off (made no-ops) and on again every N versions, and the
seconds a version takes under each are compared segment by segment
(``cost-rank<r>.json``).  One job, one machine, one staging: what two
runs of two checkouts differ by besides the spans is not in it.

A builder's tool, not part of the benchmark: it claims no metric.
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import shutil
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

NO_SPAN = "(no span)"


def self_intervals(threads: list[list[tuple]]) -> dict[str, list]:
    """Per span name the time inside it that no span nested in it
    covers, from ``(name, start, end)`` events thread by thread: every
    instant goes to the innermost span open on its thread.  A child
    whose parent the trace cut off (the step that was open when the
    trace stopped) keeps its own time."""
    from perfbench.trace_reduce import _subtract, _union

    own: dict[str, list] = {}
    for events in threads:
        open_: list[tuple] = []             # (name, start, end, children)
        for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
            while open_ and open_[-1][2] <= a:
                open_.pop()
            if open_:
                open_[-1][3].append([a, b])
            kids: list = []
            open_.append((name, a, b, kids))
            own.setdefault(name, []).append(([a, b], kids))
    return {name: _union([piece for span, kids in spans
                          for piece in _subtract([span], _union(kids))])
            for name, spans in own.items()}


def parents_of(threads: list[list[tuple]]) -> dict[str, set]:
    """Per span name the names it was seen nested directly in, from the
    same events (None: in nothing, as the outermost spans are and the
    children of the step that was open when the trace stopped)."""
    parents: dict[str, set] = {}
    for events in threads:
        open_: list[tuple] = []             # (name, end)
        for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
            while open_ and open_[-1][1] <= a:
                open_.pop()
            parents.setdefault(name, set()).add(
                open_[-1][0] if open_ else None)
            open_.append((name, b))
    return parents


def window_table(before: dict, after: dict, parents: dict,
                 prefix: str = "") -> dict:
    """What the program's table gained between two ``program.stats()``
    (with ``prefix``, its table of the recorded session): per span
    ``n``, ``total_s``, ``self_s``, ``exposed_s``, ``unsure_s`` and,
    where every child of the span was seen under it alone,
    ``exposed_own_s``: its exposed seconds less its children's, the
    seconds exposed with the span innermost."""
    names = {k[len(prefix):-len(".exposed_s")] for k in after
             if k.startswith(prefix) and k.endswith(".exposed_s")}
    gained = {name: {col: after.get(f"{prefix}{name}.{col}", 0)
                     - before.get(f"{prefix}{name}.{col}", 0)
                     for col in ("n", "total_s", "self_s", "exposed_s",
                                 "unsure_s")}
              for name in names}
    for name, row in gained.items():
        children = [c for c, ps in parents.items() if name in ps]
        if all(parents[c] - {None} == {name} for c in children):
            row["exposed_own_s"] = row["exposed_s"] - sum(
                gained.get(c, {}).get("exposed_s", 0.0) for c in children)
    return gained


def _run_id(event):
    return dict(event.stats).get("run_id")


def handovers(profile) -> list[tuple]:
    """``(when, run id)`` of every ``DoEnqueueProgram``: the runtime
    handing a program to the device, on the host's clock."""
    from perfbench import trace_reduce as tr

    return sorted((float(e.start_ns), _run_id(e)) for plane in profile.planes
                  if not tr.DEVICE_PLANE.match(plane.name)
                  for line in plane.lines for e in line.events
                  if e.name == tr.ENQUEUE)


def programs(profile) -> list[tuple]:
    """``(start, end, run id)`` of every program the device ran (one
    device plane a process), on the device's clock."""
    from perfbench import trace_reduce as tr

    return sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                   _run_id(e))
                  for plane in profile.planes
                  if tr.DEVICE_PLANE.match(plane.name)
                  for line in plane.lines if line.name == tr.MODULES_LINE
                  for e in line.events)


def clock_shift(enqueues: list, ran: list) -> tuple[float, int]:
    """Nanoseconds to add to the device's times so that no program
    starts before the runtime handed it over, and the number of pairs
    the figure rests on.  A program and its hand-over carry one run id;
    the programs handed over before the trace began and the hand-overs
    whose programs had not started when it stopped pair with nothing.
    Where the events carry no id they are matched in order, if they are
    as many (``perfbench.trace_reduce.clock_shift``'s rule)."""
    handed = {run: h for h, run in enqueues if run is not None}
    pairs = [handed[run] - a for a, _b, run in ran if run in handed]
    if not handed and len(enqueues) == len(ran):
        pairs = [h - a for (h, _), (a, _b, _run) in zip(enqueues, ran)]
    return (max(0.0, max(pairs)) if pairs else 0.0), len(pairs)


def _inside(intervals: list, t: float) -> bool:
    """Is ``t`` inside one of the disjoint, sorted ``intervals``?  Not
    at its start: the program the clocks were aligned by starts at its
    hand-over."""
    i = bisect.bisect_left(intervals, [t, t]) - 1
    return i >= 0 and intervals[i][0] < t < intervals[i][1]


def launch_lags(stamps: list, idle: list) -> list[tuple]:
    """``(hand-over, lag)`` of every hand-over that found the device
    idle: of each idle interval the first hand-over made inside it (the
    program that ends the interval is its own; those after it queue
    behind), and the interval's end less it.  ``stamps`` sorted, both
    on the host's clock."""
    out = []
    for a, b in idle:
        i = bisect.bisect_right(stamps, a)
        if i < len(stamps) and stamps[i] < b:
            out.append((stamps[i], b - stamps[i]))
    return out


def notice_lags(waits: list, busy: list) -> list[tuple]:
    """``(middle of the wait, lag)`` of every wait (``[start, end]`` of
    a ``*.wait`` span) inside which the device went idle and stayed so:
    the wait's end less the end of the device's last operation."""
    out = []
    for a, b in waits:
        i = bisect.bisect_left(busy, [b, b]) - 1
        if i >= 0 and a < busy[i][1] <= b:
            out.append(((a + b) / 2, b - busy[i][1]))
    return out


def _percentiles(lags: list, unit: str) -> dict:
    lag = sorted(x for _t, x in lags)
    if not lag:
        return {}
    return {unit + "_us_median": lag[len(lag) // 2] * 1e-3,
            unit + "_us_p95": lag[min(len(lag) - 1,
                                      int(0.95 * len(lag)))] * 1e-3,
            unit + "_us_max": lag[-1] * 1e-3}


def device_intervals(profile, enqueues: list, ran: list) -> tuple:
    """The intervals in which something ran on the device (one device
    plane a process) and those in which nothing did, on the host's
    clock; the shift applied and the pairs it rests on."""
    from perfbench import trace_reduce as tr

    (ops, _starts), = tr.device_ops(profile).values()
    shift, pairs = clock_shift(enqueues, ran)
    busy = tr._union([[a + shift, b + shift] for _n, a, b in ops])
    return (busy, tr._subtract([[busy[0][0], busy[-1][1]]], busy), shift,
            pairs)


def host_events(profile, prefix: str, mark: str = "") -> list[list[tuple]]:
    """The spans of the program thread by thread, without its marks of
    hand-overs; with ``mark``, those alone."""
    from perfbench.trace_reduce import DEVICE_PLANE
    from rabit_tpu.obs.program import MARK

    return [[(e.name[len(prefix):], float(e.start_ns),
              float(e.start_ns + e.duration_ns))
             for e in line.events if e.name.startswith(prefix)
             and (e.name[len(prefix):] == MARK) == bool(mark)]
            for plane in profile.planes if not DEVICE_PLANE.match(plane.name)
            for line in plane.lines]


def dispatch_leads(profile, prefix: str, shift: float) -> dict:
    """Does every ``allreduce.dispatch`` span begin before the program
    it enqueues starts?  Spans and executions are matched in order."""
    from perfbench import trace_reduce as tr

    spans = sorted(a for events in host_events(profile, prefix)
                   for name, a, _b in events if name == "allreduce.dispatch")
    starts = sorted(float(e.start_ns) for plane in profile.planes
                    if tr.DEVICE_PLANE.match(plane.name)
                    for line in plane.lines if line.name == tr.MODULES_LINE
                    for e in line.events if "engine_allreduce" in e.name)
    out = {"dispatch_spans": len(spans), "programs": len(starts),
           "clock_shift_us": shift * 1e-3}
    if spans and len(spans) == len(starts):
        lead = sorted(d + shift - h for h, d in zip(spans, starts))
        out.update(late=sum(1 for x in lead if x < 0),
                   lead_us_min=lead[0] * 1e-3,
                   lead_us_median=lead[len(lead) // 2] * 1e-3)
    return out


def reduce_trace(path: str, prefix: str) -> dict:
    import jax

    from perfbench import trace_reduce as tr
    from rabit_tpu.obs.program import MARK

    reduced = tr.reduce_file(path, prefix)
    profile = jax.profiler.ProfileData.from_file(path)
    handed, ran = handovers(profile), programs(profile)
    busy, idle, shift, pairs = device_intervals(profile, handed, ran)
    threads = host_events(profile, prefix)
    inner = {name: tr._overlap(idle, cover) * 1e-9
             for name, cover in self_intervals(threads).items()}
    idle_s = reduced["window_s"] - reduced["busy_s"]
    inner[NO_SPAN] = idle_s - sum(inner.values())
    covers = {name: tr._union([[a, b] for events in threads
                               for n, a, b in events if n == name])
              for name in {n for events in threads for n, _a, _b in events}}
    enqueues = [h for h, _run in handed]
    marks = sorted(a for events in host_events(profile, prefix, MARK)
                   for _n, a, _b in events)
    launches = launch_lags(marks or enqueues, idle)
    notices = notice_lags(sorted(
        [a, b] for events in threads for name, a, b in events
        if name.endswith(".wait")), busy)
    lag = {**_percentiles(launches, "launch"),
           **_percentiles(notices, "notice")}
    # a launch is idle time under whichever spans are open while it
    # lasts: the hand-over's own and those the host goes on to
    launching = tr._union([[m, m + lag["launch_us_p95"] * 1e3]
                           for m, _x in launches])

    def under(stamps):
        return {name: sum(1 for t, _x in stamps if _inside(cover, t))
                for name, cover in covers.items()}

    return {"window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
            "idle_s": idle_s,
            "idle_by_innermost_span": dict(sorted(
                inner.items(), key=lambda kv: -kv[1])),
            "no_span_share_of_idle":
                inner[NO_SPAN] / idle_s if idle_s > 0 else 0.0,
            # with this tool's shift, which `reduced["gaps"]` lacks
            # where hand-overs and programs are not as many
            "idle_by_enclosing_span": {
                name: tr._overlap(idle, cover) * 1e-9
                for name, cover in covers.items()},
            "seconds_by_span": {name: tr._length(cover) * 1e-9
                                for name, cover in covers.items()},
            "handovers_idle_by_enclosing_span": under(launches),
            "launch_s_by_enclosing_span": {
                name: tr._overlap(launching, cover) * 1e-9
                for name, cover in covers.items()},
            "waits_idle_by_enclosing_span": under(notices),
            "lag": {"handovers": len(enqueues), "marks": len(marks),
                    "programs": len(ran),
                    "handovers_idle": len(launches),
                    "waits_idle": len(notices),
                    "clock_shift_us": shift * 1e-3, "shift_pairs": pairs,
                    **lag},
            "parents": {name: sorted(ps, key=str)
                        for name, ps in parents_of(threads).items()},
            "dispatch": dispatch_leads(profile, prefix, shift),
            "device_ops": sorted(
                ([k, v[0]] for k, v in reduced["ops"].items()),
                key=lambda kv: -kv[1])[:8]}


def verdicts(result: dict) -> dict:
    """Per span of the session's table: the trace's idle seconds under
    it, the program's bounds of them, and how far outside they lie
    (0.0: inside; negative: the trace reads under ``exposed_s``;
    positive: over the upper bound).  The upper bound holds what no
    column sees (the launch after a hand-over to an idle device, as
    far as the span was open while it lasted, and the notice after a
    wait, each at its 95th percentile) and what the span's annotations
    are wider than the table's intervals."""
    notice_s = result["lag"].get("notice_us_p95", 0.0) * 1e-6
    out = {}
    for name, row in result["program_window"].items():
        seen = result["idle_by_enclosing_span"].get(name, 0.0)
        handed = result["handovers_idle_by_enclosing_span"].get(name, 0)
        waited = result["waits_idle_by_enclosing_span"].get(name, 0)
        wider = max(0.0, result["seconds_by_span"].get(name, 0.0)
                    - row["total_s"])
        upper = (row["exposed_s"] + row["unsure_s"]
                 + result["launch_s_by_enclosing_span"].get(name, 0.0)
                 + waited * notice_s + wider)
        out[name] = {"idle_s": seen, "lower_s": row["exposed_s"],
                     "upper_s": upper, "handovers_idle": handed,
                     "waits_idle": waited,
                     "outside_s": min(0.0, seen - row["exposed_s"])
                     + max(0.0, seen - upper)}
    return out


class NoSpan:
    """A span while ``--cost-every`` has the spans switched off."""
    seconds = 0.0

    def __init__(self, name, **fields):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def alternating(clock, every: int, program, on: tuple):
    """The commit wrapper of ``--cost-every``: after the n-th commit the
    spans, counters, ``enqueued`` and ``waited`` are ``on`` if
    ``n // every`` is even and no-ops if odd, on every rank alike."""
    off = (NoSpan, lambda name, k=1: None, lambda result: None, lambda: None)

    def commit(*args, **kwargs):
        clock(*args, **kwargs)
        (program.span, program.count, program.enqueued,
         program.waited) = off if (len(clock.stamps) // every) % 2 else on
    return commit


def span_cost(stamps: list[float], first: int, every: int) -> dict:
    """Seconds a version takes with the spans on less with them off.
    ``first + j`` commits came before the one ``stamps[j]`` closes, so
    the version it ends ran in segment ``(first + j) // every``, on if
    that is even.  A segment's first version (the switch) is left out,
    and every whole segment is compared with the mean of its two
    neighbours, which are of the other kind: a drift of the machine
    cancels."""
    segments: dict[int, list] = {}
    for j in range(1, len(stamps)):
        i = first + j
        if i % every:
            segments.setdefault(i // every, []).append(
                stamps[j] - stamps[j - 1])
    means = {s: statistics.fmean(g) for s, g in segments.items()
             if len(g) == every - 1}
    diffs = sorted((1 if s % 2 == 0 else -1)
                   * (means[s] - (means[s - 1] + means[s + 1]) / 2)
                   for s in means if s - 1 in means and s + 1 in means)
    out = {"every": every, "segments_compared": len(diffs)}
    for kind, parity in (("on", 0), ("off", 1)):
        kept = [m for s, m in means.items() if s % 2 == parity]
        if kept:
            out[f"version_s_{kind}"] = statistics.fmean(kept)
    if len(diffs) >= 2:
        q1, q2, q3 = statistics.quantiles(diffs, n=4)
        out.update(on_minus_off_s=q2, on_minus_off_q1_s=q1,
                   on_minus_off_q3_s=q3,
                   on_minus_off_mean_s=statistics.fmean(diffs))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "span_trace"))
    ap.add_argument("--cost-every", type=int, default=0, metavar="N",
                    help="no trace: switch the spans off and on every N "
                         "versions and compare the version times")
    ap.add_argument("--rows", type=int, default=None,
                    help="rows per chip, for a rehearsal off the chip")
    args = ap.parse_args(argv)

    import jax

    import rabit_tpu
    from perfbench import harness, trace_reduce
    from perfbench.window import StopWord, VersionClock, WindowClosed
    from rabit_tpu import engine as engine_mod
    from rabit_tpu.obs import program

    loaded = harness.load_cell(args.workload)
    cfg, traffic = loaded["cfg"], loaded["traffic"]
    world = int(traffic["world"])
    out_dir = os.path.join(args.out, args.workload)
    os.makedirs(out_dir, exist_ok=True)
    learner = harness.load_learner(loaded)
    harness.enable_compile_cache()
    rabit_tpu.init(list(traffic.get("engine_args", [])),
                   rabit_engine=traffic["engine"])
    rank = rabit_tpu.get_rank()
    harness.require_chip(jax.devices(), world)
    data = learner.make_data(
        cfg, args.seed, rank, world,
        max(1, min(8, (os.cpu_count() or 1) // world)), args.rows, None)
    # the adapter's eyes on its learner, opened as the harness opens
    # them, after it has described the job: the boosting adapters' and
    # the streamed cell's jobs count on both
    learner.describe(cfg, traffic, data)
    undo = list(learner.watch(data, harness.Spans(annotate=False), False))
    trace_dir = os.path.join(out_dir, f"trace-{rank}")
    # one word all ranks of this launch map, and no other launch
    stop_path = os.path.join(out_dir, "stop-" + os.environ.get(
        "RABIT_TRACKER_PORT", "0"))

    def start_trace():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    table = {}                          # the program's, at both ends

    def open_window():
        table["before"] = program.stats()
        start_trace()

    def close_window():
        jax.profiler.stop_trace()
        table["after"] = program.stats()

    commit = rabit_tpu.checkpoint
    warmup = int(traffic.get("warmup_versions", 2))
    traced = not args.cost_every
    clock = VersionClock(
        commit, rabit_tpu.version_number, args.seconds, warmup, rank == 0,
        StopWord(stop_path) if world > 1 else None,
        on_open=open_window if traced else None,
        on_close=close_window if traced else None)
    spans = (program.span, program.count, program.enqueued, program.waited)
    rabit_tpu.checkpoint = clock if traced else alternating(
        clock, args.cost_every, program, spans)
    try:
        learner.run_job(cfg, traffic, data)
        raise RuntimeError("the learner returned before the window closed")
    except WindowClosed:
        pass
    finally:
        rabit_tpu.checkpoint = commit
        (program.span, program.count, program.enqueued,
         program.waited) = spans
        for owner, name, fn in reversed(undo):
            setattr(owner, name, fn)
    if traced:
        result = reduce_trace(trace_reduce.find_xplane(trace_dir),
                              program.PREFIX)
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["program_window"] = window_table(
            table["before"], table["after"],
            {name: set(ps) for name, ps in result["parents"].items()},
            program.TRACED)
        result["verdicts"] = verdicts(result)
        result["program_handovers"] = {
            name: table["after"].get(program.TRACED + key, 0)
            - table["before"].get(program.TRACED + key, 0)
            for name, key in (
                ("handovers", program.HANDOVERS),
                ("handovers_idle", program.HANDOVERS + program.IDLE),
                ("waits", program.WAITS),
                ("waits_idle", program.WAITS + program.IDLE))}
    else:
        result = span_cost(clock.counted(), warmup - 1, args.cost_every)
    result.update(workload=args.workload, rank=rank, seed=args.seed,
                  device=jax.devices()[0].device_kind,
                  path_stats=engine_mod.get_engine().path_stats)
    rabit_tpu.finalize()
    name = f"rank{rank}.json" if traced else f"cost-rank{rank}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(result, f, indent=1)
    if world > 1 and rank == 0:
        os.remove(stop_path)
    if not traced:
        print(f"span_trace {args.workload} rank {rank}: " + json.dumps(
            {k: v for k, v in result.items() if k != "path_stats"}))
        return 0
    print(f"span_trace {args.workload} rank {rank}: idle "
          f"{result['idle_s']:.3f} s of {result['window_s']:.3f} s; "
          f"no span: {100 * result['no_span_share_of_idle']:.1f}% of idle; "
          f"lag {json.dumps(result['lag'])}; "
          f"dispatch {json.dumps(result['dispatch'])}; the program "
          f"counted {json.dumps(result['program_handovers'])}")
    program_s, verdict = result["program_window"], result["verdicts"]
    print(f"  {'span':24s} {'idle, innermost':>16s} {'exposed, own':>14s}"
          f" {'idle, enclosing':>16s} {'exposed_s':>11s} {'unsure_s':>11s}"
          f" {'idle h/o':>8s} {'idle w':>6s} {'upper':>11s}  verdict")
    for name, seconds in result["idle_by_innermost_span"].items():
        row, v = program_s.get(name, {}), verdict.get(name)
        own = row.get("exposed_own_s")
        print(f"  {name:24s} {seconds:16.4f} "
              + (f"{own:14.4f}" if own is not None else f"{'-':>14s}")
              + f" {result['idle_by_enclosing_span'].get(name, 0.0):16.4f}"
              f" {row.get('exposed_s', 0.0):11.4f}"
              f" {row.get('unsure_s', 0.0):11.4f}"
              + (f" {'-':>8s} {'-':>6s} {'-':>11s}  -" if v is None else
                 f" {v['handovers_idle']:8d} {v['waits_idle']:6d}"
                 f" {v['upper_s']:11.4f}  "
                 + ("inside" if not v["outside_s"] else
                    f"{v['outside_s']:+.4f} s outside")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
