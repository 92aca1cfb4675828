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
what the program's own table gained over the same window
(``exposed_s``, inclusive as the enclosing-span column is; and less its
children's, where the trace shows a span's children under no other
parent, as the innermost column is): the program's lower bound of the
idle time against the trace's measurement of it.  Also checks the two
clocks against each other: every ``rabit:allreduce.dispatch`` begins
before the collective's program it enqueues starts on the device (after
``clock_shift``).

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


def window_table(before: dict, after: dict, parents: dict) -> dict:
    """What the program's table gained between two ``program.stats()``:
    per span ``n``, ``total_s``, ``self_s``, ``exposed_s`` and, where
    every child of the span was seen under it alone, ``exposed_own_s``:
    its exposed seconds less its children's, the seconds exposed with
    the span innermost."""
    names = {k[:-len(".exposed_s")] for k in after
             if k.endswith(".exposed_s")}
    gained = {name: {col: after.get(f"{name}.{col}", 0)
                     - before.get(f"{name}.{col}", 0)
                     for col in ("n", "total_s", "self_s", "exposed_s")}
              for name in names}
    for name, row in gained.items():
        children = [c for c, ps in parents.items() if name in ps]
        if all(parents[c] - {None} == {name} for c in children):
            row["exposed_own_s"] = row["exposed_s"] - sum(
                gained.get(c, {}).get("exposed_s", 0.0) for c in children)
    return gained


def idle_intervals(profile) -> tuple[list, float]:
    """The intervals in which nothing ran on the device (one device
    plane a process), on the host's clock, and the shift applied."""
    from perfbench import trace_reduce as tr

    (ops, module_starts), = tr.device_ops(profile).values()
    shift = tr.clock_shift(profile, module_starts)
    busy = tr._union([[a + shift, b + shift] for _n, a, b in ops])
    return tr._subtract([[busy[0][0], busy[-1][1]]], busy), shift


def host_events(profile, prefix: str) -> list[list[tuple]]:
    from perfbench.trace_reduce import DEVICE_PLANE

    return [[(e.name[len(prefix):], float(e.start_ns),
              float(e.start_ns + e.duration_ns))
             for e in line.events if e.name.startswith(prefix)]
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

    reduced = tr.reduce_file(path, prefix)
    profile = jax.profiler.ProfileData.from_file(path)
    idle, shift = idle_intervals(profile)
    threads = host_events(profile, prefix)
    inner = {name: tr._overlap(idle, cover) * 1e-9
             for name, cover in self_intervals(threads).items()}
    idle_s = reduced["window_s"] - reduced["busy_s"]
    inner[NO_SPAN] = idle_s - sum(inner.values())
    return {"window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
            "idle_s": idle_s,
            "idle_by_innermost_span": dict(sorted(
                inner.items(), key=lambda kv: -kv[1])),
            "no_span_share_of_idle":
                inner[NO_SPAN] / idle_s if idle_s > 0 else 0.0,
            "idle_by_enclosing_span": reduced["gaps"],
            "parents": {name: sorted(ps, key=str)
                        for name, ps in parents_of(threads).items()},
            "dispatch": dispatch_leads(profile, prefix, shift),
            "device_ops": sorted(
                ([k, v[0]] for k, v in reduced["ops"].items()),
                key=lambda kv: -kv[1])[:8]}


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
    spans, counters and ``enqueued`` are ``on`` if ``n // every`` is even
    and no-ops if odd, on every rank alike."""
    off = (NoSpan, lambda name, k=1: None, lambda result: None)

    def commit(*args, **kwargs):
        clock(*args, **kwargs)
        program.span, program.count, program.enqueued = (
            off if (len(clock.stamps) // every) % 2 else on)
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
    # them: the boosting adapters' job counts on them
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
    spans = (program.span, program.count, program.enqueued)
    rabit_tpu.checkpoint = clock if traced else alternating(
        clock, args.cost_every, program, spans)
    try:
        learner.run_job(cfg, traffic, data)
        raise RuntimeError("the learner returned before the window closed")
    except WindowClosed:
        pass
    finally:
        rabit_tpu.checkpoint = commit
        program.span, program.count, program.enqueued = spans
        for owner, name, fn in reversed(undo):
            setattr(owner, name, fn)
    if traced:
        result = reduce_trace(trace_reduce.find_xplane(trace_dir),
                              program.PREFIX)
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["program_window"] = window_table(
            table["before"], table["after"],
            {name: set(ps) for name, ps in result["parents"].items()})
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
          f"dispatch {json.dumps(result['dispatch'])}")
    program_s = result["program_window"]
    print(f"  {'span':24s} {'idle, innermost':>16s} {'exposed, own':>14s}"
          f" {'idle, enclosing':>16s} {'exposed_s':>11s}")
    for name, seconds in result["idle_by_innermost_span"].items():
        row = program_s.get(name, {})
        own = row.get("exposed_own_s")
        print(f"  {name:24s} {seconds:16.4f} "
              + (f"{own:14.4f}" if own is not None else f"{'-':>14s}")
              + f" {result['idle_by_enclosing_span'].get(name, 0.0):16.4f}"
              f" {row.get('exposed_s', 0.0):11.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
