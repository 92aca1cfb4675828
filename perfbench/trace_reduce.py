"""From the profiler's ``.xplane.pb`` to numbers: device busy time, time
per device operation, collective time not hidden behind compute, and
idle gaps by the span the host was in.

Read with ``jax.profiler.ProfileData`` and nothing else.  The same code
reduces every PR's trace; it is checked on the small recorded trace in
``recorded/`` (tests/perfbench).

What a TPU trace looks like (seen by hand on a v5e, PR 23): one plane
``/device:TPU:<n>`` per chip whose line ``XLA Ops`` holds one event per
executed HLO operation (its name is the instruction's whole text; the
enclosing event of line ``XLA Modules`` is the jitted program), and host
planes whose lines are threads; a ``jax.profiler.TraceAnnotation`` is
an event of its name on the thread that made it.  All on one clock.

An operation is named ``<program>/<op>:<opcode>`` with ``jit_`` and
trailing ``.<n>`` counters taken off, so a name survives a recompile
(not a refactor that renames the function: ``jax.named_scope`` in the
program is a later PR's).
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
CONTAINER = ("while", "conditional", "call")
ENQUEUE = "DoEnqueueProgram"       # the host's hand-over of a program
HOST_CODE = "learner_host_code"


def _clean(name: str) -> str:
    name = re.sub(r"\(\d+\)$", "", name)          # "jit_f(123)" program ids
    name = re.sub(r"^jit_", "", name)
    return re.sub(r"[.\d]+$", "", name) or name


def op_name(text: str) -> tuple[str, str]:
    """(name, opcode) of an ``XLA Ops`` event, whose name on a TPU is
    the HLO instruction's text: ``%name.3 = <shape> opcode(operands)``.
    A plain name (the CPU backend's) is its own opcode."""
    m = re.match(r"%(\S+) = ", text)
    if not m:
        return _clean(text), _clean(text)
    rest = text[m.end():]
    if rest.startswith("("):                      # a tuple shape
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    code = re.match(r"\s*([\w-]+)", rest)
    return _clean(m.group(1)), code.group(1) if code else ""


def _union(intervals):
    """Merged, sorted, disjoint intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _length(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def _subtract(intervals, cover):
    """The parts of ``intervals`` (disjoint, sorted) outside ``cover``
    (disjoint, sorted)."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k, at = j, a
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > at:
                out.append([at, cover[k][0]])
            at = max(at, cover[k][1])
            k += 1
        if at < b:
            out.append([at, b])
    return out


def _overlap(intervals, cover) -> float:
    return _length(intervals) - _length(_subtract(intervals, cover))


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def device_ops(profile):
    """Per device plane, the executed operations as
    ``(name, start_ns, end_ns)``; containers of other operations
    (``while``, ``conditional``, ``call``) are left out, their bodies
    are there themselves."""
    planes = {}
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        modules, ops = [], []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                modules = sorted(_events(line), key=lambda e: e[1])
            elif line.name == OPS_LINE:
                ops = _events(line)
        named, at = [], 0
        for text, a, b in sorted(ops, key=lambda e: e[1]):
            name, code = op_name(text)
            if code in CONTAINER:
                continue
            while at < len(modules) - 1 and modules[at][2] < a:
                at += 1
            module = modules[at][0] if modules else ""
            named.append((f"{_clean(module)}/{name}:{code}", a, b))
        planes[plane.name] = (named, [a for _, a, _ in modules])
    if not planes:
        raise ValueError(
            "no /device:TPU:<n> plane in the trace (planes: "
            f"{[p.name for p in profile.planes]}): nothing of a chip to "
            "reduce, and host time is never counted as the device's")
    return planes


def clock_shift(profile, module_starts) -> float:
    """Nanoseconds to add to a device plane's times so that no program
    starts before the host handed it over.  The profiler puts host and
    device on one clock to within a millisecond or two (seen on a v5e:
    programs 'starting' 1.2 ms before their enqueue), which is the size
    of the gaps to be attributed.  Programs and hand-overs are matched
    in order; where their counts differ nothing is shifted."""
    enqueues = sorted(float(e.start_ns) for plane in profile.planes
                      if not DEVICE_PLANE.match(plane.name)
                      for line in plane.lines for e in line.events
                      if e.name == ENQUEUE)
    if not enqueues or len(enqueues) != len(module_starts):
        return 0.0
    return max(0.0, max(h - d for h, d in zip(enqueues, module_starts)))


def host_spans(profile, prefix: str):
    """Intervals of the benchmark's annotations by name."""
    spans: dict[str, list] = {}
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    spans.setdefault(e.name[len(prefix):], []).append(
                        [float(e.start_ns), float(e.start_ns + e.duration_ns)])
    return {k: _union(v) for k, v in spans.items()}


def reduce_profile(profile, prefix: str) -> dict:
    """Averages over the devices in the trace; seconds."""
    spans = host_spans(profile, prefix)
    per_device = []
    planes = device_ops(profile)
    for ops, module_starts in planes.values():
        if not ops:
            continue
        shift = clock_shift(profile, module_starts) if len(planes) == 1 else 0
        ops = [(n, a + shift, b + shift) for n, a, b in ops]
        start = min(a for _, a, _ in ops)
        end = max(b for _, _, b in ops)
        busy = _union([[a, b] for _, a, b in ops])
        compute = _union([[a, b] for n, a, b in ops
                          if not COLLECTIVE.search(n)])
        collective = _union([[a, b] for n, a, b in ops
                             if COLLECTIVE.search(n)])
        idle = _subtract([[start, end]], busy)
        gaps, rest = {}, idle
        for name, cover in spans.items():
            gaps[name] = _overlap(idle, cover) * 1e-9
            rest = _subtract(rest, cover)
        gaps[HOST_CODE] = _length(rest) * 1e-9
        by_op: dict[str, list] = {}
        for name, a, b in ops:
            slot = by_op.setdefault(name, [0.0, 0])
            slot[0] += (b - a) * 1e-9
            slot[1] += 1
        per_device.append({
            "window_s": (end - start) * 1e-9,
            "busy_s": _length(busy) * 1e-9,
            "collective_s": _length(collective) * 1e-9,
            "collective_exposed_s":
                _length(_subtract(collective, compute)) * 1e-9,
            "ops": by_op, "gaps": gaps})
    if not per_device:
        return {"window_s": 0.0, "busy_s": 0.0, "collective_s": 0.0,
                "collective_exposed_s": 0.0, "ops": {}, "gaps": {},
                "devices": 0}
    n = len(per_device)
    out = {key: sum(d[key] for d in per_device) / n
           for key in ("window_s", "busy_s", "collective_s",
                       "collective_exposed_s")}
    ops, gaps = {}, {}
    for d in per_device:
        for name, (s, c) in d["ops"].items():
            slot = ops.setdefault(name, [0.0, 0.0])
            slot[0] += s / n
            slot[1] += c / n
        for name, s in d["gaps"].items():
            gaps[name] = gaps.get(name, 0.0) + s / n
    out.update(ops=ops, gaps=gaps, devices=n)
    return out


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_file(path: str, prefix: str) -> dict:
    import jax

    return reduce_profile(jax.profiler.ProfileData.from_file(path), prefix)


def reduce_dir(trace_dir: str, prefix: str) -> dict:
    return reduce_file(find_xplane(trace_dir), prefix)
