"""Readers: from what the ranks observed to one metric each.

A metric of ``BENCHMARK.json`` names its reader by its own name: the
file ``end_to_end/<name>.json`` or ``layers/<name>.json`` holds a
``kind`` from the table below and that kind's parameters, and
``layers/<name>.py`` may instead hold a function ``read(observed)``
where a reader is code.  A reader that finds nothing to read returns
None and the metric is left out of the line.

Across ranks a reader takes rank 0 (``"over": "rank0"``, the job's
clock), the mean, or the worst (``"max"``).
"""
from __future__ import annotations

import os
import re

from perfbench.harness import (load_module, median, percentile, read_json)

REDUCE = {"median": median, "p95": lambda v: percentile(v, 95), "max": max,
          "first": lambda v: v[0], "mean": lambda v: sum(v) / len(v)}


def across(values, over: str):
    values = [v for v in values if v is not None and v == v]
    if not values:
        return None
    if over == "rank0":
        return values[0]
    return max(values) if over == "max" else sum(values) / len(values)


class Observed:
    def __init__(self, loaded: dict, ranks: list[dict]):
        self.loaded, self.ranks = loaded, ranks
        self.bench_dir = loaded["bench_dir"]
        self._peaks = None

    # ---- finding a reader --------------------------------------------
    def read(self, metric: dict, group: str):
        sub = "layers" if group == "per_layer" else "end_to_end"
        base = os.path.join(self.bench_dir, sub, metric["name"])
        if os.path.exists(base + ".py"):
            return load_module(base + ".py").read(self)
        spec = read_json(base + ".json")
        return KINDS[spec["kind"]](self, spec)

    def peaks(self) -> dict:
        """This device's published peaks; a device that is not in the
        table is an error, not a default."""
        if self._peaks is None:
            table = read_json(os.path.join(self.bench_dir, "peaks.json"))
            kind = self.ranks[0]["device"]["kind"]
            if kind not in table:
                raise KeyError(f"no peaks for device kind {kind!r} in "
                               "peaks.json")
            self._peaks = table[kind]
        return self._peaks

    # ---- pieces readers share ----------------------------------------
    def pattern(self, value: str):
        """A pattern of device-operation names: itself, or, written
        ``config:<key>``, the one the cell's configuration gives under
        that key (``step_op``: the kernel that runs once a step), so
        that one reader serves every learner's cells.  None where the
        configuration names none: nothing to read."""
        if value.startswith("config:"):
            return self.loaded["cfg"].get(value[len("config:"):])
        return value

    def trace_ops(self, rank: dict, pattern: str):
        """(seconds, count) of the device operations whose name matches."""
        trace, pattern = rank.get("trace"), self.pattern(pattern)
        if not trace or not pattern:
            return None
        rx = re.compile(pattern)
        hits = [v for name, v in trace["ops"].items() if rx.search(name)]
        if not hits:
            return None
        return sum(h[0] for h in hits), sum(h[1] for h in hits)

    def steps(self, rank: dict, spec: dict):
        """Step programs in the traced window: the executions of the
        cell's step kernel."""
        hit = self.trace_ops(rank, spec["steps_pattern"])
        return hit[1] if hit and hit[1] else None


# ----------------------------------------------------------------------
def rate(obs: Observed, spec: dict):
    """Work of the whole job (all ranks) over the window of rank 0."""
    r = obs.ranks[0]
    if r["versions"] < 1 or r["span_s"] <= 0:
        return None
    return r["work_per_version"] * r["versions"] / r["span_s"]


def version_gap(obs: Observed, spec: dict):
    gaps = obs.ranks[0]["version_gaps"]
    if len(gaps) < int(spec.get("min_samples", 1)):
        return None
    return REDUCE[spec["reduce"]](gaps)


def field(obs: Observed, spec: dict):
    return across([r.get(spec["field"]) for r in obs.ranks],
                  spec.get("over", "rank0"))


def span(obs: Observed, spec: dict):
    values = []
    for r in obs.ranks:
        if spec["span"] == "commit_window":
            seconds = r["commit_s"]
        else:
            seconds = r["spans"].get(spec["span"])
        values.append(REDUCE[spec["reduce"]](seconds) if seconds else None)
    return across(values, spec.get("over", "mean"))


def counter_share(obs: Observed, spec: dict):
    values = []
    for r in obs.ranks:
        stats = r.get("path_stats") or {}
        part = stats.get(spec["part"], 0)
        whole = sum(stats.get(k, 0) for k in spec["whole"])
        values.append(100.0 * part / whole if whole else None)
    return across(values, spec.get("over", "max"))


def memory(obs: Observed, spec: dict):
    value = across([r["memory"].get(spec["key"]) for r in obs.ranks], "max")
    return None if value is None else value * float(spec.get("scale", 1.0))


def trace_ops_per_step(obs: Observed, spec: dict):
    values = []
    for r in obs.ranks:
        hit, steps = obs.trace_ops(r, spec["pattern"]), None
        if hit:
            steps = obs.steps(r, spec)
        values.append(hit[0] / steps if hit and steps else None)
    return across(values, spec.get("over", "mean"))


def trace_field_per_step(obs: Observed, spec: dict):
    values = []
    for r in obs.ranks:
        trace = r.get("trace")
        steps = obs.steps(r, spec) if trace else None
        if not steps:
            values.append(None)
            continue
        if spec["field"] == "idle_s":
            value = trace["window_s"] - trace["busy_s"]
        else:
            value = trace[spec["field"]]
        values.append(value / steps)
    return across(values, spec.get("over", "mean"))


def trace_idle_pct(obs: Observed, spec: dict):
    values = [100.0 * (1.0 - r["trace"]["busy_s"] / r["trace"]["window_s"])
              if r.get("trace") and r["trace"]["window_s"] > 0 else None
              for r in obs.ranks]
    return across(values, spec.get("over", "mean"))


def roofline(obs: Observed, spec: dict):
    """The least time the chip could take for the kernel's calls (the
    larger of operations over peak FLOP/s and bytes over peak bytes/s,
    from ``kernels/<kernel>.py`` at the cell's shapes) over the time the
    trace gives them.  Says which bound in ``bound_of``."""
    cost_fn = load_module(os.path.join(
        obs.bench_dir, "kernels", spec["kernel"] + ".py")).cost
    peaks = obs.peaks()
    values = []
    for r in obs.ranks:
        hit = obs.trace_ops(r, spec["pattern"])
        if not hit or not r.get("kernel_shape"):
            values.append(None)
            continue
        seconds, calls = hit
        floor = max(floors(cost_fn(r["kernel_shape"]), peaks).values())
        values.append(100.0 * floor * calls / seconds)
    return across(values, spec.get("over", "mean"))


def floors(cost: dict, peaks: dict) -> dict:
    """The least seconds one call could take, by each bound."""
    return {"compute": cost["ops"] / peaks["flops_per_s"][cost["ops_dtype"]],
            "hbm": cost["bytes"] / peaks["hbm_bytes_per_s"]}


def bound_of(bench_dir: str, kernel: str, shape: dict, peaks: dict) -> str:
    """Which bound a kernel's roofline share is held to."""
    by = floors(load_module(os.path.join(
        bench_dir, "kernels", kernel + ".py")).cost(shape), peaks)
    return max(by, key=by.get)


KINDS = {"rate": rate, "version_gap": version_gap, "field": field,
         "span": span, "counter_share": counter_share,
         "memory": memory, "trace_ops_per_step": trace_ops_per_step,
         "trace_field_per_step": trace_field_per_step,
         "trace_idle_pct": trace_idle_pct, "roofline": roofline}


def breakdown(traces: list[dict]) -> dict:
    """The device operations that took most time and the idle time by
    what the host was doing, each averaged over the chips, ten at
    most."""
    ops, gaps = {}, {}
    for t in traces:
        for name, (s, _c) in t["ops"].items():
            ops[name] = ops.get(name, 0.0) + s / len(traces)
        for name, s in t["gaps"].items():
            gaps[name] = gaps.get(name, 0.0) + s / len(traces)

    def top(d):
        return [[k, v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:10] if v > 0]

    return {"device_ops": top(ops), "idle_gaps": top(gaps)}
