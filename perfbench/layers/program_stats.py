"""What the readers of the program's own spans and counters share.

The program times its layer boundaries itself (``rabit_tpu/obs/
program.py``) and an engine reports the table as ``path_stats``: a flat
dict with ``<span>.n``, ``<span>.total_s``, ``<span>.max_s`` and each
counter under its own name.  The harness copies it whole into every
rank's observation at the close of the window.  A program without the
table (the parent of the PR that added it) gives every reader nothing
to read: None, and the metric is left out of the line.
"""
from perfbench.readers import across


def _stats(rank: dict) -> dict:
    return rank.get("path_stats") or {}


def span_mean(observed, name: str, over: str = "mean"):
    """Mean seconds a call of a span of the loop, without the single
    longest call: the first one carries connection set-up, page faults
    and compiles."""
    values = []
    for r in observed.ranks:
        s = _stats(r)
        n = s.get(name + ".n", 0)
        values.append((s[name + ".total_s"] - s[name + ".max_s"]) / (n - 1)
                      if n > 1 else None)
    return across(values, over)


def span_total(observed, name: str, over: str = "max"):
    """Seconds in a span of set-up, which runs once."""
    return across([_stats(r).get(name + ".total_s")
                   for r in observed.ranks], over)


def counter(observed, name: str, over: str = "max"):
    return across([_stats(r).get(name) for r in observed.ranks], over)


def counter_ratio(observed, part: str, whole: str, over: str = "mean"):
    return across([_stats(r)[part] / _stats(r)[whole]
                   if _stats(r).get(whole) and part in _stats(r) else None
                   for r in observed.ranks], over)
