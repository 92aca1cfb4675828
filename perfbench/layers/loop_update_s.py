"""`learn.update`: the learner's host arithmetic between the fetch and the commit, a version; mean seconds a call."""
from perfbench.layers.program_stats import span_mean


def read(observed):
    return span_mean(observed, "learn.update")
