"""`commit.barrier`: the robust engine's consensus round before a commit; mean seconds a call."""
from perfbench.layers.program_stats import span_mean


def read(observed):
    return span_mean(observed, "commit.barrier")
