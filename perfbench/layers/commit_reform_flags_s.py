"""`commit.reform_flags`: the XLA engine's host allreduce of the degraded flags after a commit; mean seconds a call."""
from perfbench.layers.program_stats import span_mean


def read(observed):
    return span_mean(observed, "commit.reform_flags")
