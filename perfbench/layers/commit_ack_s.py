"""`commit.ack`: the robust engine's acknowledgement round after a commit; mean seconds a call."""
from perfbench.layers.program_stats import span_mean


def read(observed):
    return span_mean(observed, "commit.ack")
