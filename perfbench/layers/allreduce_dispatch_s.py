"""`allreduce.dispatch`: handing the collective's program to the device; mean seconds a call."""
from perfbench.layers.program_stats import span_mean


def read(observed):
    return span_mean(observed, "allreduce.dispatch")
