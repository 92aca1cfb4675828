"""`init.group`: forming the JAX process group and the process mesh; seconds, the slowest rank."""
from perfbench.layers.program_stats import span_total


def read(observed):
    return span_total(observed, "init.group")
