"""`learn.iterations` over `learn.versions`: the iterations the learner
dispatched for each version it committed (the chain length)."""
from perfbench.layers.program_stats import counter_ratio


def read(observed):
    return counter_ratio(observed, "learn.iterations", "learn.versions")
