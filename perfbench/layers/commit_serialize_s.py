"""`commit.serialize`: pickling the model inside rabit_tpu.checkpoint; mean seconds a call."""
from perfbench.layers.program_stats import span_mean


def read(observed):
    return span_mean(observed, "commit.serialize")
