"""`allreduce.stage`: device_put and make_array_from_single_device_arrays before a device collective; mean seconds a call."""
from perfbench.layers.program_stats import span_mean


def read(observed):
    return span_mean(observed, "allreduce.stage")
