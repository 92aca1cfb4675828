"""`learn.dispatch`: uploading the model and enqueueing a version's device work; mean seconds a call."""
from perfbench.layers.program_stats import span_mean


def read(observed):
    return span_mean(observed, "learn.dispatch")
