"""`stage.clamp`: the clamp's copy of the ELL indices; seconds, the slowest rank."""
from perfbench.layers.program_stats import span_total


def read(observed):
    return span_total(observed, "stage.clamp")
