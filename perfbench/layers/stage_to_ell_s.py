"""`stage.to_ell`: CSR to padded ELL on the host; seconds, the slowest rank."""
from perfbench.layers.program_stats import span_total


def read(observed):
    return span_total(observed, "stage.to_ell")
