"""`stage.put`: prepare_shard, as it returns (transfers may still be in flight); seconds, the slowest rank."""
from perfbench.layers.program_stats import span_total


def read(observed):
    return span_total(observed, "stage.put")
