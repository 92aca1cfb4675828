"""`compile.misses`: programs compiled because the persistent cache did
not have them, up to the close of the window; the worst rank."""
from perfbench.layers.program_stats import counter


def read(observed):
    return counter(observed, "compile.misses")
