"""`compile.seconds`: what JAX spent getting executables (compiling, or
reading the cache) up to the close of the window; the slowest rank."""
from perfbench.layers.program_stats import counter


def read(observed):
    return counter(observed, "compile.seconds")
