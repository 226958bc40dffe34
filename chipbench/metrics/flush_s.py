"""flush_s: mean time per MemGraph flush to an L0 run in the window, from
the program's ``store_flush_seconds`` span (core/store.py
``flush_memgraph``); None where the window held no flush."""
from chipbench.readers import hist_mean


def read(run):
    return hist_mean(run, "store_flush_seconds")
