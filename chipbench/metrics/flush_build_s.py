"""flush_build_s: mean time per MemGraph flush spent building the L0 run
(flush arrays, CSR build and repad, the run file's host metadata, with the
compiles they trigger), from the program's ``store_flush_build`` span
(core/store.py ``flush_memgraph``); None where the window held no flush."""
from chipbench.readers import hist_mean


def read(run):
    return hist_mean(run, "store_flush_build_seconds")
