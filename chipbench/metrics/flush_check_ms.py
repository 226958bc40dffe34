"""flush_check_ms: mean time per write-path chunk spent on the flush
trigger, which reads the MemGraph's device scalars back to the host, from
the program's ``store_flush_check`` span (core/store.py ``_should_flush``,
core/memgraph.py ``memgraph_should_flush``)."""
from chipbench.readers import hist_mean


def read(run):
    return hist_mean(run, "store_flush_check_seconds", 1e3)
