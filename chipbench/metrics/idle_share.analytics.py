"""idle_share.analytics: share of the traced window in which the chip ran no
operation, from the profiler's device trace (trace_reduce.py)."""
from chipbench.readers import idle_share


def read(run):
    return idle_share(run)
