"""apply_ms: mean write-path time per MemGraph insert chunk (up to 4,096
records), from the program's ``store_apply_seconds`` (core/store.py
``_apply``, host clock, ends in the ``bool(ok)`` device sync)."""
from chipbench.readers import hist_mean


def read(run):
    return hist_mean(run, "store_apply_seconds", 1e3)
