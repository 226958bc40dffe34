"""apply_sync_ms: mean time per write-path chunk spent waiting for the
device insert's ok flag (``bool(ok)``), from the program's
``store_apply_sync`` span (core/store.py ``_insert_batch``)."""
from chipbench.readers import hist_mean


def read(run):
    return hist_mean(run, "store_apply_sync_seconds", 1e3)
