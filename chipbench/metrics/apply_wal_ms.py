"""apply_wal_ms: mean time per write-path chunk spent logging it, from the
program's ``store_apply_wal`` span (core/store.py ``_apply``: the WAL
record's encode and append under the commit lock, storage/wal.py)."""
from chipbench.readers import hist_mean


def read(run):
    return hist_mean(run, "store_apply_wal_seconds", 1e3)
