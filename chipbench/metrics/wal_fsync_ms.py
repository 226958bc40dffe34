"""wal_fsync_ms: mean time per WAL fsync (group commit or an ack's own),
from the program's ``storage_wal_fsync_seconds`` (storage/wal.py)."""
from chipbench.readers import hist_mean


def read(run):
    return hist_mean(run, "storage_wal_fsync_seconds", 1e3)
