"""ack_wait_ms: mean time an ``ack`` blocks, from the program's
``storage_wal_ack`` span (storage/wal.py ``sync_upto``, the whole call:
an fsync of its own or the wait behind an in-flight group commit)."""
from chipbench.readers import hist_mean


def read(run):
    return hist_mean(run, "storage_wal_ack_seconds", 1e3)
