"""apply_dispatch_ms: mean time per write-path chunk spent padding it,
putting it on the device and dispatching the MemGraph insert, from the
program's ``store_apply_insert`` span (core/store.py ``_insert_batch``)."""
from chipbench.readers import hist_mean


def read(run):
    return hist_mean(run, "store_apply_insert_seconds", 1e3)
