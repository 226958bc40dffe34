"""programs_built.ingest: programs compiled or loaded from the persistent
cache in the window, from the program's ``runtime_programs_built_total``
counter (compile_cache.py); 0 is a value.  None where the program has no
such counter."""


def read(run):
    return run.obs.get("runtime_programs_built_total")
