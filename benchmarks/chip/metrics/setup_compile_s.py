"""Seconds of set-up spent in XLA compiles or loads from the persistent
compilation cache: the `compile.backend` spans the program records from
JAX's compile events (`repro.runtime.spans`; its `compile.cache_hit` and
`compile.cache_miss` counters say which).  The harness reads metrics after
the window and fails a run that compiles inside it, so every such span was
recorded before the window.  None where the program records none."""


def read(reduced: dict, ctx: dict):
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    return spans.seconds("compile.backend")
