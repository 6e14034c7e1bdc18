"""Seconds of set-up spent tracing and lowering programs to HLO: the
`compile.trace` and `compile.lower` spans the program records from JAX's
compile events (`repro.runtime.spans`).  The harness reads metrics after
the window and fails a run that compiles inside it, so every such span was
recorded before the window.  None where the program records none."""


def read(reduced: dict, ctx: dict):
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    return spans.seconds("compile.trace", "compile.lower")
