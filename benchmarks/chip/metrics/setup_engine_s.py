"""Seconds of set-up spent building the engine: the program's own
`engine.init` spans (`repro.runtime.spans`; the octree structure and the
device copies in `PlasticityEngine.__init__`).  None where the program
records no such span."""


def read(reduced: dict, ctx: dict):
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    return spans.seconds("engine.init")
