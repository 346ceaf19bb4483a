"""Host staging: seconds the ParallelSolver constructor spent building the
layout and the staged slabs on the host, from the program's spans
``repro.stage.layout`` and ``repro.stage.slabs`` in set-up."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:  # a program without the spans
        return None
    reg = obs.snapshot()
    spans = [reg.get(k) for k in ("repro.stage.layout", "repro.stage.slabs")]
    if None in spans:
        return None
    return sum(s["s"] for s in spans)
