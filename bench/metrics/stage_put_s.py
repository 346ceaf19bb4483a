"""Host staging: seconds the ParallelSolver constructor spent putting the
staged arrays on the device and waiting for them, from the program's span
``repro.stage.put`` in set-up."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:  # a program without the span
        return None
    span = obs.snapshot().get("repro.stage.put")
    return None if span is None else span["s"]
