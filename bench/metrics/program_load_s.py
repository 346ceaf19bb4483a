"""Compile and cache load: seconds of the first ``run_until`` launch in
set-up (trace, lower, and compile or load from the compile cache), from
the program's span ``repro.run_until.launch``."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:  # a program without the span
        return None
    span = obs.snapshot().get("repro.run_until.launch")
    return None if span is None else span["first_s"]
