"""Host between chunks: host round trips per ``run_until`` call (device
reads and programs launched from the host), from the program's counters
``repro.run_until.syncs`` over ``repro.run_until.calls``."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:  # a program without the counters
        return None
    reg = obs.snapshot()
    calls = reg.get("repro.run_until.calls", {}).get("n")
    syncs = reg.get("repro.run_until.syncs", {}).get("n")
    if not calls or syncs is None:
        return None
    return syncs / calls
