"""Convergence engine: one warm call of the stopping-pair probe
(``_probe_fn``), timed with ``block_until_ready`` after the window."""


def read(ctx):
    return ctx["counters"].get("probe_ms")
