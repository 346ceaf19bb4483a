"""Sweep kernel: device milliseconds per pass of the ``metric_sweep``
Pallas calls, from the traced window."""


def read(ctx):
    passes = ctx["counters"].get("passes_traced")
    secs = ctx["trace"]["class_s"].get("sweep")
    if not passes or not secs:
        return None
    return secs / passes * 1e3
