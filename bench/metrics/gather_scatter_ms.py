"""Bucket engine: device milliseconds per pass of the XLA gathers, scatters
and dynamic-update-slices, from the traced window."""


def read(ctx):
    passes = ctx["counters"].get("passes_traced")
    secs = ctx["trace"]["class_s"].get("gather_scatter")
    if not passes or not secs:
        return None
    return secs / passes * 1e3
