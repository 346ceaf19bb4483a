"""Sharded merge: device milliseconds per pass of the all-reduces that
carry the per-diagonal (n, n) deltas (opcode all-reduce, a result of rank
2 or more, so the probe's scalar maxima are not counted), averaged over
the devices; the entry reduces its own traced chunk for it."""


def read(ctx):
    return ctx["counters"].get("merge_ms")
