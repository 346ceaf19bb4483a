"""Sharded merge: milliseconds per pass of the merge's all-reduces during
which no other leaf op runs on that device (the part of the merge that
nothing hides), averaged over the devices; the entry reduces its own
traced chunk for it."""


def read(ctx):
    return ctx["counters"].get("merge_exposed_ms")
