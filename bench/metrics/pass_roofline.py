"""The pass: its floor bytes (computed from n alone) over HBM bandwidth,
as a share of the device-busy time per pass in the traced window. HBM
bandwidth bounds the pass; its operations are a few per byte."""

from bench.roofline import pass_floor_bytes


def read(ctx):
    passes = ctx["counters"].get("passes_traced")
    busy = ctx["trace"]["busy_s"]
    bw = ctx["peaks"].get("hbm_bytes_per_s")
    if not passes or not busy or not bw:
        return None
    floor_s = pass_floor_bytes(ctx["counters"]["n"]) / bw
    return 100.0 * floor_s / (busy / passes)
