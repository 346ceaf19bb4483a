"""The sharded pass: its floor bytes (computed from n alone) over the HBM
bandwidth of all the cell's chips, as a share of the device-busy time per
pass in the traced window (busy averaged over the devices)."""

from bench.roofline import pass_floor_bytes


def read(ctx):
    passes = ctx["counters"].get("passes_traced")
    busy = ctx["trace"]["busy_s"]
    bw = ctx["peaks"].get("hbm_bytes_per_s")
    if not passes or not busy or not bw:
        return None
    floor_s = pass_floor_bytes(ctx["counters"]["n"]) / (
        int(ctx["cell"]["chips"]) * bw)
    return 100.0 * floor_s / (busy / passes)
