"""Device: share of the traced window in which no op ran on a chip (1
minus the union of device-op intervals over the window, averaged over the
cell's chips)."""


def read(ctx):
    t = ctx["trace"]
    if not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
