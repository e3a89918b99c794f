"""Device (TPU v5e): the share of the traced window in which no
operation ran on the chip (1 minus the union of device-op intervals)."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.device_ops:
        return None
    lo, hi = ctx.trace_window
    return 100.0 * (1.0 - ctx.trace.busy_s(lo, hi) / ((hi - lo) / 1e9))
