"""Share of the bulk-admission window in which no operation ran on the
chip.

Trace: 1 - (union of the device's operation intervals) / window."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s())
