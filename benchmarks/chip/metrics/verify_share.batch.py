"""Share of the batch window spent in pair verification.

Program counter: ``ClusterStats.verify_seconds`` over the window (a host
clock around verify calls whose results come back to numpy)."""


def read(ctx):
    c = ctx.counters
    if c.get("window_s", 0) <= 0 or "verify_s" not in c:
        return None
    return 100.0 * c["verify_s"] / c["window_s"]
