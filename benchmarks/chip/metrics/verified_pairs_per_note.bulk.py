"""Pairs verified by the m/M estimate per note admitted in the window
(the pairs the disjoint sets did not exclude).

Program counter: ``ClusterStats.pairs_evaluated`` over the window."""


def read(ctx):
    c = ctx.counters
    if c.get("notes", 0) <= 0 or "pairs_evaluated" not in c:
        return None
    return c["pairs_evaluated"] / c["notes"]
