"""Pairs verified per note ingested in the window (the paper's Table 5:
pairs the disjoint sets did not exclude).

Program counter: ``ClusterStats.pairs_evaluated`` over the window."""


def read(ctx):
    c = ctx.counters
    if c.get("notes", 0) <= 0 or "pairs_evaluated" not in c:
        return None
    return c["pairs_evaluated"] / c["notes"]
