"""Candidate pairs generated per note admitted in the window: band
collisions within the chunk and against the retained index.

Program counter: ``ClusterStats.pairs_generated`` over the window."""


def read(ctx):
    c = ctx.counters
    if c.get("notes", 0) <= 0 or "pairs_generated" not in c:
        return None
    return c["pairs_generated"] / c["notes"]
