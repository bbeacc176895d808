"""Share of the batch window the host spends in the merge
(``ClusterAccumulator.feed``: band runs, root compression, union-find),
outside the verify calls inside it.

Program span: self time of ``dedup.merge`` in the window, as the
program kept it during the traced window, less its ``verify_ns`` stat
(the verifier's clock over the same feed)."""
import program_spans

SPAN = "dedup.merge"


def read(ctx):
    sp = program_spans.of_run(ctx)
    if sp is None or not sp.count(SPAN):
        return None
    own = sp.self_s(SPAN) - sp.stat_sum(SPAN, "verify_ns") * 1e-9
    return 100.0 * own / ctx.trace.window_s()
