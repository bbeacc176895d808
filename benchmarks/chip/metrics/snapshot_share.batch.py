"""Share of the batch window the host spends building the
cumulative snapshot after each chunk (``DedupSession.snapshot``: labels
and the sorted list of every verified pair).

Program span: self time of ``dedup.snapshot`` in the
window (its duration minus what its child spans cover), as the
program kept it during the traced window."""
import program_spans

SPAN = "dedup.snapshot"


def read(ctx):
    return program_spans.share(ctx, SPAN)
