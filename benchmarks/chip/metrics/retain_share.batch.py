"""Share of the batch window the host spends growing the exact
verifier's rows with a chunk's notes (``DedupSession._retain``).

Program span: self time of ``dedup.retain`` in the
window (its duration minus what its child spans cover), as the
program kept it during the traced window."""
import program_spans

SPAN = "dedup.retain"


def read(ctx):
    return program_spans.share(ctx, SPAN)
