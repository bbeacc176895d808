"""Share of the batch window the host spends tokenizing and
stemming a chunk's notes (``DedupPipeline.tokenize``).

Program span: self time of ``dedup.tokenize`` in the
window (its duration minus what its child spans cover), as the
program kept it during the traced window."""
import program_spans

SPAN = "dedup.tokenize"


def read(ctx):
    return program_spans.share(ctx, SPAN)
