"""Share of the batch window the host spends packing token lists
into the padded token matrix (``shingle.pack_documents``).

Program span: self time of ``dedup.pack`` in the
window (its duration minus what its child spans cover), as the
program kept it during the traced window."""
import program_spans

SPAN = "dedup.pack"


def read(ctx):
    return program_spans.share(ctx, SPAN)
