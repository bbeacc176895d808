"""Share of the batch window the host spends matching a
chunk's band values against the retained index and inserting them
(``BandIndex.match_then_insert``).

Program span: self time of ``dedup.band_index`` in the
window (its duration minus what its child spans cover), as the
program kept it during the traced window."""
import program_spans

SPAN = "dedup.band_index"


def read(ctx):
    return program_spans.share(ctx, SPAN)
