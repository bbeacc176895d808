"""Pairs the snapshots sorted into their store per note ingested in the
window.

Program counter: the ``sorted`` stat of the ``dedup.snapshot`` spans the
program kept during the traced window (the pairs each snapshot folded
into the sorted pair store, so every verified pair once) over the
window's notes.  A program whose snapshots carry no such stat (one that
re-sorts every pair on each snapshot) reads nothing."""
import program_spans

SPAN = "dedup.snapshot"
KEY = "sorted"


def read(ctx):
    sp = program_spans.of_run(ctx)
    if sp is None or not any(name == SPAN and KEY in stats
                             for name, _, _, _, stats in sp.spans):
        return None
    return program_spans.per_note(ctx, SPAN, KEY)
