"""Pairs copied into the snapshots per note ingested in the window.

Program counter: the ``pairs`` stat of the ``dedup.snapshot`` spans the
program kept during the traced window (every verified pair so far,
sorted, once per chunk) over the window's notes."""
import program_spans

SPAN = "dedup.snapshot"


def read(ctx):
    return program_spans.per_note(ctx, SPAN, "pairs")
