"""Bytes copied from host to device into the signature store per note
ingested in the window.

Program counter: the ``h2d_bytes`` stat of the ``dedup.sig_store`` spans
the program kept during the traced window (the rows when they come from
the host, and the row offset or slot indices) over the window's notes.
A program without the store reads nothing."""
import program_spans

SPAN = "dedup.sig_store"


def read(ctx):
    return program_spans.per_note(ctx, SPAN, "h2d_bytes")
