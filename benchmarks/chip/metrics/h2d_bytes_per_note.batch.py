"""Bytes copied from host to device per note ingested in the window.

Program counter: the ``h2d_bytes`` stat of the ``dedup.device_ingest``
spans the program kept during the traced window (the packed token matrix
and lengths) over the window's notes."""
import program_spans

SPAN = "dedup.device_ingest"


def read(ctx):
    return program_spans.per_note(ctx, SPAN, "h2d_bytes")
