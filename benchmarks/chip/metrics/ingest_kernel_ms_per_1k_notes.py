"""Device time of the ingest program per 1,000 notes ingested.

Trace: summed ``XLA Modules`` durations of the programs named below
(``kernels/fused_ingest.py``, jitted as ``jit_fused_ingest``)."""

PROGRAMS = ("fused_ingest",)


def read(ctx):
    if ctx.trace is None:
        return None
    s = ctx.trace.program_s(PROGRAMS)
    notes = ctx.counters.get("notes", 0)
    if s <= 0 or notes <= 0:
        return None
    return s * 1e3 / (notes / 1000.0)
