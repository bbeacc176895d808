"""Device time of the signature-estimate verify per 1,000 notes
ingested in the window: the row gather from the device signature store
and the agreement counts, one program per verify batch.

Trace: summed ``XLA Modules`` durations of the programs named below
(``kernels/sigjaccard.indexed_pair_counts``, jitted as
``jit_indexed_pair_counts``)."""
import kernel_bytes

PROGRAMS = ("indexed_pair_counts",)


def read(ctx):
    return kernel_bytes.program_ms_per_1k_notes(ctx, PROGRAMS)
