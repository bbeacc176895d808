"""Device time of the byte ingest per 1,000 notes ingested in the
window: the byte tokenizer, the token compaction and the fused shingle,
MinHash and band fold, one program a chunk.

Trace: summed ``XLA Modules`` durations of the programs named below
(``kernels/byte_shingle.bytes_to_bands``, jitted as
``jit_bytes_to_bands``)."""
import kernel_bytes

PROGRAMS = ("bytes_to_bands",)


def read(ctx):
    return kernel_bytes.program_ms_per_1k_notes(ctx, PROGRAMS)
