"""Share of the chip's HBM bandwidth the byte tokenizer reaches while
it runs: the modelled bytes of the ``byte_shingle`` ops (the byte tile
read, the token and end matrices written, the lengths and carries) over
their device time at ``peaks.hbm_bytes_per_s``.

Trace: the ``XLA Ops`` events of the Pallas call named
``byte_shingle``; the bytes from ``kernel_bytes.py``."""
import kernel_bytes

KERNEL = "byte_shingle"


def read(ctx):
    return kernel_bytes.hbm_share(ctx, KERNEL,
                                  kernel_bytes.byte_shingle_op_bytes)
