"""Share of the chip's HBM bandwidth the signature-estimate verify
reaches while it runs: the modelled bytes of each verify call (the two
index vectors, the 2 x P signature rows they gather from the device
store, and the P counts, at the padded pair count P read from its
``sigjaccard_counts`` op) over the device time of the verify programs
at ``peaks.hbm_bytes_per_s``.  The kernel alone reads its gathered rows
from VMEM, so the program's time, gather included, is the one that
holds the HBM traffic.

Trace: the ``XLA Ops`` events of the Pallas call named
``sigjaccard_counts`` for the shapes, the ``XLA Modules`` events named
below (``jit_indexed_pair_counts``) for the time; the bytes from
``kernel_bytes.py``."""
import kernel_bytes

KERNEL = "sigjaccard_counts"
PROGRAMS = ("indexed_pair_counts",)


def read(ctx):
    return kernel_bytes.hbm_share(ctx, KERNEL,
                                  kernel_bytes.sigjaccard_op_bytes,
                                  PROGRAMS)
