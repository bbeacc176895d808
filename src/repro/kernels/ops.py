"""Jit'd public wrappers over the Pallas kernels.

``interpret=None`` auto-selects: interpret mode on CPU (validation), real
Mosaic lowering on TPU.  These are the entry points the pipeline uses when
``DedupConfig.use_pallas`` is set.
"""
from __future__ import annotations

from repro.kernels.minhash import minhash_signatures
from repro.kernels.ngram import ngram_hashes
from repro.kernels.bandfold import band_values
from repro.kernels.fused_ingest import fused_ingest
from repro.kernels.byte_shingle import byte_token_hashes, bytes_to_bands
from repro.kernels.sigjaccard import (
    indexed_pair_counts,
    masked_indexed_pair_counts,
    masked_indexed_pair_estimate,
    masked_pair_counts,
    pair_counts,
)
from repro.kernels.flash_attention import flash_attention

__all__ = [
    "minhash_signatures",
    "ngram_hashes",
    "band_values",
    "fused_ingest",
    "byte_token_hashes",
    "bytes_to_bands",
    "pair_counts",
    "indexed_pair_counts",
    "masked_indexed_pair_counts",
    "masked_indexed_pair_estimate",
    "masked_pair_counts",
    "flash_attention",
]
