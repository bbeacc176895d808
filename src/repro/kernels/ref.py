"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Each function computes exactly what the corresponding kernel computes;
tests sweep shapes/dtypes and assert_allclose kernel-vs-ref.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import lsh as _lsh
from repro.core import minhash as _minhash
from repro.core import shingle as _shingle


def ngram_hashes(tokens, lengths, n: int = 8):
    return _shingle.ngram_hashes(tokens, lengths, n=n)


def minhash_signatures(ngrams, valid, seeds):
    return _minhash.signatures(ngrams, valid, seeds)


def band_values(sig, r: int):
    return _lsh.band_values(sig, r)


def pair_counts(sig_a, sig_b):
    return jnp.sum((sig_a == sig_b).astype(jnp.float32), axis=-1)


def fused_ingest(tokens, lengths, seeds, *, n: int = 8, r: int = 2):
    """Staged-jnp oracle of the fused pass: shingle -> minhash -> fold."""
    ng, valid = _shingle.ngram_hashes(tokens, lengths, n=n)
    sig = _minhash.signatures(ng, valid, seeds)
    return sig, _lsh.band_values(sig, r), valid
