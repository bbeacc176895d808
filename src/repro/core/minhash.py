"""MinHash signatures (paper §3).

``signatures``: for each document d and each of M seeded hash functions,
sig[d, m] = min over the doc's n-gram hashes x of h_m(x).  The estimate of
Jaccard(A, B) is then mean_m[ sig_A[m] == sig_B[m] ]  (paper §3.3-3.4).

Pure-jnp implementation here; the Pallas kernel in
``repro.kernels.minhash`` computes the same function with explicit VMEM
tiling and is validated against this module.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.hashing import GOLDEN32, U32_MAX, fmix32, make_seeds


@functools.partial(jax.jit, static_argnames=("m_chunk",))
def signatures(
    ngrams: jnp.ndarray,
    valid: jnp.ndarray,
    seeds: jnp.ndarray,
    m_chunk: int = 16,
) -> jnp.ndarray:
    """MinHash signature matrix.

    ngrams: (D, L) uint32 n-gram hashes; valid: (D, L) bool; seeds: (M,).
    Returns (D, M) uint32.  Invalid positions contribute U32_MAX.
    Memory is bounded by chunking over seeds: peak extra (D, L, m_chunk).
    """
    ngrams = ngrams.astype(jnp.uint32)
    seeds = seeds.astype(jnp.uint32)
    M = seeds.shape[0]
    pad = (-M) % m_chunk
    seeds_p = jnp.pad(seeds, (0, pad)).reshape(-1, m_chunk)
    masked_max = jnp.uint32(U32_MAX)

    def one_chunk(chunk_seeds):
        # (D, L, 1) x (1, 1, C) -> (D, L, C)
        h = fmix32(ngrams[:, :, None] * GOLDEN32 + chunk_seeds[None, None, :])
        h = jnp.where(valid[:, :, None], h, masked_max)
        return jnp.min(h, axis=1)  # (D, C)

    sig = jax.lax.map(one_chunk, seeds_p.astype(jnp.uint32))  # (M/C, D, C)
    sig = jnp.moveaxis(sig, 0, 1).reshape(ngrams.shape[0], -1)
    return sig[:, :M]


def signatures_np(
    ngrams: np.ndarray, valid: np.ndarray, seeds: np.ndarray
) -> np.ndarray:
    """Numpy oracle."""
    from repro.core.hashing import hash_u32_np

    D, L = ngrams.shape
    M = seeds.shape[0]
    out = np.full((D, M), U32_MAX, dtype=np.uint32)
    for m in range(M):
        h = hash_u32_np(ngrams, seeds[m])
        h = np.where(valid, h, np.uint32(U32_MAX))
        out[:, m] = h.min(axis=1)
    return out


def minhash_from_tokens(
    tokens: jnp.ndarray,
    lengths: jnp.ndarray,
    seeds: jnp.ndarray,
    n: int = 8,
) -> jnp.ndarray:
    """Fused convenience path: token matrix -> signatures."""
    from repro.core.shingle import ngram_hashes

    ngrams, valid = ngram_hashes(tokens, lengths, n=n)
    return signatures(ngrams, valid, seeds)


def default_seeds(m: int = 100) -> np.ndarray:
    return make_seeds(m)
