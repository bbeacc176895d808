"""Plain reference of the ``admission_bytes`` deployment: the m/M
signature estimate in place of exact Jaccard.

Written on ``reference.py``'s primitives, independent of the system
under test:

* ``arrays`` — tokens (no stemming for this deployment), word n-gram
  MinHash signatures and the r-row band values;
* ``candidate_pairs`` — every pair of notes whose band keys collide in
  at least one band, over all the notes;
* ``estimate`` — the fraction of the M hashes on which two notes'
  signatures agree: the integer count, then ``float32(count) /
  float32(M)``, one correctly rounded division.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference as ref


# Documents signed at a time, and blocks signed at once: a block's
# tokens and n-gram arrays take about 12 KB a note.
BLOCK = 32768
WORKERS = 4


def arrays(texts: list[str], cfg: dict) -> tuple[np.ndarray, np.ndarray]:
    """(D, M) signatures and (D, M // r, 2) band values of ``texts``.

    A document's signature depends on its own tokens alone, so blocks of
    documents are tokenized and signed apart, a few at once in threads
    (numpy's array kernels release the interpreter lock); the result is
    the same as one ``ref.signatures`` call over every document, in a
    fraction of its memory."""
    seeds = ref.minhash_seeds(cfg["num_hashes"], cfg["seed_key"])

    def sign(lo: int) -> np.ndarray:
        tok = ref.Tokens(texts[lo:lo + BLOCK], do_stem=cfg["stem"])
        return ref.signatures(tok, cfg["ngram"], seeds)

    with ThreadPoolExecutor(WORKERS) as pool:
        sig = np.concatenate(list(pool.map(sign, range(0, len(texts),
                                                       BLOCK)))
                             or [np.zeros((0, len(seeds)), np.uint32)])
    return sig, ref.band_values(sig, cfg["rows_per_band"])


def candidate_pairs(bands: np.ndarray) -> np.ndarray:
    """Distinct pairs (a < b) that share a band key, sorted."""
    keys = ref.band_keys(bands)
    n = len(keys)
    found = [ref.group_pairs(keys[:, j], np.arange(n))
             for j in range(keys.shape[1])]
    return ref.unique_pairs(np.concatenate(found), n)


def estimate(sig: np.ndarray, pairs: np.ndarray,
             block: int = 65536) -> np.ndarray:
    """float32 m/M estimate of each (a, b) pair of notes."""
    pairs = np.asarray(pairs, dtype=np.int64).reshape(-1, 2)
    count = np.empty(len(pairs), dtype=np.int64)
    for s in range(0, len(pairs), block):
        p = pairs[s:s + block]
        count[s:s + len(p)] = np.sum(sig[p[:, 0]] == sig[p[:, 1]], axis=1)
    return count.astype(np.float32) / np.float32(sig.shape[1])
