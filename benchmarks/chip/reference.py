"""Plain numpy reference of the deduplication the benchmark checks.

Written from the method's description (arXiv:1704.05617 §2-§6 and the
constants the configuration files state), independent of the system
under test: it imports nothing of it and takes nothing it has made.

* ``tokenize`` / ``Tokens`` — alphanumeric runs, lower-cased, optionally
  stemmed by the suffix-stripping stemmer of the configuration
  (``STEM_SUFFIXES``); a token's id is FNV-1a over its bytes, then the
  seeded mix.
* ``signatures`` — word n-gram rolling hashes, then M seeded minima.
* ``band_values`` — the r rows of each band folded into two 32-bit lanes.
* ``GramSets`` — exact n-gram sets (dense ids), exact Jaccard by merge,
  and ``similar_pairs``: every pair whose Jaccard exceeds a threshold,
  by a prefix-filtered set-similarity join (exact, no sampling).
* ``components`` — connected components of a pair graph.

Sims are float32 exactly as the method states them: exact Jaccard is
``inter / union`` divided in float64 and rounded once to float32.
Checks built on these are in ``compare.py``.
"""
from __future__ import annotations

import re

import numpy as np

WORD_RE = re.compile(r"[A-Za-z0-9]+")
STEM_SUFFIXES = (
    "ational", "iveness", "fulness", "ousness",
    "ication", "izations", "ization",
    "ingly", "edly", "ings",
    "ing", "ies", "ied", "ely", "es", "ed", "ly", "s",
)
FMIX_C1 = np.uint32(0x85EBCA6B)
FMIX_C2 = np.uint32(0xC2B2AE35)
GOLDEN = np.uint32(0x9E3779B9)
NGRAM_BASE = np.uint32(0x01000193)
FNV_OFFSET = 2166136261
FNV_PRIME = 16777619
TOKEN_SEED = np.uint32(0x7045)
LANE_SEEDS = (np.uint32(0x2545F491), np.uint32(0x9E3779B9))
U32_MAX = np.uint32(0xFFFFFFFF)


def stem(word: str) -> str:
    w = word.lower()
    for suf in STEM_SUFFIXES:
        if w.endswith(suf) and len(w) - len(suf) >= 3:
            return w[: -len(suf)]
    return w


def tokenize(text: str, do_stem: bool) -> list[str]:
    toks = WORD_RE.findall(text)
    return [stem(t) for t in toks] if do_stem else [t.lower() for t in toks]


def fmix32(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, dtype=np.uint32)
    with np.errstate(over="ignore"):
        x = x ^ (x >> np.uint32(16))
        x = (x * FMIX_C1).astype(np.uint32)
        x = x ^ (x >> np.uint32(13))
        x = (x * FMIX_C2).astype(np.uint32)
        x = x ^ (x >> np.uint32(16))
    return x


def seeded_hash(x: np.ndarray, seed) -> np.ndarray:
    """h_seed(x) = fmix32(x * GOLDEN + seed), all mod 2**32."""
    with np.errstate(over="ignore"):
        return fmix32((np.asarray(x, dtype=np.uint32) * GOLDEN
                       ).astype(np.uint32) + np.uint32(seed))


def minhash_seeds(m: int, key: int) -> np.ndarray:
    """The M hash seeds: numpy's legacy generator seeded with ``key``."""
    rng = np.random.RandomState(key & 0x7FFFFFFF)
    return rng.randint(0, 2**32, size=(m,), dtype=np.uint64).astype(np.uint32)


def _fnv1a(token: str) -> int:
    h = FNV_OFFSET
    for ch in token.encode("utf-8"):
        h = ((h ^ ch) * FNV_PRIME) & 0xFFFFFFFF
    return h


class Tokens:
    """Token lists of a corpus as one flat array of dense token ids."""

    def __init__(self, texts: list[str], do_stem: bool):
        vocab: dict[str, int] = {}
        flat: list[int] = []
        lengths = np.empty(len(texts), dtype=np.int64)
        cache: dict[str, str] = {}
        for i, text in enumerate(texts):
            raw = WORD_RE.findall(text)
            for t in raw:
                s = cache.get(t)
                if s is None:
                    s = cache[t] = stem(t) if do_stem else t.lower()
                flat.append(vocab.setdefault(s, len(vocab)))
            lengths[i] = len(raw)
        self.words = list(vocab)
        self.flat = np.asarray(flat, dtype=np.int64)
        self.lengths = lengths
        self.offsets = np.concatenate([[0], np.cumsum(lengths)])
        hashed = np.array([_fnv1a(w) for w in self.words], dtype=np.uint32)
        self.word_hash = (seeded_hash(hashed, TOKEN_SEED) if len(hashed)
                          else hashed)

    def __len__(self) -> int:
        return len(self.lengths)


def _gram_starts(tok: Tokens, n: int) -> tuple[np.ndarray, np.ndarray]:
    """(doc, flat start) of every n-gram: a document of L >= n tokens
    has L - n + 1; a shorter non-empty one has one, its whole text."""
    count = np.where(tok.lengths >= n, tok.lengths - n + 1,
                     (tok.lengths > 0).astype(np.int64))
    doc = np.repeat(np.arange(len(tok)), count)
    first = np.repeat(tok.offsets[:-1], count)
    within = np.arange(count.sum()) - np.repeat(
        np.concatenate([[0], np.cumsum(count)[:-1]]), count)
    return doc, first + within


def signatures(tok: Tokens, n: int, seeds: np.ndarray) -> np.ndarray:
    """(D, M) MinHash signatures over word n-gram rolling hashes.

    The n-gram hash is ``fmix32(sum_k BASE^(n-1-k) * id[i+k])`` with
    ids past a document's end read as 0; a document with no token has
    every signature entry at 2**32 - 1.
    """
    doc, start = _gram_starts(tok, n)
    ids = np.concatenate([tok.word_hash[tok.flat],
                          np.zeros(n, dtype=np.uint32)])
    end = np.repeat(tok.offsets[1:], np.bincount(doc, minlength=len(tok)))
    acc = np.zeros(len(doc), dtype=np.uint32)
    with np.errstate(over="ignore"):
        for k in range(n):
            pos = start + k
            v = np.where(pos < end, ids[np.minimum(pos, len(ids) - 1)], 0)
            acc = (acc * NGRAM_BASE + v.astype(np.uint32)).astype(np.uint32)
    grams = fmix32(acc)
    sig = np.full((len(tok), len(seeds)), U32_MAX, dtype=np.uint32)
    if not len(grams):
        return sig
    has = np.bincount(doc, minlength=len(tok)) > 0
    bounds = np.concatenate([[0], np.cumsum(
        np.bincount(doc, minlength=len(tok)))[:-1]])[has]
    for m, s in enumerate(seeds):
        h = seeded_hash(grams, s)
        sig[has, m] = np.minimum.reduceat(h, bounds)
    return sig


def band_values(sig: np.ndarray, r: int) -> np.ndarray:
    """(D, M) signatures -> (D, M // r, 2) band values."""
    d, m = sig.shape
    rows = sig.reshape(d, m // r, r)
    lanes = []
    with np.errstate(over="ignore"):
        for seed in LANE_SEEDS:
            h = np.full((d, m // r), seed, dtype=np.uint32)
            for k in range(r):
                h = fmix32((h * GOLDEN).astype(np.uint32) + rows[:, :, k])
            lanes.append(h)
    return np.stack(lanes, axis=-1)


def band_keys(bands: np.ndarray) -> np.ndarray:
    """(D, b, 2) uint32 band values -> (D, b) uint64 keys."""
    return (bands[..., 0].astype(np.uint64) << np.uint64(32)) | \
        bands[..., 1].astype(np.uint64)


def group_pairs(groups: np.ndarray, members: np.ndarray) -> np.ndarray:
    """Every pair (a < b) of ``members`` that share a value of
    ``groups`` (both 1-D, any order), as an (E, 2) int64 array with
    repeats where a pair shares several groups."""
    order = np.lexsort((members, groups))
    g, m = groups[order], np.asarray(members, dtype=np.int64)[order]
    n = len(g)
    if n < 2:
        return np.zeros((0, 2), dtype=np.int64)
    end = np.empty(n, dtype=np.int64)
    cut = np.flatnonzero(g[1:] != g[:-1]) + 1
    bounds = np.concatenate([cut, [n]])
    starts = np.concatenate([[0], cut])
    end[:] = np.repeat(bounds, bounds - starts)
    later = end - np.arange(n) - 1
    total = int(later.sum())
    left = np.repeat(np.arange(n), later)
    offs = np.arange(total) - np.repeat(np.cumsum(later) - later, later)
    right = left + 1 + offs
    a, b = m[left], m[right]
    return np.stack([np.minimum(a, b), np.maximum(a, b)], axis=1)


def unique_pairs(pairs: np.ndarray, n: int) -> np.ndarray:
    """Distinct rows of an (E, 2) pair array over ``n`` nodes, sorted."""
    if not len(pairs):
        return pairs
    key = np.unique(pairs[:, 0] * np.int64(n) + pairs[:, 1])
    return np.stack([key // n, key % n], axis=1)


def components(n: int, edges: np.ndarray) -> np.ndarray:
    """Connected-component label (its smallest member) of each node."""
    parent = np.arange(n)

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for a, b in np.asarray(edges, dtype=np.int64).reshape(-1, 2):
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[max(ra, rb)] = min(ra, rb)
    return np.array([find(i) for i in range(n)], dtype=np.int64)


class GramSets:
    """Exact word n-gram sets of a corpus, as sorted dense-id rows."""

    def __init__(self, tok: Tokens, n: int):
        if len(tok.words) >= 1 << 16:
            raise ValueError("more than 65,535 distinct tokens")
        doc, start = _gram_starts(tok, n)
        end = np.repeat(tok.offsets[1:],
                        np.bincount(doc, minlength=len(tok)))
        # Token id + 1 packed 16 bits apiece (0 marks a position past a
        # short document's end): two uint64 words hold an 8-gram exactly.
        words = [np.zeros(len(doc), dtype=np.uint64) for _ in range(
            (n + 3) // 4)]
        for k in range(n):
            pos = start + k
            v = np.where(pos < end, tok.flat[np.minimum(
                pos, len(tok.flat) - 1)] + 1, 0).astype(np.uint64)
            w = k // 4
            words[w] = (words[w] << np.uint64(16)) | v
        order = np.lexsort([doc] + words[::-1])  # by (gram words, doc)
        sorted_words = [w[order] for w in words]
        new = np.ones(len(order), dtype=bool)
        if len(order):
            new[1:] = np.any(np.stack(
                [w[1:] != w[:-1] for w in sorted_words]), axis=0)
        gid = np.empty(len(order), dtype=np.int64)
        gid[order] = np.cumsum(new) - 1
        # Rows: sorted unique gram ids of each doc.
        n_g = int(gid.max(initial=-1)) + 1
        key = np.unique(doc * np.int64(max(n_g, 1)) + gid)
        self.n_docs = len(tok)
        self.doc = key // max(n_g, 1)
        self.gid = key % max(n_g, 1)
        self.sizes = np.bincount(self.doc, minlength=self.n_docs)
        self.offsets = np.concatenate([[0], np.cumsum(self.sizes)])
        self._rows = None
        self.freq = np.bincount(self.gid) if len(self.gid) else \
            np.zeros(0, dtype=np.int64)

    def jaccard(self, pairs: np.ndarray, block: int = 8192) -> np.ndarray:
        """Exact float32 Jaccard of each (a, b) pair of documents."""
        pairs = np.asarray(pairs, dtype=np.int64).reshape(-1, 2)
        out = np.empty(len(pairs), dtype=np.float32)
        rows = self.padded()
        for s in range(0, len(pairs), block):
            p = pairs[s:s + block]
            merged = np.sort(np.concatenate([rows[p[:, 0]], rows[p[:, 1]]],
                                            axis=1), axis=1)
            # Pads are -1: equal neighbours count only where >= 0.
            inter = np.sum((merged[:, 1:] == merged[:, :-1])
                           & (merged[:, 1:] >= 0), axis=1)
            union = self.sizes[p[:, 0]] + self.sizes[p[:, 1]] - inter
            out[s:s + len(p)] = np.where(
                union > 0, inter / np.maximum(union, 1), 1.0)
        return out

    def padded(self) -> np.ndarray:
        """(D, max set size) gram ids, -1 past each set's end."""
        if getattr(self, "_rows", None) is None:
            width = int(self.sizes.max(initial=1))
            rows = np.full((self.n_docs, width), -1, dtype=np.int64)
            col = np.arange(len(self.gid)) - self.offsets[self.doc]
            rows[self.doc, col] = self.gid
            self._rows = rows
        return self._rows

    def similar_pairs(self, threshold: float, keys: np.ndarray | None = None
                      ) -> tuple[np.ndarray, np.ndarray]:
        """Every pair (a < b) with float32 Jaccard above ``threshold``.

        Prefix filtering: order grams by global frequency; any pair with
        Jaccard >= t shares a gram among the first
        ``|x| - ceil(t |x|) + 1`` grams of each set.  The pairs sharing
        a prefix gram are then verified exactly.  With ``keys`` (band
        keys, (D, b)) only pairs that share a band are kept: at r = 2 and
        b = 50 a pair above 0.75 misses every band with probability
        under 1e-18.
        """
        rank = np.lexsort((np.arange(len(self.freq)), self.freq))
        pos = np.empty_like(rank)
        pos[rank] = np.arange(len(rank))
        key = pos[self.gid]
        order = np.lexsort((key, self.doc))
        doc_s, gid_s = self.doc[order], self.gid[order]
        sizes = self.sizes
        plen = sizes - np.ceil(threshold * sizes - 1e-9).astype(np.int64) + 1
        plen = np.where(sizes > 0, np.minimum(plen, sizes), 0)
        within = np.arange(len(doc_s)) - self.offsets[doc_s]
        take = within < plen[doc_s]
        pd, pg = doc_s[take], gid_s[take]
        cand = unique_pairs(group_pairs(pg, pd), self.n_docs)
        if keys is not None and len(cand):
            cand = cand[np.any(keys[cand[:, 0]] == keys[cand[:, 1]], axis=1)]
        if not len(cand):
            return cand, np.zeros(0, dtype=np.float32)
        sims = self.jaccard(cand)
        keep = sims > np.float32(threshold)
        return cand[keep], sims[keep]
