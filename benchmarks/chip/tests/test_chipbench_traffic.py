"""The benchmark's traffic generator against the repository's corpus
generator: the same notes by distribution, many times faster.

The comparison is the point of this test, so it imports ``repro.data``;
the benchmark's own modules never do.
"""
from __future__ import annotations

import itertools
import os
import re
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

from traffic import clinical_notes as cn  # noqa: E402

WORD = re.compile(r"[A-Za-z0-9]+")


def _grams(text: str, n: int = 8) -> set:
    t = [w.lower() for w in WORD.findall(text)]
    return {tuple(t[i:i + n]) for i in range(len(t) - n + 1)}


def _jaccards(notes: list[str]) -> np.ndarray:
    sets = [_grams(t) for t in notes]
    return np.array([len(a & b) / len(a | b)
                     for a, b in itertools.combinations(sets, 2)])


def _corpus_theirs(n: int, seed: int) -> list[str]:
    from repro.data import inject_near_duplicates, make_i2b2_like

    notes, _ = inject_near_duplicates(make_i2b2_like(n - n // 8, seed=seed),
                                      n // 8, seed=seed + 1)
    return notes


def _corpus_ours(n: int, seed: int) -> list[str]:
    return cn.corpus_chunk([], n, 0.125, 0.0, 0.2, cn.rng_for(seed, "t"))


def test_notes_match_repository_generator_in_shape():
    theirs, ours = _corpus_theirs(1600, 5), _corpus_ours(1600, 5)
    for measure in (lambda t: len(WORD.findall(t)), len):
        a = np.array([measure(t) for t in theirs], dtype=float)
        b = np.array([measure(t) for t in ours], dtype=float)
        assert abs(a.mean() - b.mean()) < 0.01 * a.mean()
        assert abs(a.std() - b.std()) < 0.15 * a.std() + 1.0


def test_pairwise_jaccard_distribution_matches():
    ja = _jaccards(_corpus_theirs(240, 11))
    jb = _jaccards(_corpus_ours(240, 11))
    assert abs(ja.mean() - jb.mean()) < 0.003
    for q in (0.5, 0.9, 0.99):
        assert abs(np.quantile(ja, q) - np.quantile(jb, q)) < 0.01
    # Near-duplicate pairs: the same share above a high similarity.
    for cut in (0.3, 0.75):
        assert abs((ja > cut).mean() - (jb > cut).mean()) < 0.002


def test_copy_is_more_than_ten_times_faster():
    from repro.data import make_i2b2_like

    t0 = time.perf_counter()
    make_i2b2_like(1000, seed=1)
    theirs = time.perf_counter() - t0
    t0 = time.perf_counter()
    cn.make_notes(1000, cn.rng_for(1, "t"))
    ours = time.perf_counter() - t0
    assert ours * 10 < theirs, (ours, theirs)


def test_same_seed_same_traffic_and_stratified_work():
    a = cn.corpus_chunk([], 256, 0.125, 0.0, 0.2, cn.rng_for(2**31 + 7, "c"))
    b = cn.corpus_chunk([], 256, 0.125, 0.0, 0.2, cn.rng_for(2**31 + 7, "c"))
    c = cn.corpus_chunk([], 256, 0.125, 0.0, 0.2, cn.rng_for(-3, "c"))
    assert a == b and a != c
    f1 = cn.stratified(64, 0.0, 0.2, cn.rng_for(1, "f"))
    f2 = cn.stratified(64, 0.0, 0.2, cn.rng_for(2, "f"))
    assert np.array_equal(np.sort(f1), np.sort(f2))
    assert not np.array_equal(f1, f2)
    assert 0.0 < f1.min() and f1.max() < 0.2
