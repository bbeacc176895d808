"""The plain reference against the system at small sizes on the CPU, and
the checks built on it catching a corrupted band value, sim or label."""
from __future__ import annotations

import itertools
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

import compare  # noqa: E402
import reference as ref  # noqa: E402
from traffic import clinical_notes as cn  # noqa: E402

RCFG = {"stem": True, "seed_key": 0x5EED, "ngram": 8, "num_hashes": 100,
        "rows_per_band": 2, "edge_threshold": 0.75, "tree_threshold": 0.4}


def _notes(n: int, seed: int, frac_high: float = 0.02) -> list[str]:
    pool: list[str] = []
    rng = cn.rng_for(seed, "ref")
    return cn.corpus_chunk(pool, n, 0.25, 0.0, frac_high, rng)


def _program_bands(sess, n):
    out = np.zeros((n, 50, 2), dtype=np.uint32)
    for j, buckets in enumerate(sess.view().band_maps):
        for key, docs in buckets.items():
            out[list(docs), j] = key
    return out


@pytest.fixture(scope="module")
def exact_run():
    from repro.core import DedupConfig, DedupSession

    texts = _notes(96, 1) + _notes(32, 2)
    sess = DedupSession(DedupConfig(verify_batch="band"), backend="host")
    sess.ingest(texts[:96])
    snap = sess.ingest(texts[96:])
    return texts, _program_bands(sess, len(texts)), \
        np.asarray(snap.labels), snap.pairs


def test_signatures_and_bands_match_both_tokenizers():
    from repro.core import DedupConfig, DedupPipeline

    texts = _notes(24, 3) + ["", "one two", "Ünïcode wörds and 42 numbers"]
    for stem, cfg in ((True, DedupConfig()),
                      (False, DedupConfig(byte_ingest=True,
                                          exact_verification=False))):
        pipe = DedupPipeline(cfg)
        if stem:
            sig, bands = pipe.compute_arrays(pipe.tokenize(texts))
        else:
            sig, bands = pipe.compute_arrays_bytes(texts)
        tok = ref.Tokens(texts, do_stem=stem)
        want = ref.signatures(tok, 8, ref.minhash_seeds(100, 0x5EED))
        assert np.array_equal(np.asarray(sig), want)
        assert np.array_equal(np.asarray(bands), ref.band_values(want, 2))


def test_exact_session_agrees_with_reference(exact_run):
    texts, bands, labels, pairs = exact_run
    checks = compare.batch_checks(texts, RCFG, bands, labels, pairs,
                                  first_checked=96, sample=10_000,
                                  rng=np.random.default_rng(0))
    assert checks["_sims_checked"] > 40 and checks["_edges"] > 0
    assert checks["band_rows_wrong"] == checks["sims_wrong"] == \
        checks["label_faults"] == 0


@pytest.mark.parametrize("fault", ["band", "sim", "merge", "split"])
def test_corrupted_output_is_caught(exact_run, fault):
    texts, bands, labels, pairs = exact_run
    bands, labels, pairs = bands.copy(), labels.copy(), list(pairs)
    if fault == "band":
        bands[100, 7, 1] ^= np.uint32(1)
    elif fault == "sim":
        a, b, s = next(p for p in pairs if p[1] >= 96)
        pairs[pairs.index((a, b, s))] = (a, b, float(np.nextafter(
            np.float32(s), np.float32(2))))
    elif fault == "merge":
        size = np.bincount(labels, minlength=len(labels))
        single = np.flatnonzero(size[labels] == 1)
        labels[single[-1]] = labels[single[0]]
    else:
        size = np.bincount(labels, minlength=len(labels))
        pair_root = np.flatnonzero(size == 2)[0]
        members = np.flatnonzero(labels == pair_root)
        labels[members[1]] = members[1]
    checks = compare.batch_checks(texts, RCFG, bands, labels, pairs,
                                  first_checked=96, sample=10_000,
                                  rng=np.random.default_rng(0))
    key = {"band": "band_rows_wrong", "sim": "sims_wrong"}.get(
        fault, "label_faults")
    assert checks[key] >= 1


def test_similar_pairs_and_band_pairs_equal_brute_force():
    texts = _notes(80, 4, frac_high=0.05)
    tok = ref.Tokens(texts, do_stem=True)
    grams = ref.GramSets(tok, 8)
    allp = np.array(list(itertools.combinations(range(len(texts)), 2)))
    sims = grams.jaccard(allp)
    got, got_s = grams.similar_pairs(0.75)
    assert np.array_equal(got, allp[sims > np.float32(0.75)])
    assert np.array_equal(got_s, sims[sims > np.float32(0.75)])
    sets = [set(zip(*[t[i:] for t in [ref.tokenize(x, True)]
                      for i in range(8)])) for x in texts[:20]]
    for a, b in itertools.combinations(range(20), 2):
        j = len(sets[a] & sets[b]) / len(sets[a] | sets[b])
        assert grams.jaccard(np.array([[a, b]]))[0] == np.float32(j)
    keys = ref.band_keys(ref.band_values(ref.signatures(
        tok, 8, ref.minhash_seeds(100, 0x5EED)), 2))
    shared = np.any(keys[allp[:, 0]] == keys[allp[:, 1]], axis=1)
    above = sims > np.float32(0.75)
    got, _ = grams.similar_pairs(0.75, keys=keys)
    assert np.array_equal(got, allp[shared & above])
