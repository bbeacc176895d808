"""Edge inputs at the two benchmarked deployments' own widths.

``paper_exact`` (word 8-grams, M=100, r=2, exact Jaccard) and
``admission_bytes`` (word 5-grams, M=112, r=8, the m/M estimate over
raw bytes) are read from ``benchmarks/chip/configs/*.json``; typical
notes at these widths are covered end to end elsewhere, so every case
here is an edge: empty or punctuation-only notes, lengths at the n-gram
window and the 128-token tile, non-ASCII bytes, pair counts at the
power-of-two pair buckets, and similarities exactly at a threshold.
"""
import json
import os

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import DedupConfig, DedupPipeline, DedupSession
from repro.core import jaccard, lsh, minhash, shingle
from repro.core.candidates import BandMatrixSource
from repro.core.engine import ClusterAccumulator
from repro.core.verify import (
    CallbackVerifier, ExactJaccardVerifier, SignatureVerifier,
)
from repro.data import inject_near_duplicates, make_i2b2_like
from repro.kernels import ops

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip", "configs")
DEPLOYMENTS = ("paper_exact", "admission_bytes")


def _dedup(name: str) -> dict:
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)["dedup"]


def _config(name: str, **overrides) -> DedupConfig:
    return DedupConfig(**{**_dedup(name), **overrides})


def _widths(name: str) -> tuple[int, int, int]:
    d = _dedup(name)
    return d["ngram"], d["num_hashes"], d["rows_per_band"]


def test_config_files_hold_the_widths_these_tests_name():
    assert _widths("paper_exact") == (8, 100, 2)
    assert _widths("admission_bytes") == (5, 112, 8)


# -- fused_ingest against the numpy oracle chain ---------------------------

L = 256          # padded width: two 128-token tiles
D = 8


def _lengths(profile: str, n: int) -> np.ndarray:
    return np.array({
        "empty": [0] * D,
        "n_minus_1": [n - 1] * D,
        "exactly_n": [n] * D,
        "tile_edge": [127, 128, 129, 127, 128, 129, 0, n],
        "full_width": [L] * D,
        "one_token_repeated": [L, L - 1, n, n - 1, 129, 128, 1, 0],
    }[profile], dtype=np.int32)


@pytest.mark.parametrize("profile", ["empty", "n_minus_1", "exactly_n",
                                     "tile_edge", "full_width",
                                     "one_token_repeated"])
@pytest.mark.parametrize("deployment", DEPLOYMENTS)
def test_fused_ingest_matches_numpy_at_deployment_widths(deployment,
                                                         profile):
    n, m, r = _widths(deployment)
    rng = np.random.RandomState(len(profile) * 7 + n)
    tokens = rng.randint(0, 2**32, size=(D, L), dtype=np.uint64
                         ).astype(np.uint32)
    if profile == "one_token_repeated":
        tokens[:] = tokens[0, 0]
    lengths = _lengths(profile, n)
    seeds = minhash.default_seeds(m)
    sig, bands, valid = (np.asarray(x) for x in ops.fused_ingest(
        jnp.asarray(tokens), jnp.asarray(lengths), jnp.asarray(seeds),
        n=n, r=r))
    ng, want_valid = shingle.ngram_hashes_np(tokens, lengths, n=n)
    want_sig = minhash.signatures_np(ng, want_valid, seeds)
    assert sig.shape == (D, m) and bands.shape == (D, m // r, 2)
    np.testing.assert_array_equal(valid, want_valid)
    np.testing.assert_array_equal(sig, want_sig)
    np.testing.assert_array_equal(bands, lsh.band_values_np(want_sig, r))
    # An empty note has no shingle: every hash stays at its max.  A
    # note shorter than n is one shingle of all its tokens.
    empty = (sig == np.iinfo(np.uint32).max).all(axis=1)
    np.testing.assert_array_equal(empty, lengths == 0)
    if profile == "one_token_repeated":
        # One repeated token is one n-gram at any length from n on.
        long = np.flatnonzero(lengths >= n)
        assert (sig[long] == sig[long[0]]).all()


# -- bytes_to_bands at FineWeb's widths against the host tokenizer ---------

NOTE = "Patient seen today for follow-up of hypertension and diabetes."

BYTE_NOTES = {
    "empty": "",
    "whitespace_only": " \t\n  \r\n\t ",
    "punctuation_only": "... ,;:!? -- (( )) // ** ## @@ '' \"\"",
    "one_token": "hypertension",
    "four_tokens": "BP 120 over 80",
    "five_tokens": "BP 120 over 80 sitting",
    "non_ascii_between_words": "patienté café naïve "
                               "темп 38C 心 "
                               "ECG\U0001f691stat résumé ok",
    # Ends in a token at byte 2,047: the 2,048-byte bucket holds it with
    # the one trailing zero a token at the last byte needs.
    "bytes_2047": ("word " * 410)[:2046] + "z",
}


@pytest.mark.parametrize("kind", list(BYTE_NOTES))
def test_bytes_to_bands_matches_host_tokenize_at_fineweb_widths(kind):
    note = BYTE_NOTES[kind]
    docs = [note, NOTE]
    width = shingle.pow2_bucket(
        max(len(d.encode("utf-8")) for d in docs) + 1)
    if kind == "bytes_2047":
        assert len(note.encode("utf-8")) == 2047 and width == 2048
    byte_pipe = DedupPipeline(_config("admission_bytes"))
    sig_b, bands_b = byte_pipe.compute_arrays_bytes(docs, width)
    tok_pipe = DedupPipeline(_config("admission_bytes", byte_ingest=False))
    sig_t, bands_t = tok_pipe.compute_arrays(
        [shingle.tokenize(d, do_stem=False) for d in docs])
    np.testing.assert_array_equal(sig_b, sig_t)
    np.testing.assert_array_equal(bands_b, bands_t)
    n_tokens = len(shingle.tokenize(note, do_stem=False))
    # No token, no shingle: the all-max signature.  One to n-1 tokens
    # are one shingle.
    assert (sig_b[0] == np.iinfo(np.uint32).max).all() == (n_tokens == 0)


# -- the estimate verifier at M=112, at the pair-bucket edges --------------

def _agreement_ladder(m: int, rows: int, seed: int) -> np.ndarray:
    """Row 1 + k (k <= m) agrees with row 0 on exactly k of the m hashes,
    so the pairs (0, 1 + k) reach every estimate k/m."""
    rng = np.random.RandomState(seed)
    sig = rng.randint(0, 2**32, size=(rows, m), dtype=np.uint64
                      ).astype(np.uint32)
    for k in range(m + 1):
        sig[1 + k, :k] = sig[0, :k]
        sig[1 + k, k:] = ~sig[0, k:]
    return sig


def _bucket_pairs(p: int, rows: int, m: int, rng) -> np.ndarray:
    ladder = np.stack([np.zeros(m + 1, np.int64),
                       1 + np.arange(m + 1)], axis=1)
    # The first pair is the threshold itself (84 of 112 agree).
    ladder = np.concatenate([ladder[[m * 3 // 4]], ladder])
    rand = rng.randint(0, rows, size=(max(p - len(ladder), 0), 2))
    return np.concatenate([ladder, rand])[:p]


@pytest.mark.parametrize("p", [1, 255, 256, 257, 8192])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_estimate_verifier_every_count_at_pair_bucket_edges(backend, p):
    """A full fixed-capacity store at M=112: every agreement count 0..112
    comes back bit for bit as numpy's, at pair counts on each side of a
    power-of-two bucket and at the default batch of 8,192 pairs."""
    m = _widths("admission_bytes")[1]
    rows = 128
    sig = _agreement_ladder(m, rows, seed=p)
    v = SignatureVerifier(sig, backend=backend, capacity=rows)
    assert v._store.fixed and v._store.capacity == rows
    pairs = _bucket_pairs(p, rows, m, np.random.RandomState(p))
    got = v(pairs)
    want = SignatureVerifier(sig)(pairs)
    np.testing.assert_array_equal(got.view(np.uint32),
                                  want.view(np.uint32))
    assert got[0] == np.float32(0.75)
    assert v.n_batches == 1
    if p > m + 1:
        counts = np.rint(got[1:m + 2] * m).astype(int)
        np.testing.assert_array_equal(counts, np.arange(m + 1))


# -- the exact verifier on edge sets at the paper's n=8 --------------------

def _words(prefix: str, k: int) -> list[str]:
    return [f"{prefix}{i}" for i in range(k)]


EXACT_CASES = {
    # (first note's tokens, second note's tokens, Jaccard)
    "both_empty": ([], [], 1.0),
    "one_empty": (_words("a", 40), [], 0.0),
    "identical": (_words("b", 300), _words("b", 300), 1.0),
    "disjoint": (_words("c", 300), _words("d", 280), 0.0),
    # 30 shared 8-grams of 40 in the union.
    "three_quarters": (_words("e", 37) + _words("f", 10),
                       _words("e", 37), 0.75),
}


@pytest.mark.parametrize("case", list(EXACT_CASES))
def test_exact_verifier_edge_sets_across_an_extension(case):
    """The pair's two rows come from two chunks, the second appended by
    ``extend_token_lists`` beside a 600-token note, so the padded rows
    are far longer than either set: pads never match each other or a
    real n-gram."""
    a, b, want = EXACT_CASES[case]
    n = _widths("paper_exact")[0]
    v = ExactJaccardVerifier.from_token_lists([a, _words("x", 20)], n=n)
    v.extend_token_lists([_words("y", 600), b])
    got = v(np.array([[0, 3]]))
    assert got.dtype == np.float32
    assert got[0] == np.float32(want)
    assert want == jaccard.exact_jaccard(shingle.ngram_set(a, n),
                                         shingle.ngram_set(b, n))


# -- the strict thresholds at the engine -----------------------------------

def _sim(deployment: str, x: float) -> float:
    """The sim nearest ``x`` at or above it that the deployment's verifier
    can return: a float32 Jaccard, or an agreement count over M."""
    d = _dedup(deployment)
    if d["exact_verification"]:
        return float(np.float32(x))
    m = d["num_hashes"]
    return float(np.float32(np.ceil(x * m)) / np.float32(m))


def _colliding_bands(num_docs: int, num_bands: int,
                     collisions: list[tuple[int, int, int]]) -> np.ndarray:
    """Distinct band values for every doc, except each (band, a, b) in
    ``collisions``, where b takes a's value."""
    bands = np.zeros((num_docs, num_bands, 2), np.uint32)
    bands[:, :, 0] = np.arange(1, num_docs + 1)[:, None]
    for j, a, b in collisions:
        bands[b, j] = bands[a, j]
    return bands


def _feed(deployment: str, sim: float, num_docs: int, collisions):
    """One band source through an accumulator at the deployment's
    settings, every pair verified at ``sim``."""
    cfg = _config(deployment)
    acc = ClusterAccumulator(num_docs, CallbackVerifier(lambda a, b: sim),
                             cfg.edge_threshold, cfg.tree_threshold,
                             use_disjoint_sets=cfg.use_disjoint_sets,
                             batch=cfg.verify_batch)
    stats = acc.feed(BandMatrixSource(
        _colliding_bands(num_docs, cfg.num_bands, collisions)))
    return acc, stats


@pytest.mark.parametrize("above", [False, True], ids=["at", "next_above"])
@pytest.mark.parametrize("deployment", DEPLOYMENTS)
def test_edge_threshold_is_strict(deployment, above):
    """A sim of exactly 0.75 is no edge; the next sim the verifier can
    return above it (the next float32, or 85/112 against 84/112) is."""
    at = _sim(deployment, 0.75)
    assert at == 0.75
    sim = _sim(deployment, np.nextafter(np.float32(0.75), np.float32(1))
               ) if above else at
    assert (sim > 0.75) == above
    acc, st = _feed(deployment, sim, 3, [(0, 0, 1)])
    assert st.pairs_evaluated == 1 and acc.pairs[0][2] == sim
    assert st.pairs_above_edge == st.unions_done == int(above)
    assert (acc.uf.find(0) == acc.uf.find(1)) == above
    assert acc.uf.find(2) == 2


@pytest.mark.parametrize("deployment", DEPLOYMENTS)
def test_tree_bound_refuses_a_union(deployment):
    """{0,1} and {2,3} join; their roots then collide, and the leaf-to-
    leaf bound min_score + min_score + sim - 2 falls below the 0.40 tree
    threshold, so that union is refused.  The same chain at a higher sim
    goes through."""
    tree = _config(deployment).tree_threshold
    collisions = [(0, 0, 1), (0, 2, 3), (1, 1, 2)]
    for x, joined in ((0.77, False), (0.95, True)):
        sim = _sim(deployment, x)
        assert sim > 0.75 and (3 * sim - 2 >= tree) == joined
        acc, st = _feed(deployment, sim, 4, collisions)
        assert st.pairs_above_edge == 3
        assert st.unions_done == (3 if joined else 2)
        assert st.unions_rejected == (0 if joined else 1)
        labels = acc.uf.components()
        assert labels[0] == labels[1] and labels[2] == labels[3]
        assert (labels[1] == labels[2]) == joined


# -- chunk shapes through a session built from each config file ------------

def _session(deployment: str) -> DedupSession:
    # A small store: a capacity of 0 grows by doubling, bit for bit the
    # same sims as the deployment's fixed store.
    return DedupSession(_config(deployment, sig_store_capacity=0),
                        backend="host")


def _base_notes() -> list[str]:
    notes = make_i2b2_like(40, seed=0)
    return inject_near_duplicates(notes, 10, frac_high=0.05, seed=1)[0]


def _chunk(shape: str, base: list[str]) -> list[str]:
    if shape == "one_note":
        return [inject_near_duplicates([base[3]], 1, frac_high=0.05,
                                       seed=2)[0][1]]
    if shape == "repeats_retained":
        return [base[i] for i in (0, 3, 3, 17, 49)]
    if shape == "self_duplicates":
        fresh = make_i2b2_like(8, seed=9)
        return inject_near_duplicates(fresh, 6, frac_high=0.05,
                                      seed=10)[0]
    assert shape == "empty"
    return []


def _partition(labels) -> list[int]:
    """Labels as a partition: each doc's first co-member."""
    first: dict[int, int] = {}
    return [first.setdefault(int(x), i) for i, x in enumerate(labels)]


@pytest.mark.parametrize("shape", ["one_note", "repeats_retained",
                                   "self_duplicates", "empty"])
@pytest.mark.parametrize("deployment", DEPLOYMENTS)
def test_session_chunk_shapes_match_one_shot(deployment, shape):
    base = _base_notes()
    chunk = _chunk(shape, base)
    sess = _session(deployment)
    before = sess.ingest(base)
    snap = sess.ingest(chunk)
    ref = _session(deployment).ingest(base + chunk)
    assert snap.n_docs == ref.n_docs == len(base) + len(chunk)
    assert _partition(snap.labels) == _partition(ref.labels)
    sims = {(a, b): s for a, b, s in ref.pairs}
    shared = [(a, b, s) for a, b, s in snap.pairs if (a, b) in sims]
    assert shared
    assert all(np.float64(s) == np.float64(sims[a, b])
               for a, b, s in shared)
    if shape == "empty":
        # An empty chunk is no step: the snapshot is the one before it.
        assert sess.steps_ingested == 1
        assert snap.labels.tolist() == before.labels.tolist()
        assert snap.pairs == before.pairs
    else:
        assert sess.steps_ingested == 2
    if shape == "repeats_retained":
        # Each repeat joins its original at sim 1.0.
        labels = snap.labels
        for i, doc in enumerate((0, 3, 3, 17, 49)):
            assert labels[len(base) + i] == labels[doc]
    if shape == "self_duplicates":
        labels = snap.labels
        new = set(labels[len(base):].tolist())
        assert len(new) < len(chunk)
        assert not new & set(labels[:len(base)].tolist())
