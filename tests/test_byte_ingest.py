"""Device byte-shingle chain vs the host tokenize path, bit for bit.

The zero-copy ingest contract (DESIGN.md §11): for no-stem
tokenization, ``bytes_to_bands`` over packed UTF-8 bytes is
bit-identical (``array_equal``, never allclose) to host
``tokenize(do_stem=False)`` + ``token_ids`` + ``pack_documents`` +
``fused_ingest`` — which is what lets ``byte_ingest=True`` drop into
any session backend and the serving read path with zero drift.

Deterministic cases live here (tier-1 everywhere); the randomized text
sweep at the bottom gates on hypothesis like the other kernel sweeps.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import shingle
from repro.kernels import ops

# Mixed corpus: ASCII clinical-ish text, case folding, digits,
# multi-byte UTF-8 (2/3/4-byte sequences), empties, punctuation runs.
CORPUS = [
    "CHIEF COMPLAINT : fever . Vitals BP 120/80 , HR 92 .",
    "patient denies chest pain; möglich über café naïve",
    "температура 38.5 градусов — прием 2x daily",
    "心电图 normal ECG 🚑 stat",
    "",
    "...",
    "a",
    "A" * 40 + " " + "b2" * 30,
    "x" * 300,
]


def _host_arrays(texts, seeds, n, r, pad_len=None):
    toks = [shingle.tokenize(t, do_stem=False) for t in texts]
    width = pad_len or shingle.pow2_bucket(
        max((len(t) for t in toks), default=1))
    packed = shingle.pack_documents(toks, width)
    sig, bands, _ = ops.fused_ingest(
        jnp.asarray(packed.tokens), jnp.asarray(packed.lengths),
        jnp.asarray(seeds), n=n, r=r)
    return np.asarray(sig), np.asarray(bands)


def _byte_arrays(texts, seeds, n, r, **tiles):
    blen = shingle.pow2_bucket(
        max((len(t.encode("utf-8")) for t in texts), default=0) + 1)
    packed = shingle.pack_bytes(texts, blen)
    sig, bands, _ = ops.bytes_to_bands(
        jnp.asarray(packed.data), jnp.asarray(packed.lengths),
        jnp.asarray(seeds), n=n, r=r, **tiles)
    return np.asarray(sig), np.asarray(bands)


def _seeds(m, seed=3):
    rng = np.random.RandomState(seed)
    return rng.randint(0, 2**32, size=(m,), dtype=np.uint64
                       ).astype(np.uint32)


# -- byte tokenizer oracle vs the host tokenizer -----------------------------

def test_byte_oracle_matches_host_tokenizer():
    """`byte_token_ids_np` == token_ids(tokenize(do_stem=False)):
    byte-level boundaries reproduce the host no-stem tokenizer exactly,
    including multi-byte UTF-8 (every byte >= 0x80 is a separator, so
    boundary detection can never split inside a sequence)."""
    for text in CORPUS:
        want = shingle.token_ids(shingle.tokenize(text, do_stem=False))
        got = shingle.byte_token_ids_np(text)
        assert np.array_equal(got, want), text


def test_byte_kernel_matches_numpy_oracle():
    """Kernel (tok, ends) matrices == `byte_token_hashes_np`, including
    garbage padding beyond each row's byte length."""
    rng = np.random.RandomState(7)
    D, LB = 6, 96
    data = rng.randint(0, 256, size=(D, LB)).astype(np.uint8)
    lengths = np.array([0, 1, 40, 95, 95, 17], dtype=np.int32)
    # Garbage beyond `lengths` must be masked by the position check.
    tok_np, ends_np = shingle.byte_token_hashes_np(data, lengths)
    tok_k, ends_k = ops.byte_token_hashes(
        jnp.asarray(data), jnp.asarray(lengths))
    assert np.array_equal(np.asarray(tok_k), tok_np)
    assert np.array_equal(np.asarray(ends_k), ends_np)


def test_byte_kernel_tile_boundaries():
    """Tokens straddling the L-tile edge exercise the FNV/prev carries:
    byte lengths pinned around tlb=128 bit-match the numpy oracle."""
    texts = ["ab " * 43 + "tail",            # 133 bytes, token at edge
             "c" * 126, "d" * 127, "e" * 128, "f" * 129,
             "g" * 127 + " h"]
    blen = 256
    packed = shingle.pack_bytes(texts, blen)
    tok_np, ends_np = shingle.byte_token_hashes_np(
        packed.data, packed.lengths)
    tok_k, ends_k = ops.byte_token_hashes(
        jnp.asarray(packed.data), jnp.asarray(packed.lengths),
        td=2, tlb=128)
    assert np.array_equal(np.asarray(tok_k), tok_np)
    assert np.array_equal(np.asarray(ends_k), ends_np)


def test_byte_kernel_tile_size_invariance():
    """Tiling is an implementation detail: every (td, tlb) choice
    yields the same bits (carries persist across L revisits)."""
    packed = shingle.pack_bytes(CORPUS, 512)
    dj, lj = jnp.asarray(packed.data), jnp.asarray(packed.lengths)
    outs = [tuple(np.asarray(x) for x in
                  ops.byte_token_hashes(dj, lj, td=td, tlb=tlb))
            for td, tlb in [(8, 256), (1, 512), (9, 64), (3, 101)]]
    for got in outs[1:]:
        for g, w in zip(got, outs[0]):
            assert np.array_equal(g, w)


# -- token compaction: log-step shifts against a plain numpy compaction -------

def _compact_np(tok, ends, width):
    """Each row's ids at its end positions, in order, zero-filled."""
    out = np.zeros((tok.shape[0], width), np.uint32)
    for d in range(tok.shape[0]):
        ids = tok[d][ends[d] > 0]
        out[d, :len(ids)] = ids
    return out


def _ends_rows(kinds, L, rng):
    """(D, L) end flags, one row per kind: ``empty``, ``dense`` (an end
    every second position from 1: the most a row can hold),
    ``last_column`` (one end at L - 1), ``random`` (ends at least two
    apart, as the tokenizer emits them)."""
    ends = np.zeros((len(kinds), L), np.int32)
    for d, kind in enumerate(kinds):
        if kind == "dense":
            ends[d, 1::2] = 1
        elif kind == "last_column":
            ends[d, L - 1] = 1
        elif kind == "random":
            last = -2
            for p in np.flatnonzero(rng.rand(L) < rng.rand()):
                if p >= 1 and p - last >= 2:
                    ends[d, p], last = 1, p
    return ends


ALL_ROWS = ("empty", "dense", "last_column", "random", "random")


def _compaction_case(case):
    """(tok, ends) as (D, L) arrays for one named case, L = LB + 1."""
    rng = np.random.RandomState(len(case))
    if case == "clinical_notes":
        from repro.data import inject_near_duplicates, make_i2b2_like

        notes, _ = inject_near_duplicates(make_i2b2_like(12, seed=3), 4,
                                          seed=4)
        width = shingle.pow2_bucket(
            max(len(t.encode("utf-8")) for t in notes) + 1)
        packed = shingle.pack_bytes(notes, width)
        data = np.pad(packed.data, ((0, 0), (0, 1)))
        return shingle.byte_token_hashes_np(data, packed.lengths)
    L, kinds = {
        "width_2": (2, ALL_ROWS),
        "width_33": (33, ALL_ROWS),
        "width_257": (257, ALL_ROWS),
        "width_2049": (2049, ALL_ROWS),
        "empty_rows": (65, ("empty",) * 3),
        "full_density": (65, ("dense",) * 3),
        "end_at_last_column": (65, ("last_column", "dense", "random")),
        "one_document": (129, ("random",)),
        "docs_not_multiple_of_128": (129, ALL_ROWS * 26),
    }[case]
    ends = _ends_rows(kinds, L, rng)
    # Ids everywhere: the compaction must keep only those at the ends.
    tok = rng.randint(0, 2**32, size=ends.shape, dtype=np.uint64
                      ).astype(np.uint32)
    return tok, ends


@pytest.mark.parametrize("case", [
    "width_2", "width_33", "width_257", "width_2049", "empty_rows",
    "full_density", "end_at_last_column", "one_document",
    "docs_not_multiple_of_128", "clinical_notes"])
def test_compact_token_ends_matches_numpy(case):
    """``compact_token_ends`` == the plain compaction, bit for bit, at
    ``bytes_to_bands``' width (LB + 1) // 2 + 1 for L = LB + 1."""
    from repro.kernels.byte_shingle import compact_token_ends

    tok, ends = _compaction_case(case)
    D, L = tok.shape
    width = L // 2 + 1
    got = np.asarray(compact_token_ends(
        jnp.asarray(tok), jnp.asarray(ends), width))
    np.testing.assert_array_equal(got, _compact_np(tok, ends, width))
    if case == "full_density":
        # A token every second byte fills all but the last column.
        assert (ends.sum(axis=1) == width - 1).all()
    if case == "docs_not_multiple_of_128":
        assert D % 128 != 0


def test_compact_token_ends_has_no_scatter_or_sort():
    """At the bulk cell's shape (8,192 notes of 2,048 bytes: 2,049
    positions, 1,025 token columns) the compaction lowers to neither a
    scatter nor a sort."""
    import jax
    from repro.kernels.byte_shingle import compact_token_ends

    text = jax.jit(compact_token_ends, static_argnums=2).lower(
        jax.ShapeDtypeStruct((8192, 2049), jnp.uint32),
        jax.ShapeDtypeStruct((8192, 2049), jnp.int32),
        1025).as_text()
    assert "stablehlo.scatter" not in text
    assert "stablehlo.sort" not in text
    assert "stablehlo.gather" not in text


# -- the fused bytes->bands chain --------------------------------------------

def test_bytes_to_bands_matches_host_chain():
    seeds = _seeds(20)
    sig_h, bands_h = _host_arrays(CORPUS, seeds, n=8, r=2)
    sig_b, bands_b = _byte_arrays(CORPUS, seeds, n=8, r=2)
    assert np.array_equal(sig_b, sig_h)
    assert np.array_equal(bands_b, bands_h)


def test_bytes_to_bands_short_docs_and_odd_bands():
    """Docs shorter than the shingle window (L < n) and a non-default
    (n, r) still bit-match the host chain."""
    texts = ["one two", "a b c", "", "solo", "🚑 🚑"]
    seeds = _seeds(15, seed=5)
    sig_h, bands_h = _host_arrays(texts, seeds, n=3, r=3)
    sig_b, bands_b = _byte_arrays(texts, seeds, n=3, r=3)
    assert np.array_equal(sig_b, sig_h)
    assert np.array_equal(bands_b, bands_h)


def test_bytes_to_bands_zero_docs():
    seeds = _seeds(10)
    sig, bands, toklen = ops.bytes_to_bands(
        jnp.zeros((0, 16), jnp.uint8), jnp.zeros((0,), jnp.int32),
        jnp.asarray(seeds), n=8, r=2)
    assert sig.shape == (0, 10) and bands.shape == (0, 5, 2)
    assert toklen.shape == (0,)


def test_pack_bytes_width_validation():
    """The matrix must be strictly wider than every byte length (the
    final-token emission column)."""
    with pytest.raises(ValueError):
        shingle.pack_bytes(["abcdef"], 6)
    packed = shingle.pack_bytes(["abcdef"], 7)
    assert packed.data.shape == (1, 7)
    assert packed.lengths.tolist() == [6]


# -- config / pipeline / session wiring --------------------------------------

def test_config_rejects_exact_verification():
    from repro.core.pipeline import DedupConfig

    with pytest.raises(ValueError):
        DedupConfig(byte_ingest=True, exact_verification=True)


def test_pipeline_byte_parity():
    from repro.core.pipeline import DedupConfig, DedupPipeline
    from repro.data import inject_near_duplicates, make_i2b2_like

    notes = make_i2b2_like(18, seed=0)
    notes, _ = inject_near_duplicates(notes, 5, frac_low=0.0,
                                      frac_high=0.005, seed=1)
    tok = DedupPipeline(DedupConfig(
        fused_ingest=True, exact_verification=False))
    byt = DedupPipeline(DedupConfig(
        byte_ingest=True, exact_verification=False))
    byt.seeds = tok.seeds
    toks = [shingle.tokenize(t, do_stem=False) for t in notes]
    tok_pad = shingle.pow2_bucket(max(len(t) for t in toks))
    sig_t, bands_t = tok.compute_arrays(toks, tok_pad)
    pad = shingle.pow2_bucket(
        max(len(t.encode("utf-8")) for t in notes) + 1)
    sig_b, bands_b = byt.compute_arrays_bytes(notes, pad)
    assert np.array_equal(sig_b, sig_t)
    assert np.array_equal(bands_b, bands_t)
    assert byt.stage_timings["signature_s"] > 0
    assert byt.stage_timings["bands_s"] == 0.0


@pytest.mark.parametrize("backend", ["host", "streaming"])
def test_session_byte_parity(backend):
    """Host/streaming byte sessions produce the same labels and pair
    sims as token sessions fed no-stem token lists."""
    from repro.core.pipeline import DedupConfig
    from repro.core.session import DedupSession
    from repro.data import inject_near_duplicates, make_i2b2_like

    notes = make_i2b2_like(40, seed=0)
    notes, _ = inject_near_duplicates(notes, 8, frac_low=0.0,
                                      frac_high=0.005, seed=1)
    kw = dict(exact_verification=False, edge_threshold=0.88)
    tok_sess = DedupSession(DedupConfig(**kw), backend=backend)
    byt_sess = DedupSession(DedupConfig(byte_ingest=True, **kw),
                            backend=backend)
    for lo in range(0, len(notes), 16):
        chunk = notes[lo:lo + 16]
        snap_t = tok_sess.ingest_tokens(
            [shingle.tokenize(t, do_stem=False) for t in chunk])
        snap_b = byt_sess.ingest(chunk)
    assert snap_b.labels.tolist() == snap_t.labels.tolist()
    assert snap_b.pairs == snap_t.pairs
    _, counts = np.unique(snap_b.labels, return_counts=True)
    assert (counts >= 2).sum() > 0  # the injected dups actually merged


def test_query_service_bytes():
    """`query_bytes` answers straight from UTF-8 against a byte
    session, bit-consistent with the microbatched token route."""
    from repro.core.pipeline import DedupConfig
    from repro.core.session import DedupSession
    from repro.data import make_i2b2_like
    from repro.serving.dedup_service import DedupQueryService

    notes = list(make_i2b2_like(30, seed=2))
    sess = DedupSession(DedupConfig(
        byte_ingest=True, exact_verification=False))
    sess.ingest(notes)
    svc = DedupQueryService(sess)
    dup = svc.query(notes[:6])
    assert all(r.is_duplicate and r.best_sim == 1.0 for r in dup)
    novel = svc.query(["entirely novel prose about nothing clinical"])
    assert not novel[0].is_duplicate
    # Microbatched submit/step path agrees bit for bit.
    for t in notes[:6]:
        svc.submit(t)
    svc.run_until_drained()
    assert svc.stats.duplicates_found >= 12


def test_probe_candidates_device_parity():
    """The device searchsorted band probe returns exactly what the
    host dict walk returns (candidates AND bloom filter hits)."""
    from repro.core.pipeline import DedupConfig, DedupPipeline
    from repro.core.query import _device_probe_index, probe_candidates
    from repro.core.session import DedupSession
    from repro.data import make_i2b2_like

    notes = list(make_i2b2_like(48, seed=4))
    sess = DedupSession(DedupConfig(
        byte_ingest=True, exact_verification=False))
    sess.ingest(notes)
    view = sess.view()
    # Query bands: half ingested docs (hits), half novel (misses).
    queries = notes[:24] + [f"novel text {i} zzz" for i in range(24)]
    pipe = DedupPipeline(sess.config)
    pipe.seeds = sess.seeds
    blen = shingle.pow2_bucket(
        max(len(t.encode("utf-8")) for t in queries) + 1)
    _, bands = pipe.compute_arrays_bytes(queries, blen)
    walk = probe_candidates(view, bands, device_min_batch=10**9)
    dev = probe_candidates(view, bands, device_min_batch=8)
    assert _device_probe_index(view) is not None  # index built+cached
    for got, want in zip(dev[0], walk[0]):
        assert np.array_equal(got, want)
    assert dev[1] == walk[1]
    assert any(len(c) for c in dev[0])  # probe actually hit something


# -- randomized sweep (hypothesis-gated, like the kernel sweeps) -------------

def test_byte_oracle_hypothesis_sweep():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.text(max_size=200))
    def check(text):
        want = shingle.token_ids(shingle.tokenize(text, do_stem=False))
        got = shingle.byte_token_ids_np(text)
        assert np.array_equal(got, want)

    check()
