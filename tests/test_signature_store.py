"""The device signature store of the jnp/pallas verify backends
(``verify.SignatureStore``): rows written in place, chunk by chunk and
into reused slots, bit-identical verify against the numpy backend, every
pair bucket compiled when a fixed-capacity store is made and nothing
after, and a copy to the device that does not grow with the retained
rows."""
import numpy as np
import pytest

import jax

from repro.core import DedupConfig, DedupSession, spans
from repro.core import verify
from repro.core.verify import SignatureStore, SignatureVerifier
from repro.data import inject_near_duplicates, make_i2b2_like
from repro.kernels import ops

M = 112
# The byte-ingest estimate session at FineWeb's MinHash settings.
BYTES = dict(ngram=5, num_hashes=M, rows_per_band=8,
             exact_verification=False, byte_ingest=True,
             verify_batch="band")


def _sig(n, seed):
    rng = np.random.RandomState(seed)
    # Few distinct values, so pairs agree on a spread of hash counts.
    return rng.randint(0, 4, size=(n, M)).astype(np.uint32)


def _pairs(n):
    return np.array([(a, b) for a in range(0, n, 3)
                     for b in range(a + 1, n, 5)], dtype=np.int64)


def _chunks(k, size, seed=3):
    """``k`` chunks of fresh notes and near-duplicates (0-5% of words
    changed) of notes of this chunk or an earlier one."""
    pool, out = [], []
    n_dup = size // 4
    for i in range(k):
        fresh = make_i2b2_like(size - n_dup, seed=seed + i)
        pool += fresh
        dups = inject_near_duplicates(pool, n_dup, frac_high=0.05,
                                      seed=seed + i)[0][len(pool):]
        out.append(fresh + dups)
    return out


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("capacity", [0, 64])
def test_appends_across_chunks_match_numpy(backend, capacity):
    sig = _sig(40, 1)
    full = SignatureVerifier(sig)
    v = SignatureVerifier(sig[:10], backend=backend, capacity=capacity)
    v.extend_signatures(sig[10:25])
    # Rows handed over on the device are written without a host copy.
    v.extend_signatures(sig[25:], device_rows=jax.device_put(sig[25:]))
    store = v._store
    assert store.fixed == (capacity > 0)
    assert store.capacity == (capacity or 50)      # 10, 25, then 50
    buf = np.asarray(store.buf)
    # Rows padded to one 128-lane tile, zero past the rows written.
    assert buf.shape[1] == 128
    np.testing.assert_array_equal(buf[:40, :M], sig)
    assert not buf[40:].any() and not buf[:, M:].any()
    pairs = _pairs(40)
    got = v(pairs)
    np.testing.assert_array_equal(got.view(np.uint32),
                                  full(pairs).view(np.uint32))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_fixed_store_compiles_every_pair_bucket_when_made(backend):
    fn = (verify._gather_counts_jit if backend == "jnp"
          else ops.indexed_pair_counts)
    n0 = fn._cache_size()
    # A capacity no other test uses, so every shape here is new.
    v = SignatureVerifier(_sig(30, 11), backend=backend, batch_pairs=1024,
                          capacity=72)
    assert fn._cache_size() == n0 + 3          # buckets 256, 512, 1024
    ref = SignatureVerifier(_sig(30, 11))
    rng = np.random.RandomState(12)
    for p in (1, 256, 300, 700, 1024, 2500):
        pairs = rng.randint(0, 30, size=(p, 2))
        np.testing.assert_array_equal(v(pairs).view(np.uint32),
                                      ref(pairs).view(np.uint32))
    assert fn._cache_size() == n0 + 3


def test_fixed_capacity_refuses_rows_past_its_end():
    v = SignatureVerifier(_sig(8, 2), backend="jnp", capacity=10)
    with pytest.raises(ValueError, match="sig_store_capacity"):
        v.extend_signatures(_sig(3, 3))


def test_growth_doubles_on_the_device():
    store = SignatureStore(M)
    store.write(_sig(5, 4), start=0)
    assert store.capacity == 5
    store.write(_sig(2, 5), start=5)
    assert store.capacity == 10
    store.write(_sig(30, 6), start=7)
    assert store.capacity == 37
    buf = np.asarray(store.buf)
    np.testing.assert_array_equal(buf[:5, :M], _sig(5, 4))
    np.testing.assert_array_equal(buf[5:7, :M], _sig(2, 5))
    np.testing.assert_array_equal(buf[7:, :M], _sig(30, 6))


@pytest.mark.parametrize("capacity", [0, 32])
def test_slot_reuse_lands_in_the_device_store(capacity):
    sig = _sig(14, 7)
    ref = SignatureVerifier(sig)
    v = SignatureVerifier(sig[:10].copy(), backend="pallas",
                          capacity=capacity)
    v.release_rows([2, 5, 7])
    v.extend_signatures(sig[10:])         # docs 10-12 reuse slots, 13 appends
    buf = np.asarray(v._store.buf)
    for doc, slot in v._slot_of.items():
        np.testing.assert_array_equal(buf[slot, :M], sig[doc])
    assert not buf[:, M:].any()
    live = [d for d in range(14) if d not in (2, 5, 7)]
    pairs = np.array([(a, b) for a in live for b in live if a < b])
    np.testing.assert_array_equal(v(pairs), ref(pairs))


def test_adopted_view_reads_the_owners_store():
    sig = _sig(20, 8)
    owner = SignatureVerifier(sig[:12], backend="jnp", capacity=32)
    view = SignatureVerifier(np.zeros((0, M), np.uint32), backend="jnp")
    view.adopt_layout(owner)
    assert view._store is owner._store
    owner.extend_signatures(sig[12:])
    view.adopt_layout(owner)
    pairs = _pairs(20)
    np.testing.assert_array_equal(view(pairs), SignatureVerifier(sig)(pairs))
    with pytest.raises(ValueError):
        SignatureVerifier(sig, backend="numpy").adopt_layout(owner)


@pytest.fixture(scope="module")
def byte_sessions():
    """Six equal chunks through a byte-ingest session with the pallas
    verifier over a fixed store and through one with the numpy
    verifier, under the profiler; what compiled in each chunk of the
    first session and its kept spans."""
    import tempfile

    chunks = _chunks(6, 48)
    cfg = DedupConfig(verify_backend="pallas", sig_store_capacity=512,
                      **BYTES)
    dev, host = DedupSession(cfg), DedupSession(
        DedupConfig(verify_backend="numpy", **BYTES))
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    per_chunk, snaps, cache = [], [], []
    spans.take()
    with tempfile.TemporaryDirectory() as out:
        jax.profiler.start_trace(out)
        try:
            for ch in chunks:
                n0 = len(compiles)
                snaps.append((dev.ingest(ch), host.ingest(ch)))
                per_chunk.append(len(compiles) - n0)
                cache.append(ops.indexed_pair_counts._cache_size())
        finally:
            jax.profiler.stop_trace()
    kept = [s for s in spans.take() if s[0] == "dedup.sig_store"]
    return dev, snaps, per_chunk, cache, kept


def test_byte_session_matches_numpy_bit_for_bit(byte_sessions):
    _, snaps, _, _, _ = byte_sessions
    for d, h in snaps:
        np.testing.assert_array_equal(d.labels, h.labels)
        np.testing.assert_array_equal(d.pairs.ab, h.pairs.ab)
        np.testing.assert_array_equal(d.pairs.sim, h.pairs.sim)
    assert len(snaps[-1][0].pairs), "the chunks must give verified pairs"


def test_nothing_compiles_after_the_first_chunk(byte_sessions):
    dev, snaps, per_chunk, cache, _ = byte_sessions
    assert snaps[0][0].stats.pairs_evaluated > 0
    assert per_chunk[1:] == [0] * 5, per_chunk
    # One store shape, and the pair buckets compiled when the store was
    # made: the verify program's jit cache never grows after that.
    assert cache[1:] == cache[:1] * 5
    assert dev.verifier._store.capacity == 512


def test_store_copies_no_rows_from_the_host(byte_sessions):
    _, _, _, _, kept = byte_sessions
    assert len(kept) == 6
    # Rows come from the byte ingest's device output: only the int32
    # row offset crosses, the same for every chunk.
    assert [s[4]["h2d_bytes"] for s in kept] == [4] * 6


def test_host_rows_copy_only_the_chunk():
    sig = _sig(60, 9)
    v = SignatureVerifier(sig[:10], backend="jnp", capacity=64)
    spans.take()
    import tempfile

    with tempfile.TemporaryDirectory() as out:
        jax.profiler.start_trace(out)
        try:
            for s in range(10, 60, 10):
                v.extend_signatures(sig[s:s + 10])
        finally:
            jax.profiler.stop_trace()
    got = [s[4]["h2d_bytes"] for s in spans.take()
           if s[0] == "dedup.sig_store"]
    assert got == [10 * M * 4 + 4] * 5
