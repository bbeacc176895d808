"""Pallas kernels vs pure-jnp refs: shape/dtype sweeps (hypothesis)."""
import numpy as np
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


@given(st.integers(1, 40), st.integers(8, 300), st.integers(2, 8))
@settings(max_examples=12, deadline=None)
def test_ngram_kernel_sweep(d, l, n):
    rng = np.random.RandomState(d * 1000 + l)
    tokens = rng.randint(0, 2**32, size=(d, l), dtype=np.uint64
                         ).astype(np.uint32)
    lengths = rng.randint(0, l + 1, size=(d,)).astype(np.int32)
    hk, vk = ops.ngram_hashes(jnp.asarray(tokens), jnp.asarray(lengths),
                              n=n)
    hr, vr = ref.ngram_hashes(jnp.asarray(tokens), jnp.asarray(lengths),
                              n=n)
    assert np.array_equal(np.asarray(vk), np.asarray(vr))
    m = np.asarray(vk)
    assert np.array_equal(np.asarray(hk)[m], np.asarray(hr)[m])


@given(st.integers(1, 30), st.integers(4, 200), st.integers(1, 130))
@settings(max_examples=12, deadline=None)
def test_minhash_kernel_sweep(d, l, m):
    rng = np.random.RandomState(d + l + m)
    ng = rng.randint(0, 2**32, size=(d, l), dtype=np.uint64
                     ).astype(np.uint32)
    valid = rng.rand(d, l) < 0.8
    seeds = rng.randint(0, 2**32, size=(m,), dtype=np.uint64
                        ).astype(np.uint32)
    got = ops.minhash_signatures(jnp.asarray(ng), jnp.asarray(valid),
                                 jnp.asarray(seeds))
    want = ref.minhash_signatures(jnp.asarray(ng), jnp.asarray(valid),
                                  jnp.asarray(seeds))
    assert np.array_equal(np.asarray(got), np.asarray(want))


@given(st.integers(1, 50), st.integers(1, 8), st.integers(1, 30))
@settings(max_examples=12, deadline=None)
def test_bandfold_kernel_sweep(d, r, b):
    rng = np.random.RandomState(d * 7 + r)
    sig = rng.randint(0, 2**32, size=(d, r * b), dtype=np.uint64
                      ).astype(np.uint32)
    got = ops.band_values(jnp.asarray(sig), r)
    want = ref.band_values(jnp.asarray(sig), r)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@given(st.integers(1, 300), st.integers(1, 128))
@settings(max_examples=12, deadline=None)
def test_sigjaccard_kernel_sweep(p, m):
    rng = np.random.RandomState(p + m)
    a = rng.randint(0, 4, size=(p, m)).astype(np.uint32)
    b = rng.randint(0, 4, size=(p, m)).astype(np.uint32)
    got = np.asarray(ops.pair_counts(jnp.asarray(a), jnp.asarray(b)))
    want = np.asarray(ref.pair_counts(jnp.asarray(a), jnp.asarray(b)))
    assert np.array_equal(got, want)
    # The host /M of the counts is the numpy estimator, bit for bit.
    assert np.array_equal(got / np.float32(m),
                          (a == b).mean(axis=-1, dtype=np.float32))


@given(st.integers(2, 60), st.integers(1, 300), st.integers(1, 128))
@settings(max_examples=12, deadline=None)
def test_sigjaccard_masked_indexed_sweep(d, p, m):
    """Masked fused gather+estimate == numpy mean where valid, 0 elsewhere.

    Bit-identical to the host estimator (float32 division), which is
    what lets the device-resident stage-2 scores pass through the host
    merge with zero drift; out-of-range indices under an invalid mask
    must be tolerated (the cross-shard straggler lanes).
    """
    rng = np.random.RandomState(d * 31 + p + m)
    sig = rng.randint(0, 4, size=(d, m)).astype(np.uint32)
    a = rng.randint(-d, 2 * d, size=(p,)).astype(np.int32)
    b = rng.randint(-d, 2 * d, size=(p,)).astype(np.int32)
    valid = (a >= 0) & (a < d) & (b >= 0) & (b < d) & (rng.rand(p) < 0.8)
    got = np.asarray(ops.masked_indexed_pair_estimate(
        jnp.asarray(sig), jnp.asarray(a), jnp.asarray(b),
        jnp.asarray(valid)))
    want = np.zeros(p, dtype=np.float32)
    for i in range(p):
        if valid[i]:
            want[i] = (sig[a[i]] == sig[b[i]]).mean(dtype=np.float32)
    assert np.array_equal(got, want)


@given(st.integers(1, 300), st.integers(1, 128))
@settings(max_examples=12, deadline=None)
def test_sigjaccard_masked_rows_sweep(p, m):
    """Pre-gathered-operand masked counts == exact agreement counts.

    The cross-shard straggler scoring gathers one operand from the
    local signature shard and the other from the exchanged row buffer,
    so the kernel takes (P, M) rows directly; counts must be exact
    integers where valid and 0 elsewhere.
    """
    rng = np.random.RandomState(p * 13 + m)
    a = rng.randint(0, 4, size=(p, m)).astype(np.uint32)
    b = rng.randint(0, 4, size=(p, m)).astype(np.uint32)
    valid = rng.rand(p) < 0.7
    got = np.asarray(ops.masked_pair_counts(
        jnp.asarray(a), jnp.asarray(b), jnp.asarray(valid)))
    want = np.where(valid, (a == b).sum(axis=1), 0).astype(np.float32)
    assert np.array_equal(got, want)


def test_kernel_tile_size_invariance():
    rng = np.random.RandomState(0)
    ng = rng.randint(0, 2**32, size=(17, 97), dtype=np.uint64
                     ).astype(np.uint32)
    valid = rng.rand(17, 97) < 0.9
    seeds = rng.randint(0, 2**32, size=(33,), dtype=np.uint64
                        ).astype(np.uint32)
    outs = [
        np.asarray(ops.minhash_signatures(
            jnp.asarray(ng), jnp.asarray(valid), jnp.asarray(seeds),
            td=td, tl=tl, tm=tm))
        for td, tl, tm in [(8, 128, 128), (4, 32, 16), (17, 97, 33)]
    ]
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[0], outs[2])


@given(st.integers(1, 24), st.integers(1, 200), st.integers(2, 10),
       st.integers(1, 3))
@settings(max_examples=12, deadline=None)
def test_fused_ingest_sweep(d, l, n, r):
    """Fused pass bit-matches every staged reference over random shapes,
    including ragged lengths (0..L) and docs shorter than the window.

    Deterministic fused-ingest cases (edge cases, tile invariance,
    pipeline wiring) live in ``test_fused_ingest.py`` so they run even
    without hypothesis installed.
    """
    from test_fused_ingest import assert_fused_parity

    rng = np.random.RandomState(d * 131 + l * 7 + n)
    m = r * rng.randint(1, 20)  # M must be a multiple of r
    tokens = rng.randint(0, 2**32, size=(d, l), dtype=np.uint64
                         ).astype(np.uint32)
    lengths = rng.randint(0, l + 1, size=(d,)).astype(np.int32)
    seeds = rng.randint(0, 2**32, size=(m,), dtype=np.uint64
                        ).astype(np.uint32)
    assert_fused_parity(tokens, lengths, seeds, n=n, r=r)
