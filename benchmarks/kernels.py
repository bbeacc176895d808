"""Per-kernel µs/call (interpret mode on CPU) + allclose spot-check.

On-TPU these kernels lower via Mosaic; interpret mode here validates the
kernel bodies and gives relative cost shapes, not TPU wall time.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, section, timeit
from repro.kernels import ops, ref


def run():
    section("kernels: pallas(interpret) vs jnp ref, µs/call")
    rng = np.random.RandomState(0)
    D, L, M = 128, 512, 128
    tokens = rng.randint(0, 2**32, size=(D, L), dtype=np.uint64
                         ).astype(np.uint32)
    lengths = rng.randint(L // 2, L, size=(D,)).astype(np.int32)
    seeds = rng.randint(0, 2**32, size=(M,), dtype=np.uint64
                        ).astype(np.uint32)
    tj, lj, sj = map(jnp.asarray, (tokens, lengths, seeds))

    ng_k, valid = ops.ngram_hashes(tj, lj, n=8)
    ng_r, _ = ref.ngram_hashes(tj, lj, n=8)
    vm = np.asarray(valid)
    assert np.array_equal(np.asarray(ng_k)[vm], np.asarray(ng_r)[vm])
    for name, fn in [
        ("ngram_pallas", lambda: jax.block_until_ready(
            ops.ngram_hashes(tj, lj, n=8)[0])),
        ("ngram_ref", lambda: jax.block_until_ready(
            ref.ngram_hashes(tj, lj, n=8)[0])),
    ]:
        emit(name, timeit(fn), f"D={D};L={L}")

    sig_k = ops.minhash_signatures(ng_k, valid, sj)
    sig_r = ref.minhash_signatures(ng_k, valid, sj)
    assert np.array_equal(np.asarray(sig_k), np.asarray(sig_r))
    for name, fn in [
        ("minhash_pallas", lambda: jax.block_until_ready(
            ops.minhash_signatures(ng_k, valid, sj))),
        ("minhash_ref", lambda: jax.block_until_ready(
            ref.minhash_signatures(ng_k, valid, sj))),
    ]:
        emit(name, timeit(fn), f"D={D};L={L};M={M}")

    for name, fn in [
        ("bandfold_pallas", lambda: jax.block_until_ready(
            ops.band_values(sig_k, 2))),
        ("bandfold_ref", lambda: jax.block_until_ready(
            ref.band_values(sig_k, 2))),
    ]:
        emit(name, timeit(fn), f"D={D};b={M//2}")

    a = jnp.asarray(np.asarray(sig_k)[rng.randint(0, D, 512)])
    b = jnp.asarray(np.asarray(sig_k)[rng.randint(0, D, 512)])
    for name, fn in [
        ("sigjaccard_pallas", lambda: jax.block_until_ready(
            ops.pair_counts(a, b))),
        ("sigjaccard_ref", lambda: jax.block_until_ready(
            ref.pair_counts(a, b))),
    ]:
        emit(name, timeit(fn), "P=512")

    run_fused_ingest()
    run_byte_ingest()


def run_fused_ingest(D: int = 256, L: int = 512, M: int = 128,
                     n: int = 8, r: int = 2):
    """Fused one-pass ingest vs the staged three-dispatch chain.

    ``us_per_call`` is the fused wall time; ``derived`` carries the
    staged wall, the speedup, and a ``drift`` canary (#mismatching
    uint32 words across signatures AND band values vs staged — the
    bit-parity contract, gated to 0 by ``compare_rows``).
    """
    section("fused ingest: one-pass shingle->minhash->fold vs staged")
    rng = np.random.RandomState(7)
    tokens = rng.randint(0, 2**32, size=(D, L), dtype=np.uint64
                         ).astype(np.uint32)
    lengths = rng.randint(L // 2, L, size=(D,)).astype(np.int32)
    seeds = rng.randint(0, 2**32, size=(M,), dtype=np.uint64
                        ).astype(np.uint32)
    tj, lj, sj = map(jnp.asarray, (tokens, lengths, seeds))

    def staged():
        ng, valid = ops.ngram_hashes(tj, lj, n=n)
        sig = ops.minhash_signatures(ng, valid, sj)
        return jax.block_until_ready(ops.band_values(sig, r))

    def fused():
        return jax.block_until_ready(ops.fused_ingest(tj, lj, sj,
                                                      n=n, r=r)[1])

    bands_s = np.asarray(staged())
    ng, valid = ops.ngram_hashes(tj, lj, n=n)
    sig_s = np.asarray(ops.minhash_signatures(ng, valid, sj))
    sig_f, bands_f, _ = ops.fused_ingest(tj, lj, sj, n=n, r=r)
    drift = int((np.asarray(sig_f) != sig_s).sum()
                + (np.asarray(bands_f) != bands_s).sum())

    staged_us = timeit(staged)
    fused_us = timeit(fused)
    emit("fused_ingest_speedup", fused_us,
         f"staged_us={staged_us:.1f};"
         f"speedup={staged_us / max(fused_us, 1e-9):.2f};"
         f"drift={drift};D={D};L={L};M={M}")


def run_byte_ingest(D: int = 256, M: int = 128, n: int = 8, r: int = 2):
    """Zero-copy bytes->bands vs the host-tokenize + fused-ingest path.

    Both sides run their FULL ingest honestly: the host side pays
    tokenize + token_ids + pack + fused dispatch, the byte side pays
    pack_bytes + the ``bytes_to_bands`` chain.  ``drift`` counts
    mismatching uint32 words across signatures AND band values (the
    bit-parity contract for no-stem tokenization, gated to 0 by
    ``compare_rows``).
    """
    section("byte ingest: device bytes->bands vs host tokenize + fused")
    from repro.core import shingle
    from repro.data import make_i2b2_like

    notes = list(make_i2b2_like(D, seed=11))
    rng = np.random.RandomState(11)
    seeds = rng.randint(0, 2**32, size=(M,), dtype=np.uint64
                        ).astype(np.uint32)
    sj = jnp.asarray(seeds)

    def host_path():
        toks = [shingle.tokenize(t, do_stem=False) for t in notes]
        lt_bucket = shingle.pow2_bucket(max(len(t) for t in toks))
        packed = shingle.pack_documents(toks, lt_bucket)
        return ops.fused_ingest(jnp.asarray(packed.tokens),
                                jnp.asarray(packed.lengths), sj,
                                n=n, r=r)

    def byte_path():
        lb_bucket = shingle.pow2_bucket(
            max(len(t.encode("utf-8")) for t in notes) + 1)
        packed = shingle.pack_bytes(notes, lb_bucket)
        return ops.bytes_to_bands(jnp.asarray(packed.data),
                                  jnp.asarray(packed.lengths), sj,
                                  n=n, r=r)

    sig_h, bands_h, _ = host_path()
    sig_b, bands_b, _ = byte_path()
    drift = int((np.asarray(sig_b) != np.asarray(sig_h)).sum()
                + (np.asarray(bands_b) != np.asarray(bands_h)).sum())

    host_us = timeit(lambda: jax.block_until_ready(host_path()[1]))
    byte_us = timeit(lambda: jax.block_until_ready(byte_path()[1]))
    emit("byte_ingest_speedup", byte_us,
         f"host_us={host_us:.1f};"
         f"speedup={host_us / max(byte_us, 1e-9):.2f};"
         f"drift={drift};D={D};M={M}")


if __name__ == "__main__":
    run()
