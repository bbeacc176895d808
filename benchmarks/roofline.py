"""Measured dedup-ingest roofline (``run_ingest_roofline``).

Times the staged three-dispatch ingest chain against the fused one-pass
kernel on this host's devices and reports docs/sec/device alongside the
analytic HBM bytes each path moves, then the host->device transfer of
padded tokens against raw bytes (``run_transfer_roofline``).
"""
from __future__ import annotations

from benchmarks.common import emit, section, timeit


def ingest_bytes_moved(D: int, L: int, M: int, r: int,
                       tm: int = 128, lb: int | None = None):
    """Analytic HBM traffic (bytes) of one ingest batch: staged vs fused.

    Staged chain round-trips every intermediate through HBM:
      tokens in, n-gram hashes out+in, valid mask out+in,
      signatures out+in, band values out.
    Fused keeps n-gram hashes and the hash cube in VMEM; its only HBM
    traffic is tokens in (re-read once per M-tile, ``ceil(M/tm)``),
    seeds in, signatures out, band values out.

    With ``lb`` (padded byte-matrix width) a third term is returned for
    the byte-ingest path (``kernels/byte_shingle.bytes_to_bands``): raw
    uint8 bytes in, the per-position token/end matrices out+in around
    the compaction, the compacted token matrix (width ``lb//2 + 1``)
    written once and re-read per M-tile by the fused stage, then the
    fused stage's own seed/signature/band traffic.  HOST->DEVICE
    transfer drops 4x PER MATRIX ELEMENT (uint8 vs int32); the net
    measured ratio depends on mean token length and rides the
    ``roofline_ingest_transfer`` bench row.
    """
    b_bands = (M // r) * 2 * 4  # per-doc band bytes (2 fold lanes)
    staged = (D * L * 4            # tokens in (shingle)
              + 2 * D * L * 4      # ngram hashes out + in
              + 2 * D * L         # valid mask out + in (int8)
              + M * 4              # seeds in
              + 2 * D * M * 4      # signatures out + in
              + D * b_bands)       # band values out
    m_tiles = -(-M // tm)
    fused = (m_tiles * D * L * 4   # tokens re-read per M-tile
             + M * 4               # seeds in
             + D * M * 4           # signatures out (once, final flush)
             + D * b_bands)        # band values out
    if lb is None:
        return staged, fused
    lbe = lb + 1                   # +1 emission column (byte_shingle)
    lt = lbe // 2 + 1              # compacted token-matrix width
    byte_fused = (D * lbe          # raw uint8 bytes in (byte kernel)
                  + 2 * D * lbe * 4  # token-hash matrix out + in
                  + 2 * D * lbe * 4  # token-end matrix out + in
                  + D * lt * 4     # compacted tokens out
                  + m_tiles * D * lt * 4  # re-read per fused M-tile
                  + M * 4          # seeds in
                  + D * M * 4      # signatures out
                  + D * b_bands)   # band values out
    return staged, fused, byte_fused


def run_ingest_roofline(D: int = 256, L: int = 512, M: int = 128,
                        n: int = 8, r: int = 2):
    """Measured dedup-ingest roofline: docs/sec/device, staged vs fused."""
    section("measured dedup-ingest roofline (docs/sec/device)")
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.RandomState(3)
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
        return jax.block_until_ready(
            ops.fused_ingest(tj, lj, sj, n=n, r=r)[1])

    staged()  # compile outside the timed region
    fused()
    staged_us = timeit(staged)
    fused_us = timeit(fused)
    # The batch runs on one device; per-device throughput is the
    # number a pod multiplies by its device count.
    docs_fused = D / (fused_us * 1e-6)
    docs_staged = D / (staged_us * 1e-6)
    bytes_staged, bytes_fused = ingest_bytes_moved(D, L, M, r)
    emit(
        "roofline_dedup_ingest", fused_us,
        f"docs_per_s_per_device={docs_fused:.0f};"
        f"staged_docs_per_s_per_device={docs_staged:.0f};"
        f"bytes_hbm_fused={bytes_fused};"
        f"bytes_hbm_staged={bytes_staged};"
        f"traffic_ratio={bytes_staged / bytes_fused:.2f};"
        f"backend={jax.default_backend()};D={D};L={L};M={M}")
    run_transfer_roofline(D=D, M=M, n=n, r=r)


def run_transfer_roofline(D: int = 256, M: int = 128,
                          n: int = 8, r: int = 2):
    """Measured host->device transfer: padded tokens vs raw bytes.

    Same corpus both ways.  The token path stages host tokenize +
    ``pack_documents`` and ships a padded int32 matrix; the byte path
    ships the uint8 byte matrix and lets ``bytes_to_bands`` tokenize on
    device.  ``bytes_h2d_*`` are the actual ``.nbytes`` of what crosses
    PCIe per batch.  Per matrix element the byte path moves 4x less
    (uint8 vs int32); the net ``transfer_ratio`` depends on mean token
    length — word-level corpora average >4 bytes/token, so the decisive
    win there is removing host tokenize from the critical path
    (measured by ``byte_ingest_speedup``), while the transfer win is
    realized for short-token/character-shingle regimes.
    """
    section("measured ingest transfer: int32 tokens vs uint8 bytes")
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core import shingle
    from repro.data import make_i2b2_like
    from repro.kernels import ops

    notes = list(make_i2b2_like(D, seed=3))
    rng = np.random.RandomState(3)
    seeds = rng.randint(0, 2**32, size=(M,), dtype=np.uint64
                        ).astype(np.uint32)
    sj = jnp.asarray(seeds)

    toks = [shingle.tokenize(t, do_stem=False) for t in notes]
    lt_bucket = shingle.pow2_bucket(max(len(t) for t in toks))
    ptok = shingle.pack_documents(toks, lt_bucket)
    lb_bucket = shingle.pow2_bucket(
        max(len(t.encode("utf-8")) for t in notes) + 1)
    pbyt = shingle.pack_bytes(notes, lb_bucket)

    # What actually crosses host->device per batch.
    h2d_tok = ptok.tokens.nbytes + ptok.lengths.nbytes
    h2d_byt = pbyt.data.nbytes + pbyt.lengths.nbytes

    def token_path():
        return jax.block_until_ready(
            ops.fused_ingest(jnp.asarray(ptok.tokens),
                             jnp.asarray(ptok.lengths), sj,
                             n=n, r=r)[1])

    def byte_path():
        return jax.block_until_ready(
            ops.bytes_to_bands(jnp.asarray(pbyt.data),
                               jnp.asarray(pbyt.lengths), sj,
                               n=n, r=r)[1])

    token_path()  # compile outside the timed region
    byte_path()
    tok_us = timeit(token_path)
    byt_us = timeit(byte_path)
    _, hbm_tok, hbm_byt = ingest_bytes_moved(
        D, lt_bucket, M, r, lb=lb_bucket)
    emit(
        "roofline_ingest_transfer", byt_us,
        f"token_us={tok_us:.1f};"
        f"bytes_h2d_tokens={h2d_tok};"
        f"bytes_h2d_bytes={h2d_byt};"
        f"transfer_ratio={h2d_tok / h2d_byt:.2f};"
        f"bytes_hbm_token_fused={hbm_tok};"
        f"bytes_hbm_byte_fused={hbm_byt};"
        f"backend={jax.default_backend()};D={D};M={M}")


if __name__ == "__main__":
    run_ingest_roofline()
