"""Pallas TPU kernel: device-resident byte-level shingling.

Completes the zero-copy ingest path (DESIGN.md §11): raw UTF-8 bytes are
the only host->device transfer, and tokenize + token-hash + shingle +
minhash + band-fold all run on device as one ``bytes_to_bands`` pass.

Tokenization contract (bit-identical to the host no-stem path): a token
is a maximal run of ASCII alphanumerics, A-Z folds to a-z (+32), and
every other byte — including every byte >= 0x80 of a multi-byte UTF-8
sequence — is a separator.  ``core.shingle._WORD_RE`` only matches
ASCII, and an ASCII token's UTF-8 encoding is its own bytes, so the
per-token FNV-1a over folded bytes reproduces
``token_ids(tokenize(text, do_stem=False))`` exactly; multi-byte safety
is structural (no token byte can sit inside a multi-byte sequence).

FNV-1a is sequential per token, so the kernel walks byte positions one
at a time, carrying (FNV state, prev-byte-was-alnum) per document.  The
byte matrix is transposed on the way in: positions run down the rows and
documents across the 128 lanes, so each step updates 128 documents at
once and the loop indexes the major axis (Mosaic lowers no scan over
a lane axis).  The carries persist across L tiles as revisited (1, TD)
output blocks (the ``fused_ingest`` signature-accumulator idiom: the
grid's last axis is sequential on TPU, so the carry block stays resident
in VMEM across the L revisits) and are re-initialized at the first L
tile.  Zero padding is a separator, so a token ending at the last byte
of a document emits at the following zero column — callers must keep
matrix width strictly greater than every byte length (``pack_bytes``
enforces this; ``bytes_to_bands`` also pads one extra column).

Grid (D/TD, LB/TLB), L innermost.  VMEM per step is one (TLB, TD) uint8
byte tile, its int32 copy, the uint32 token/end tiles and two (1, TD)
carries.  The per-position token/end matrices go back to HBM, where
XLA compacts them into the token matrix handed to ``fused_ingest``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import FNV_OFFSET32, FNV_PRIME32, GOLDEN32, fmix32
from repro.kernels.common import resolve_interpret
from repro.kernels.fused_ingest import fused_ingest

# Default seed of core.shingle.token_ids (the hash-vocabulary seed).
TOKEN_SEED = 0x7045

# Default tiles: 128 documents (one lane width) x 256 byte positions.
TD, TLB = 128, 256
_ROWS = 8  # byte rows per loop step: one (8, 128) int32 vreg of docs


def _byte_kernel(byte_ref, len_ref, tok_ref, end_ref, h_ref, p_ref,
                 col_ref, *, tlb: int, seed: int):
    """One (TLB bytes, TD docs) tile; byte positions run down the rows.

    Documents sit on the lane axis, so the FNV chain walks the major
    (row) axis: a ``fori_loop`` over groups of 8 rows reads aligned
    (8, TD) slabs of the int32 copy of the tile, and the (1, TD)
    carries live in revisited output blocks across the L tiles.
    """
    l_idx = pl.program_id(1)

    @pl.when(l_idx == 0)
    def _init():
        h_ref[...] = jnp.full(h_ref.shape, jnp.uint32(FNV_OFFSET32),
                              dtype=jnp.uint32)
        p_ref[...] = jnp.zeros(p_ref.shape, dtype=jnp.int32)

    col_ref[...] = byte_ref[...].astype(jnp.int32)
    lens = len_ref[...]                               # (1, TD)
    offset = jnp.uint32(FNV_OFFSET32)
    prime = jnp.uint32(FNV_PRIME32)

    def group(g, carry):
        h, prev = carry                               # (1, TD) each
        row0 = pl.multiple_of(g * _ROWS, _ROWS)
        slab = col_ref[pl.ds(row0, _ROWS), :]         # (8, TD) int32
        toks, ends = [], []
        for j in range(_ROWS):
            b = slab[j:j + 1, :]
            # Positions at or beyond a document's byte length are
            # separators, so garbage padding never leaks into tokens.
            live = l_idx * tlb + row0 + j < lens
            upper = (b >= 65) & (b <= 90)
            alnum = (upper | ((b >= 97) & (b <= 122))
                     | ((b >= 48) & (b <= 57))) & live
            folded = jnp.where(upper, b + 32, b).astype(jnp.uint32)
            in_run = prev > 0
            # A run restarts from the FNV offset basis at its first byte.
            h0 = jnp.where(in_run, h, offset)
            end = in_run & jnp.logical_not(alnum)
            toks.append(jnp.where(
                end, fmix32(h * GOLDEN32 + jnp.uint32(seed)), jnp.uint32(0)))
            ends.append(end.astype(jnp.int32))
            h = jnp.where(alnum, (h0 ^ folded) * prime, h)
            prev = alnum.astype(jnp.int32)
        tok_ref[pl.ds(row0, _ROWS), :] = jnp.concatenate(toks, axis=0)
        end_ref[pl.ds(row0, _ROWS), :] = jnp.concatenate(ends, axis=0)
        return h, prev

    h, prev = jax.lax.fori_loop(0, tlb // _ROWS, group,
                                (h_ref[...], p_ref[...]))
    h_ref[...] = h
    p_ref[...] = prev


@functools.partial(
    jax.jit, static_argnames=("td", "tlb", "id_seed", "interpret"))
def byte_token_hashes(
    data: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    td: int = TD,
    tlb: int = TLB,
    id_seed: int = TOKEN_SEED,
    interpret: bool | None = None,
):
    """(D, LB) uint8 bytes + (D,) byte lengths ->
    (token ids (D, LB) uint32, token ends (D, LB) int32).

    ``ends[d, i]`` is 1 iff a token ends at byte position i (exclusive)
    and ``tok[d, i]`` is its hashed id.  Matches
    ``core.shingle.byte_token_hashes_np`` bit-for-bit.  The matrix width
    must exceed every byte length (a token touching the last column
    would have nowhere to emit) — ``pack_bytes`` guarantees this.
    """
    interpret = resolve_interpret(interpret)
    data = data.astype(jnp.uint8)
    lengths = lengths.astype(jnp.int32)
    D, LB = data.shape
    if D == 0:
        return (jnp.zeros((0, LB), jnp.uint32),
                jnp.zeros((0, LB), jnp.int32))
    td_ = min(td, max(1, D))
    # Byte rows are walked 8 at a time, so the tile rounds up to 8 rows
    # (on the TPU a uint8 tile must also be a multiple of 32 rows).
    tlb_ = min(tlb, max(1, LB))
    tlb_ = -(-tlb_ // _ROWS) * _ROWS
    Dp = -(-D // td_) * td_
    Lp = -(-LB // tlb_) * tlb_
    # Transposed: byte positions down the rows, documents across lanes.
    buf = jnp.pad(data, ((0, Dp - D), (0, Lp - LB))).T
    ln = jnp.pad(lengths, (0, Dp - D))[None, :]

    tok, ends, _, _ = pl.pallas_call(
        functools.partial(_byte_kernel, tlb=tlb_, seed=id_seed),
        grid=(Dp // td_, Lp // tlb_),
        in_specs=[
            pl.BlockSpec((tlb_, td_), lambda d, l: (l, d)),
            pl.BlockSpec((1, td_), lambda d, l: (0, d)),
        ],
        out_specs=[
            pl.BlockSpec((tlb_, td_), lambda d, l: (l, d)),
            pl.BlockSpec((tlb_, td_), lambda d, l: (l, d)),
            # FNV-state / in-run carries: revisited (1, TD) blocks,
            # VMEM-resident across the sequential L axis.
            pl.BlockSpec((1, td_), lambda d, l: (0, d)),
            pl.BlockSpec((1, td_), lambda d, l: (0, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Lp, Dp), jnp.uint32),
            jax.ShapeDtypeStruct((Lp, Dp), jnp.int32),
            jax.ShapeDtypeStruct((1, Dp), jnp.uint32),
            jax.ShapeDtypeStruct((1, Dp), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((tlb_, td_), jnp.int32)],
        interpret=interpret,
        name="byte_shingle",
    )(buf, ln)
    return tok.T[:D, :LB], ends.T[:D, :LB]


@functools.partial(
    jax.jit,
    static_argnames=("n", "r", "td", "tlb", "id_seed", "interpret"))
def bytes_to_bands(
    data: jnp.ndarray,
    lengths: jnp.ndarray,
    seeds: jnp.ndarray,
    *,
    n: int = 8,
    r: int = 2,
    td: int = TD,
    tlb: int = TLB,
    id_seed: int = TOKEN_SEED,
    interpret: bool | None = None,
):
    """(D, LB) uint8 bytes + (D,) byte lengths + (M,) seeds ->
    ((D, M) signatures, (D, M//r, 2) band values, (D,) token counts).

    The full zero-copy ingest: byte shingle kernel -> on-device token
    compaction (cumsum/scatter; dropped positions go out of bounds) ->
    ``fused_ingest``.  Bit-identical to host tokenize(do_stem=False) +
    ``token_ids`` + ``pack_documents`` + ``fused_ingest``.  Callers feed
    pow2-bucketed widths (``pack_bytes`` + ``pow2_bucket``) so the
    compile set stays bounded — RPR003 audits call sites.
    """
    data = data.astype(jnp.uint8)
    lengths = lengths.astype(jnp.int32)
    D, LB = data.shape
    M = seeds.shape[0]
    assert M % r == 0, f"M={M} not divisible by r={r}"
    if D == 0:
        return (jnp.zeros((0, M), jnp.uint32),
                jnp.zeros((0, M // r, 2), jnp.uint32),
                jnp.zeros((0,), jnp.int32))
    # One extra zero column so a token ending at the last byte of a
    # full-width row still emits (zero padding is a separator).
    buf = jnp.pad(data, ((0, 0), (0, 1)))
    tok, ends = byte_token_hashes(
        buf, lengths, td=td, tlb=tlb, id_seed=id_seed, interpret=interpret)

    # Compact sparse per-position emissions into a dense token matrix.
    # Capacity: token ends are >= 2 bytes apart, so ceil((LB+1)/2) is a
    # hard cap; the width is derived from the bucketed LB, keeping the
    # downstream fused_ingest compile set bounded too.
    lt_bucket = (LB + 1) // 2 + 1
    tidx = jnp.cumsum(ends, axis=1) - 1
    dst = jnp.where(ends > 0, tidx, lt_bucket)  # non-ends dropped (OOB)
    row = jnp.arange(D, dtype=jnp.int32)[:, None]
    tokens = jnp.zeros((D, lt_bucket), jnp.uint32)
    tokens = tokens.at[row, dst].set(tok, mode="drop")
    tok_lengths = jnp.sum(ends, axis=1).astype(jnp.int32)

    sig, bands, _ = fused_ingest(
        tokens, tok_lengths, seeds, n=n, r=r, interpret=interpret)
    return sig, bands, tok_lengths
