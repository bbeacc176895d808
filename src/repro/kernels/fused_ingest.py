"""Pallas TPU kernel: fused device-resident ingest.

One pass computes the whole signature-production chain the staged path
runs as three dispatches (``kernels/ngram.py`` -> ``kernels/minhash.py``
-> ``kernels/bandfold.py``):

    packed (tokens, lengths, seeds) -> (signatures, band_values, valid)

Grid (D/TD, M/TM, L/TL) with L innermost (sequential on TPU), exactly
the minhash tiling (DESIGN.md §2/§8):

* The rolling n-gram hash is recomputed per token tile from the tile
  plus its L-halo (the ``kernels/ngram.py`` idiom: two in_specs over the
  same operand with shifted index maps) — the (TD, TL) hash tile lives
  only in VMEM and is never written to HBM.
* The seeded (TD, TL, TM) hash cube is min-accumulated into the output
  signature block, which Pallas keeps resident in VMEM across the L
  revisits (the ``kernels/minhash.py`` accumulation).
* At the LAST L tile the signature block is final, so the 2-lane band
  fold (``kernels/bandfold.py``) runs on it in-register and writes the
  (TD, TM/r, 2) band block — signatures are read back out of VMEM, not
  HBM.  ``tm`` is clamped to a multiple of ``r`` so every band's r rows
  live inside one M tile.

Bit-parity contract: every op is exact uint32 arithmetic (wraparound
multiply / xor / shift), so outputs are bit-identical to the staged
kernels AND to the pure-jnp refs (``core.shingle`` / ``core.minhash`` /
``core.lsh``) — drift = 0 is pinned by tests and the bench gate.

``interpret=None`` auto-selects interpreter mode on CPU so the fused
path runs (and is parity-checked in CI) without a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.hashing import GOLDEN32, NGRAM_BASE, U32_MAX, fmix32
from repro.kernels.common import resolve_interpret, umin, uminimum

_LANE_SEEDS = (0x2545F491, 0x9E3779B9)

# Defaults match kernels/minhash.py: (TD, TL, TM) cube = 512 KiB VMEM.
TD, TL, TM = 8, 128, 128


def _fused_kernel(tok_ref, halo_ref, len_ref, seeds_ref, sig_ref,
                  band_ref, *, n: int, r: int, td: int, tl: int,
                  tm: int, n_l: int):
    l_idx = pl.program_id(2)
    tok = tok_ref[...]                         # (TD, TL)
    halo = halo_ref[...]                       # (TD, TL) next tile (clamped)
    ln = len_ref[...]                          # (TD, 1)
    seeds = seeds_ref[...]                     # (1, TM), band-major order

    # --- shingle: rolling n-gram polynomial hash over the halo'd tile
    # (static lane slices; Mosaic has no dynamic_slice).
    cat = jnp.concatenate([tok, halo], axis=1)
    acc = jnp.zeros_like(tok)
    base = jnp.uint32(NGRAM_BASE)
    for k in range(n):
        acc = acc * base + cat[:, k:k + tl]
    ng = fmix32(acc)                            # (TD, TL), VMEM-only

    # Validity of each window position (incl. the short-doc single
    # shingle at position 0), from lengths alone — no mask operand.
    pos = l_idx * tl + jax.lax.broadcasted_iota(jnp.int32, (td, tl), 1)
    valid = (pos + n <= ln) | ((ln < n) & (pos == 0) & (ln > 0))

    # --- minhash: seeded cube, min-accumulate into the resident block.
    # Invalid positions OR to all-ones (U32_MAX), which never wins a min;
    # a uint32 mask, because Mosaic cannot reshape a boolean tile to 3-D.
    dead = jnp.where(valid, jnp.uint32(0), jnp.uint32(U32_MAX))
    x = fmix32(ng[:, :, None] * GOLDEN32 + seeds[None, :, :])
    part = umin(x | dead[:, :, None], axis=1)  # (TD, TM)

    @pl.when(l_idx == 0)
    def _init():
        sig_ref[...] = part

    @pl.when(l_idx > 0)
    def _acc():
        sig_ref[...] = uminimum(sig_ref[...], part)

    # --- band fold: the signature block is final on the last L tile;
    # fold its bands in-register (tm % r == 0 by construction).  The
    # band-major column order turns row k of every band into one
    # contiguous lane slice.
    @pl.when(l_idx == n_l - 1)
    def _fold():
        s = sig_ref[...]
        bt = tm // r
        for lane, seed in enumerate(_LANE_SEEDS):
            h = jnp.full((td, bt), jnp.uint32(seed), dtype=jnp.uint32)
            for k in range(r):
                h = fmix32(h * GOLDEN32 + s[:, k * bt:(k + 1) * bt])
            band_ref[lane] = h


@functools.partial(
    jax.jit, static_argnames=("n", "r", "td", "tl", "tm", "interpret"))
def fused_ingest(
    tokens: jnp.ndarray,
    lengths: jnp.ndarray,
    seeds: jnp.ndarray,
    *,
    n: int = 8,
    r: int = 2,
    td: int = TD,
    tl: int = TL,
    tm: int = TM,
    interpret: bool | None = None,
):
    """(D, L) uint32 tokens + (D,) lengths + (M,) seeds ->
    ((D, M) signatures, (D, M//r, 2) band values, (D, L) validity).

    One device-resident pass; n-gram hashes and the minhash cube never
    leave VMEM.  Matches the staged kernels and the jnp refs bit-for-
    bit.  Unlike the staged ngram kernel, batches whose padded width is
    shorter than ``n`` are handled (the tile length is clamped up to
    ``n`` and the zero right-padding reproduces the short-doc rule).
    """
    interpret = resolve_interpret(interpret)
    tokens = tokens.astype(jnp.uint32)
    lengths = lengths.astype(jnp.int32)
    seeds = seeds.astype(jnp.uint32)
    D, L = tokens.shape
    M = seeds.shape[0]
    assert M % r == 0, f"M={M} not divisible by r={r}"
    b = M // r
    if D == 0:
        return (jnp.zeros((0, M), jnp.uint32),
                jnp.zeros((0, b, 2), jnp.uint32),
                jnp.zeros((0, L), jnp.bool_))
    td_ = min(td, max(1, D))
    # The halo read needs tl >= n (a window crosses at most one tile
    # boundary); clamping up also absorbs batches with L < n.
    tl_ = max(min(tl, max(1, L)), n)
    # Every band's r rows must fall inside one M tile.  On the TPU a
    # band block narrower than M must be a multiple of 128 lanes; the
    # default tile holds all of M (the paper's M=100) in one.
    tm_ = min(tm, max(1, M))
    tm_ = max(r, (tm_ // r) * r)
    Dp = -(-D // td_) * td_
    Lp = -(-L // tl_) * tl_
    Mp = -(-M // tm_) * tm_
    tok = jnp.pad(tokens, ((0, Dp - D), (0, Lp - L)))
    # Lengths ride as a (Dp, 1) column: a rank-1 (td,) block is not a
    # legal TPU block unless it is 128-aligned or the whole array.
    ln = jnp.pad(lengths, (0, Dp - D))[:, None]
    n_l = Lp // tl_
    n_m = Mp // tm_
    bt = tm_ // r
    # Band-major seed order inside each M tile: column k*bt + j holds
    # signature row j*r + k, so the fold reads contiguous lane slices.
    sd = jnp.pad(seeds, (0, Mp - M)).reshape(n_m, bt, r)
    sd = sd.transpose(0, 2, 1).reshape(1, Mp)

    sig, bands = pl.pallas_call(
        functools.partial(_fused_kernel, n=n, r=r, td=td_, tl=tl_,
                          tm=tm_, n_l=n_l),
        grid=(Dp // td_, Mp // tm_, Lp // tl_),
        in_specs=[
            pl.BlockSpec((td_, tl_), lambda d, m, l: (d, l)),
            # Halo: next L tile, clamped at the edge (edge positions
            # are invalid by construction there).
            pl.BlockSpec(
                (td_, tl_),
                lambda d, m, l: (d, jnp.minimum(l + 1, n_l - 1))),
            pl.BlockSpec((td_, 1), lambda d, m, l: (d, 0)),
            pl.BlockSpec((1, tm_), lambda d, m, l: (0, m)),
        ],
        out_specs=[
            pl.BlockSpec((td_, tm_), lambda d, m, l: (d, m)),
            pl.BlockSpec((2, td_, tm_ // r), lambda d, m, l: (0, d, m)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Dp, Mp), jnp.uint32),
            jax.ShapeDtypeStruct((2, Dp, Mp // r), jnp.uint32),
        ],
        interpret=interpret,
        # A stable device-op name for operators reading a trace; the
        # ``jit_fused_ingest`` program around it keeps the jit's name.
        name="fused_ingest",
    )(tok, tok, ln, sd)
    sig = sig.reshape(Dp, n_m, r, bt).transpose(0, 1, 3, 2).reshape(Dp, Mp)
    bands = bands.transpose(1, 2, 0)             # (Dp, Mp // r, 2)

    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    ln2 = lengths[:, None]
    valid = (pos + n <= ln2) | ((ln2 < n) & (pos == 0) & (ln2 > 0))
    return sig[:D, :M], bands[:D, :b], valid
