"""Pallas TPU kernel: rolling n-gram polynomial hash with halo blocks.

Each output position l hashes tokens[l : l+n].  The window crosses tile
boundaries, so the kernel reads its own (TD, TL) token tile plus the next
tile along L (halo) — two in_specs over the same operand with shifted
index maps (the standard Pallas halo idiom; BlockSpecs cannot overlap).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.hashing import NGRAM_BASE, fmix32
from repro.kernels.common import resolve_interpret

TD, TL = 8, 256


def _ngram_kernel(tok_ref, halo_ref, out_ref, *, n: int, tl: int):
    tok = tok_ref[...].astype(jnp.uint32)    # (TD, TL)
    halo = halo_ref[...].astype(jnp.uint32)  # (TD, TL) — next tile (clamped)
    cat = jnp.concatenate([tok, halo], axis=1)
    acc = jnp.zeros_like(tok)
    base = jnp.uint32(NGRAM_BASE)
    for k in range(n):  # static lane slices: Mosaic has no dynamic_slice
        acc = acc * base + cat[:, k:k + tl]
    out_ref[...] = fmix32(acc)


@functools.partial(jax.jit, static_argnames=("n", "td", "tl", "interpret"))
def ngram_hashes(
    tokens: jnp.ndarray,
    lengths: jnp.ndarray,
    n: int = 8,
    *,
    td: int = TD,
    tl: int = TL,
    interpret: bool | None = None,
):
    """(D, L) uint32 tokens -> ((D, L) hashes, (D, L) validity).

    Matches ``repro.core.shingle.ngram_hashes`` (the ref oracle), including
    the short-document single-shingle rule.
    """
    interpret = resolve_interpret(interpret)
    D, L = tokens.shape
    td_ = min(td, max(1, D))
    # Clamp the L tile UP to n: a batch narrower than the window pads to
    # one n-wide tile whose zero fill reproduces the oracle's zero-padded
    # prefix hash (the short-document single-shingle rule).
    tl_ = max(min(tl, max(1, L)), n)
    Dp, Lp = -(-D // td_) * td_, -(-L // tl_) * tl_
    tok = jnp.pad(tokens.astype(jnp.uint32), ((0, Dp - D), (0, Lp - L)))
    n_l = Lp // tl_

    out = pl.pallas_call(
        functools.partial(_ngram_kernel, n=n, tl=tl_),
        grid=(Dp // td_, n_l),
        in_specs=[
            pl.BlockSpec((td_, tl_), lambda d, l: (d, l)),
            # Halo: next L tile, clamped at the edge (edge outputs are
            # invalid by construction: l + n > length there).
            pl.BlockSpec(
                (td_, tl_), lambda d, l: (d, jnp.minimum(l + 1, n_l - 1))
            ),
        ],
        out_specs=pl.BlockSpec((td_, tl_), lambda d, l: (d, l)),
        out_shape=jax.ShapeDtypeStruct((Dp, Lp), jnp.uint32),
        interpret=interpret,
    )(tok, tok)
    out = out[:D, :L]
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    ln = lengths.astype(jnp.int32)[:, None]
    valid = pos + n <= ln
    short = (ln < n) & (pos == 0) & (ln > 0)
    # Short docs hash their full prefix: recompute position 0 with the
    # actual (clamped) window — handled on the host side of the kernel.
    return out, valid | short
