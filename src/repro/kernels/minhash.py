"""Pallas TPU kernel: MinHash signature matrix.

sig[d, m] = min over valid n-gram positions l of fmix32(ng[d,l]*G + seed[m])

Tiling (DESIGN.md §2): grid (D/TD, M/TM, L/TL).  The L axis is the
innermost (sequential on TPU) grid dimension so the output block (TD, TM)
is revisited and min-accumulated in VMEM — the (TD, TL, TM) hash cube
never leaves registers/VMEM.  Block sizes keep the cube ≈ 0.5 MiB and the
M tile a multiple of 128 lanes for the VPU.

This kernel is the paper's dominant cost (its production run spent 75 of
99 hours producing signatures, §12).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.hashing import GOLDEN32, U32_MAX, fmix32
from repro.kernels.common import resolve_interpret, umin, uminimum

# Default tile sizes: (TD, TL, TM) cube = 8*128*128*4B = 512 KiB in VMEM.
TD, TL, TM = 8, 128, 128


def _minhash_kernel(ng_ref, valid_ref, seeds_ref, out_ref):
    l_idx = pl.program_id(2)
    ng = ng_ref[...]                              # (TD, TL)
    valid = valid_ref[...]                        # (TD, TL) uint32 0/1
    seeds = seeds_ref[...]                        # (1, TM)

    x = fmix32(ng[:, :, None] * GOLDEN32 + seeds[None, :, :])
    # Invalid positions OR to all-ones (U32_MAX), which never wins a min.
    dead = jnp.where(valid != 0, jnp.uint32(0), jnp.uint32(U32_MAX))
    part = umin(x | dead[:, :, None], axis=1)     # (TD, TM)

    @pl.when(l_idx == 0)
    def _init():
        out_ref[...] = part

    @pl.when(l_idx > 0)
    def _acc():
        out_ref[...] = uminimum(out_ref[...], part)


@functools.partial(
    jax.jit, static_argnames=("td", "tl", "tm", "interpret")
)
def minhash_signatures(
    ngrams: jnp.ndarray,
    valid: jnp.ndarray,
    seeds: jnp.ndarray,
    *,
    td: int = TD,
    tl: int = TL,
    tm: int = TM,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(D, L) uint32 n-gram hashes + (D, L) validity -> (D, M) signatures."""
    interpret = resolve_interpret(interpret)
    D, L = ngrams.shape
    M = seeds.shape[0]
    td = min(td, max(1, D))
    tl = min(tl, max(1, L))
    tm = min(tm, max(1, M))
    Dp, Lp, Mp = -(-D // td) * td, -(-L // tl) * tl, -(-M // tm) * tm
    ng = jnp.pad(ngrams.astype(jnp.uint32), ((0, Dp - D), (0, Lp - L)))
    vd = jnp.pad(valid.astype(jnp.uint32), ((0, Dp - D), (0, Lp - L)))
    sd = jnp.pad(seeds.astype(jnp.uint32), (0, Mp - M))[None, :]

    out = pl.pallas_call(
        _minhash_kernel,
        grid=(Dp // td, Mp // tm, Lp // tl),
        in_specs=[
            pl.BlockSpec((td, tl), lambda d, m, l: (d, l)),
            pl.BlockSpec((td, tl), lambda d, m, l: (d, l)),
            pl.BlockSpec((1, tm), lambda d, m, l: (0, m)),
        ],
        out_specs=pl.BlockSpec((td, tm), lambda d, m, l: (d, m)),
        out_shape=jax.ShapeDtypeStruct((Dp, Mp), jnp.uint32),
        interpret=interpret,
    )(ng, vd, sd)
    return out[:D, :M]
