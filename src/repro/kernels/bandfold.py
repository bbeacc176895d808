"""Pallas TPU kernel: fold signature rows into 2-lane band values.

(D, b, r) uint32 -> (D, b, 2) uint32: per band, chained
h <- fmix32(h * GOLDEN + sig_k) over the r rows, one chain per lane seed
(paper §4.3 folds r values to one 64-bit integer; two 32-bit lanes here,
see DESIGN.md §2/§5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.hashing import GOLDEN32, fmix32
from repro.kernels.common import resolve_interpret

_LANE_SEEDS = (0x2545F491, 0x9E3779B9)
TD, TB = 64, 64


def _bandfold_kernel(sig_ref, out_ref, *, r: int):
    sig = sig_ref[...].astype(jnp.uint32)       # (TD, TB, r)
    for lane, seed in enumerate(_LANE_SEEDS):
        h = jnp.full(sig.shape[:2], jnp.uint32(seed), dtype=jnp.uint32)
        for k in range(r):
            h = fmix32(h * GOLDEN32 + sig[:, :, k])
        out_ref[:, :, lane] = h


@functools.partial(jax.jit, static_argnames=("r", "td", "tb", "interpret"))
def band_values(
    sig: jnp.ndarray,
    r: int,
    *,
    td: int = TD,
    tb: int = TB,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(D, M) uint32 signatures -> (D, b, 2) uint32 band values."""
    interpret = resolve_interpret(interpret)
    D, M = sig.shape
    assert M % r == 0
    b = M // r
    td_ = min(td, max(1, D))
    tb_ = min(tb, max(1, b))
    Dp, Bp = -(-D // td_) * td_, -(-b // tb_) * tb_
    s3 = sig.astype(jnp.uint32).reshape(D, b, r)
    s3 = jnp.pad(s3, ((0, Dp - D), (0, Bp - b), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_bandfold_kernel, r=r),
        grid=(Dp // td_, Bp // tb_),
        in_specs=[pl.BlockSpec((td_, tb_, r), lambda d, j: (d, j, 0))],
        out_specs=pl.BlockSpec((td_, tb_, 2), lambda d, j: (d, j, 0)),
        out_shape=jax.ShapeDtypeStruct((Dp, Bp, 2), jnp.uint32),
        interpret=interpret,
    )(s3)
    return out[:D, :b]
