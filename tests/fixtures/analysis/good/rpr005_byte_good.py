"""Clean fixture: the byte-shingle carry-block tiling (RPR005).

Mirrors ``kernels/byte_shingle.py`` (DESIGN.md §11): documents sit on
the lane axis of a (tlb_, td_) tile, grid-varying tile dims are
min-clamped locals, and the lengths and the FNV-state carry are 2-D
(1, td_) blocks (a rank-1 block tiled along a grid axis is not a legal
TPU block).  The carry is a revisited output block (same block for
every L step, re-initialized at the first L tile) whose out_shape rank
matches, and the resident tiles stay far under the VMEM ceiling.
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _byte_kernel(byte_ref, len_ref, tok_ref, h_ref):
    l_idx = pl.program_id(1)

    @pl.when(l_idx == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    tok_ref[...] = byte_ref[...].astype(jnp.uint32)
    h_ref[...] = h_ref[...] + len_ref[...].astype(jnp.uint32)


def launch(data, lengths, td: int = 128, tlb: int = 256):
    LB, D = data.shape
    td_ = min(td, max(1, D))
    tlb_ = min(tlb, max(1, LB))
    return pl.pallas_call(
        _byte_kernel,
        grid=(-(-D // td_), -(-LB // tlb_)),
        in_specs=[
            pl.BlockSpec((tlb_, td_), lambda d, l: (l, d)),
            pl.BlockSpec((1, td_), lambda d, l: (0, d)),
        ],
        out_specs=[
            pl.BlockSpec((tlb_, td_), lambda d, l: (l, d)),
            pl.BlockSpec((1, td_), lambda d, l: (0, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((LB, D), jnp.uint32),
            jax.ShapeDtypeStruct((1, D), jnp.uint32),
        ],
    )(data, lengths[None, :])
