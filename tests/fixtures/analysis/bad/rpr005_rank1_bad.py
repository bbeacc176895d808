"""Intentionally-bad fixture: RPR005 rank-1 blocks on the byte carry.

The tiling ``kernels/byte_shingle.py`` used before it was compiled for a
TPU: the lengths input and the FNV-state carry are rank-1 (td_,) blocks
that move along the document grid axis.  Interpret mode runs it; the
TPU lowering refuses both, because a rank-1 block must be the whole
vector or a multiple of 128 lanes.  Everything else (clamped tiles,
matching out ranks, small VMEM) is clean, so only the rank-1 findings
fire.
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


def launch(data, lengths, td: int = 8, tlb: int = 256):
    D, LB = data.shape
    td_ = min(td, max(1, D))
    tlb_ = min(tlb, max(1, LB))
    return pl.pallas_call(
        _byte_kernel,
        grid=(-(-D // td_), -(-LB // tlb_)),
        in_specs=[
            pl.BlockSpec((td_, tlb_), lambda d, l: (d, l)),
            pl.BlockSpec((td_,), lambda d, l: (d,)),
        ],
        out_specs=[
            pl.BlockSpec((td_, tlb_), lambda d, l: (d, l)),
            pl.BlockSpec((td_,), lambda d, l: (d,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((D, LB), jnp.uint32),
            jax.ShapeDtypeStruct((D,), jnp.uint32),
        ],
    )(data, lengths)
