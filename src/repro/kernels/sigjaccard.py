"""Pallas TPU kernel: signature-agreement counts for candidate pairs.

Given signature rows for P candidate pairs, computes the exact count
c[p] = #{m : a[p, m] == b[p, m]} as float32 (an exact integer value);
paper §3.4's m/M estimate is c / M.  Memory-bound; tiled (TP, M) so both
operands stream through VMEM once.

The kernels emit counts, never the estimate: a float32 c / M computed
on the device is not the host estimator's correctly rounded division
(XLA turns division by a constant into a multiply by its reciprocal,
which is 1 ulp off for 30 of the 101 counts at M=100).  Consumers divide
in numpy, so every verify backend is bit-identical to the numpy one.

Per-pair vectors (the output, the validity mask) are (P, 1) columns:
XLA lays a rank-1 vector out in 1024-element tiles, which a (TP,) block
does not match, while a (TP, 1) block is legal for any TP.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret

TP = 256


def _counts_kernel(a_ref, b_ref, v_ref, out_ref):
    eq = (a_ref[...] == b_ref[...]).astype(jnp.float32)
    out_ref[...] = jnp.where(v_ref[...] != 0,
                             jnp.sum(eq, axis=1, keepdims=True), 0.0)


def _counts_rows(sig_a, sig_b, valid, tp: int, interpret: bool | None):
    """Masked agreement counts over PRE-GATHERED (P, M) row operands."""
    interpret = resolve_interpret(interpret)
    P, M = sig_a.shape
    tp_ = min(tp, max(1, P))
    Pp = -(-P // tp_) * tp_
    a = jnp.pad(sig_a.astype(jnp.uint32), ((0, Pp - P), (0, 0)))
    b = jnp.pad(sig_b.astype(jnp.uint32), ((0, Pp - P), (0, 0)))
    v = jnp.pad(valid.astype(jnp.int32), (0, Pp - P))[:, None]

    out = pl.pallas_call(
        _counts_kernel,
        grid=(Pp // tp_,),
        in_specs=[
            pl.BlockSpec((tp_, M), lambda p: (p, 0)),
            pl.BlockSpec((tp_, M), lambda p: (p, 0)),
            pl.BlockSpec((tp_, 1), lambda p: (p, 0)),
        ],
        out_specs=pl.BlockSpec((tp_, 1), lambda p: (p, 0)),
        out_shape=jax.ShapeDtypeStruct((Pp, 1), jnp.float32),
        interpret=interpret,
        name="sigjaccard_counts",
    )(a, b, v)
    return out[:P, 0]


def _gather_rows(sig, a_idx, b_idx, width=None):
    """Row gather with indices clipped to the local row range; with
    ``width``, only each row's leading ``width`` hashes (a matrix whose
    rows are padded to whole lanes)."""
    D = sig.shape[0]
    cols = slice(None) if width is None else slice(0, width)
    return (sig[jnp.clip(a_idx, 0, D - 1)][:, cols],
            sig[jnp.clip(b_idx, 0, D - 1)][:, cols])


@functools.partial(jax.jit, static_argnames=("tp", "interpret"))
def pair_counts(
    sig_a: jnp.ndarray,
    sig_b: jnp.ndarray,
    *,
    tp: int = TP,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(P, M), (P, M) uint32 -> (P,) float32 agreement counts."""
    valid = jnp.ones((sig_a.shape[0],), jnp.int32)
    return _counts_rows(sig_a, sig_b, valid, tp, interpret)


@functools.partial(jax.jit, static_argnames=("width", "tp", "interpret"))
def indexed_pair_counts(
    sig: jnp.ndarray,
    a_idx: jnp.ndarray,
    b_idx: jnp.ndarray,
    *,
    width: int | None = None,
    tp: int = TP,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused gather + agreement counts: one dispatch per index batch.

    sig (D, W) uint32, a_idx/b_idx (P,) int -> (P,) float32 counts over
    the leading ``width`` (default W) hashes of each row.  The row
    gather runs on device inside the same jit as the kernel, so
    verifiers never materialize the gathered operands on the host; they
    divide the counts by M in numpy.  A signature store keeps its rows
    padded to whole 128-lane tiles (M=112 padded to 128), which the TPU
    lays out row-major; a (D, 112) matrix it would lay out column-major
    and copy whole into row order on every call.
    """
    valid = jnp.ones(a_idx.shape, jnp.int32)
    return _counts_rows(*_gather_rows(sig, a_idx, b_idx, width), valid, tp,
                        interpret)


@functools.partial(jax.jit, static_argnames=("tp", "interpret"))
def masked_pair_counts(
    sig_a: jnp.ndarray,
    sig_b: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    tp: int = TP,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Masked full-M agreement *count* over pre-gathered row operands.

    sig_a/sig_b (P, M) uint32, valid (P,) bool -> (P,) float32 exact
    agreement counts where ``valid``, 0.0 elsewhere.  The pre-gathered
    variant of ``masked_indexed_pair_counts`` for operands that do NOT
    both live in one local matrix — the cross-shard straggler scoring
    of the sharded dedup path gathers one side from the device's own
    signature shard and the other from the bounded row buffer exchanged
    inside the all_to_all, then scores the pair here.
    """
    return _counts_rows(sig_a, sig_b, valid, tp, interpret)


@functools.partial(jax.jit, static_argnames=("tp", "interpret"))
def masked_indexed_pair_counts(
    sig: jnp.ndarray,
    a_idx: jnp.ndarray,
    b_idx: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    tp: int = TP,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused gather + full-M agreement *count* with a validity mask.

    sig (D, M) uint32, a_idx/b_idx (P,) int, valid (P,) bool ->
    (P,) float32: #agreeing signature rows (an exact integer value)
    where ``valid``, 0.0 elsewhere.  Indices are clipped to the local
    row range before the gather, so callers can pass raw shard-relative
    indices whose invalid lanes (cross-shard edges, empty buffer slots)
    point outside the shard — this is the device-resident stage-2
    verify of the sharded dedup path, run under ``shard_map`` over each
    device's own signature shard with a ``psum`` combining the
    per-shard masked contributions.  The /M division happens on the
    host merge in numpy (see the module docstring).
    """
    return _counts_rows(*_gather_rows(sig, a_idx, b_idx), valid, tp,
                        interpret)


def masked_indexed_pair_estimate(
    sig: jnp.ndarray,
    a_idx: jnp.ndarray,
    b_idx: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    tp: int = TP,
    interpret: bool | None = None,
) -> np.ndarray:
    """Masked fused gather + full-M estimate: device counts / M on the
    host, so the result is bit-identical to the numpy estimator."""
    counts = masked_indexed_pair_counts(
        sig, a_idx, b_idx, valid, tp=tp, interpret=interpret)
    return np.asarray(counts) / np.float32(sig.shape[1])
