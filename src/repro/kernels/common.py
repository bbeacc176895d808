"""Helpers shared by the dedup Pallas kernels: the interpret-mode
choice, and uint32 minimums in a form Mosaic lowers for the TPU."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_SIGN = 0x80000000


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret=None`` picks the Pallas interpreter on the CPU backend
    and the compiled Mosaic kernel everywhere else."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def _to_ordered(x):
    return jax.lax.bitcast_convert_type(x ^ jnp.uint32(_SIGN), jnp.int32)


def _from_ordered(s):
    return jax.lax.bitcast_convert_type(s, jnp.uint32) ^ jnp.uint32(_SIGN)


def umin(x, axis: int):
    """uint32 min-reduction as an order-preserving int32 min.

    Mosaic has no unsigned min.  Flipping the sign bit maps the uint32
    order onto the int32 order, so the int32 min of the flipped values,
    flipped back, is the uint32 min bit for bit.
    """
    return _from_ordered(jnp.min(_to_ordered(x), axis=axis))


def uminimum(a, b):
    """Elementwise uint32 minimum (same sign-flip trick as ``umin``)."""
    return _from_ordered(jnp.minimum(_to_ordered(a), _to_ordered(b)))
