"""Modelled HBM bytes of the Pallas kernels that per-layer shares read,
and the device time of their ops in a trace.

Both kernels are memory-bound by their design, so each is measured
against the chip's HBM bandwidth (``peaks.hbm_bytes_per_s``):

* ``byte_shingle`` (``kernels/byte_shingle.py``) does a few integer
  operations per byte of text it reads and writes eight bytes (a token
  id and an end flag) per byte position;
* ``sigjaccard_counts`` (``kernels/sigjaccard.py``) does one compare
  per eight bytes of gathered signature rows.  On the TPU, XLA puts the
  gathered rows it reads in VMEM (``S(1)`` in the op's layouts), so
  the HBM traffic is the row gather before it: its share is taken over
  the whole verify program (gather and kernel), with the op's padded
  shapes giving the bytes.

A compute roofline waits for a sourced VPU peak.

A kernel's shapes come from its op in the trace: on the TPU an ``XLA
Ops`` event is named by its HLO instruction, such as ``%sigjaccard_counts.3
= f32[8192,1]{1,0} custom-call(u32[8192,112]{1,0} %a, u32[8192,112]{1,0}
%b, s32[8192,1]{1,0} %v), custom_call_target="tpu_custom_call", ...``;
the padded shapes the kernel ran at are read from there.
"""
from __future__ import annotations

import re

SHAPE = re.compile(r"\b(pred|bf16|[suf]\d+)\[([\d,]*)\]")


def op_name_matches(name: str, kernel: str) -> bool:
    """True for the op of the Pallas call named ``kernel``."""
    return re.match(rf"^%?{re.escape(kernel)}(\.\d+)?\s*=", name) is not None


def op_shapes(name: str) -> tuple[list, list] | None:
    """(results, operands) of a custom-call op's HLO text, each a list of
    (dtype, dims); None when the name holds no custom call."""
    head = name.split("custom_call_target")[0]
    lhs, sep, rhs = head.partition("custom-call(")
    if not sep:
        return None

    def shapes(text):
        return [(t, tuple(int(x) for x in d.split(",") if x))
                for t, d in SHAPE.findall(text)]

    return shapes(lhs.partition("=")[2]), shapes(rhs)


def byte_shingle_bytes(positions: int, docs: int) -> int:
    """One ``byte_shingle`` call over a (positions, docs) byte matrix
    (byte positions down the rows, documents across the lanes): it reads
    the uint8 bytes and the (1, docs) int32 lengths, and writes the
    uint32 token ids, the int32 end flags and the two (1, docs) carries."""
    return positions * docs * (1 + 4 + 4) + docs * 4 + 2 * docs * 4


def sigjaccard_bytes(pairs: int, width: int) -> int:
    """One verify call over ``pairs`` (padded) pairs of ``width``-hash
    signatures: the two int32 index vectors and the 2 x ``pairs`` uint32
    rows they gather from the store are read, the (pairs,) float32
    counts written."""
    return 2 * pairs * width * 4 + 2 * pairs * 4 + pairs * 4


def byte_shingle_op_bytes(name: str) -> int | None:
    got = op_shapes(name)
    if got is None:
        return None
    data = [d for t, d in got[1] if t == "u8" and len(d) == 2]
    return byte_shingle_bytes(*data[0]) if data else None


def sigjaccard_op_bytes(name: str) -> int | None:
    got = op_shapes(name)
    if got is None:
        return None
    rows = [d for t, d in got[1] if t == "u32" and len(d) == 2]
    return sigjaccard_bytes(*rows[0]) if rows else None


def kernel_ops(trace, kernel: str) -> list[tuple[str, float]]:
    """(name, device seconds) of every op of ``kernel`` in the trace."""
    return [(n, d * 1e-9) for evs in trace.ops.values() for n, _, d in evs
            if op_name_matches(n, kernel)]


def hbm_share(ctx, kernel: str, op_bytes,
              programs: tuple | None = None) -> float | None:
    """Modelled HBM bytes of ``kernel``'s ops over their device time, or
    over that of the programs named by ``programs`` (each holding one
    such op), at the chip's HBM bandwidth, in percent; None without
    such ops or programs, or with an op whose shapes cannot be read."""
    if ctx.trace is None:
        return None
    ops = kernel_ops(ctx.trace, kernel)
    sizes = [op_bytes(n) for n, _ in ops]
    if not ops or any(b is None for b in sizes):
        return None
    seconds = sum(s for _, s in ops)
    if programs is not None:
        try:
            seconds = ctx.trace.program_s(programs)
        except LookupError:
            return None
    if seconds <= 0:
        return None
    import jax

    import peaks

    bw = peaks.for_kind(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * sum(sizes) / (seconds * bw)


def program_ms_per_1k_notes(ctx, programs: tuple) -> float | None:
    """Device milliseconds of the programs named by ``programs`` per
    1,000 notes of the window; None without a trace or such a program."""
    if ctx.trace is None:
        return None
    try:
        s = ctx.trace.program_s(programs)
    except LookupError:
        return None
    notes = ctx.counters.get("notes", 0)
    if s <= 0 or notes <= 0:
        return None
    return s * 1e3 / (notes / 1000.0)
