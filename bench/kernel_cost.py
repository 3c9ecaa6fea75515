"""Bytes and operations one pair-gain kernel call needs, from its shapes.

The engine computes a sweep's swap gains as two Pallas calls, one per
side of every candidate pair (``kernels/pair_gain.py``).  A side call
over ``p`` pairs with ELL width ``k`` reduces, for each pair (a, b) and
each neighbour slot x of a, ``w_ax * (D(pa, px) - D(pb, px))``.

What it has to move, at the least:

* closed forms (``tree``, ``torus``): the two endpoint PEs per pair
  (int32), the neighbour PEs and weights per slot (int32 + float32), and
  one float32 gain per pair out;
* ``matrix``: the two gathered distances per slot in the table's stored
  type (``KernelConfig.dist_dtype``: int8, int16, or float32 when
  unpacked), the weight per slot, and the gain out.

The lane padding of ``k`` up to 128 inside the kernel is not counted:
it is work the algorithm does not need.  Operations are the per-slot
arithmetic of the distance form, for the record; the bound the
roofline share uses is the memory one (see :func:`least_seconds`), since
these are integer and elementwise operations with no published peak of
their own on the chip.
"""

from __future__ import annotations

_ITEMSIZE = {None: 4, "float32": 4, "int16": 2, "int8": 1}
# elementwise operations per (pair, slot) for one side, both distance
# evaluations included: per torus axis a divide, a modulo, a subtract,
# an abs, a subtract and a min, then a weighted add; per tree level a
# divide per PE, a compare and a select; then the difference, the
# product with the weight and the accumulate
_OPS_PER_AXIS = {"torus": 7, "tree": 4}


def side_bytes(kind: str, p: int, k: int, dist_dtype=None) -> int:
    """Least bytes one side call over ``p`` pairs of width ``k`` moves."""
    if kind == "matrix":
        per_slot = 2 * _ITEMSIZE[dist_dtype] + 4
        return p * k * per_slot + p * 4
    if kind in ("tree", "torus"):
        return p * 8 + p * k * 8 + p * 4
    raise ValueError(f"unknown distance form {kind!r}")


def side_ops(kind: str, p: int, k: int, axes: int) -> int:
    """Elementwise operations of one side call (``axes``: torus axes or
    tree levels; ignored for ``matrix``)."""
    per_dist = _OPS_PER_AXIS.get(kind, 0) * axes
    return p * k * (2 * per_dist + 3)


def least_seconds(bytes_: float, ops: float, peaks: dict) -> tuple:
    """``(seconds, bound)``: the larger of bytes over HBM bandwidth and
    operations over the chip's int8 operation peak, and which it was."""
    t_mem = bytes_ / peaks["hbm_bytes_per_s"]
    t_ops = ops / peaks["int8_ops_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
