"""The least work a pass has to do, computed from n alone.

A CC-LP pass reads and writes each of the three triangle duals of every
triplet once (f32: 3 duals x 4 B x 2 = 24 B per triplet) and reads or
writes X and W once (f32: 2 x 4 B x 2 = 16 B per cell of the n x n
matrices). Padding, staging and how the pass is implemented do not enter,
so the count reads the same work whatever runs the pass.
"""

from __future__ import annotations


def triplets(n: int) -> int:
    """C(n, 3)."""
    return n * (n - 1) * (n - 2) // 6


def pass_floor_bytes(n: int) -> int:
    """Bytes one pass must move between HBM and the chip: 24 C(n,3) + 16 n^2."""
    return 24 * triplets(n) + 16 * n * n
