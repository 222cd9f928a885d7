"""Bytes each kernel launch has to move through HBM, from its shapes.

The NTT kernel (``kernels/ntt`` of the program) reads a (batch, n) uint32
block of coefficients and a (log2 n, n) uint32 table of stage twiddles,
and writes a (batch, n) uint32 block; ``batch`` is the launch's padded
batch.  Its integer multiply throughput has no published peak, so its
roofline is the bytes bound alone.
"""

from __future__ import annotations

U32 = 4


def ntt_bytes(batch: int, n: int) -> int:
    stages = n.bit_length() - 1
    return U32 * (2 * batch * n + stages * n)


def least_seconds(nbytes: int, peaks: dict) -> float:
    """The least time HBM needs to move ``nbytes``."""
    return nbytes / float(peaks["hbm_bytes_per_s"])
