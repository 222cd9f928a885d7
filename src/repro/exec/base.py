"""BatchedProtocolDriver: the contract between the engine's batched fast
path and protocol-specific batch kernels.

A batched driver *wraps* an existing scalar ``ProtocolDriver`` — the scalar
driver remains the bitwise reference and still handles every instruction
the batch path declines (barriers, ops outside ``batch_ops``, singleton
groups).  The engine hands a batch as column arrays:

    execute_batch(op, imm, out_idx, in_idx, memory)

* ``op``      — the shared opcode of the group;
* ``imm``     — the group's (uniform) immediate tuple;
* ``out_idx`` / ``in_idx`` — one ``(starts, length)`` pair per operand
  slot: ``starts`` is an int64 ``(count,)`` array of span start addresses,
  ``length`` the shared span length;
* ``memory``  — the engine array, shape ``(n_slots, lane)``.

The driver gathers operand columns, runs one vectorized/compiled kernel
over the whole group, and scatters results back.  Gather/scatter helpers
below write exactly the slots the scalar driver writes, so engine memory is
bitwise identical after a batched group and after the equivalent scalar
replay — the property the digest tests assert.
"""

from __future__ import annotations

from typing import Any

import numpy as np

from ..core.bytecode import Instr, Op
from ..core.engine import ProtocolDriver

#: one operand column: (span start addresses (count,), shared span length)
SpanCol = tuple[np.ndarray, int]


def gather_spans(memory: np.ndarray, col: SpanCol) -> np.ndarray:
    """(count, length, lane) copy of the group's operand spans."""
    starts, length = col
    return memory[starts[:, None] + np.arange(length, dtype=np.int64)]


def scatter_spans(memory: np.ndarray, col: SpanCol,
                  vals: np.ndarray) -> None:
    starts, length = col
    memory[starts[:, None] + np.arange(length, dtype=np.int64)] = vals


def strided_positions(col: SpanCol, n: int, stride: int) -> np.ndarray:
    """(count, n) slot addresses at ``start + k*stride`` — the wire-strided
    value positions the plaintext driver reads/writes."""
    starts, _ = col
    return starts[:, None] + np.arange(n, dtype=np.int64) * stride


class BatchedProtocolDriver(ProtocolDriver):
    """Wraps a scalar driver; adds ``execute_batch`` over span columns.

    Scalar calls (``execute``/``cost``/``finalize``/``outputs``) delegate
    to the wrapped driver, so a batched driver is a drop-in
    ``ProtocolDriver`` even on the scalar engine path.
    """

    #: ops this driver can execute batched; everything else scalar-delegates
    batch_ops: frozenset = frozenset()
    #: ops of ``batch_ops`` the engine sends through ``execute_batch`` even
    #: alone, as a group of one (where the batched path is the faster one)
    solo_ops: frozenset = frozenset()

    def __init__(self, inner: ProtocolDriver):
        self.inner = inner
        self.lane = inner.lane
        self.dtype = inner.dtype
        self.name = f"{inner.name}+batched"

    @property
    def outputs(self) -> dict:
        return getattr(self.inner, "outputs", {})

    def execute(self, op: Op, imm: tuple, outs, ins) -> None:
        self.inner.execute(op, imm, outs, ins)

    def cost(self, instr: Instr) -> float:
        return self.inner.cost(instr)

    def finalize(self) -> None:
        self.inner.finalize()

    def execute_batch(self, op: Op, imm: tuple, out_idx: list[SpanCol],
                      in_idx: list[SpanCol], memory: np.ndarray) -> None:
        raise NotImplementedError


def make_batched(driver: ProtocolDriver) -> Any:
    """Wrap ``driver`` in its protocol's batched driver, if one exists.

    Unknown driver types pass through unchanged — the engine only takes
    the batched fast path when the driver actually has ``execute_batch``,
    so exotic drivers silently keep scalar semantics.
    """
    from ..protocols.ckks.driver import CkksDriver
    from ..protocols.garbled.driver import _GCDriverBase, PlaintextDriver
    from ..protocols.shamir.driver import ShamirDriver
    from .batched_ckks import BatchedCkksDriver
    from .batched_gc import BatchedGCDriver, BatchedPlaintextDriver
    from .batched_shamir import BatchedShamirDriver
    if isinstance(driver, PlaintextDriver):
        return BatchedPlaintextDriver(driver)
    if isinstance(driver, _GCDriverBase):
        return BatchedGCDriver(driver)
    if isinstance(driver, CkksDriver):
        return BatchedCkksDriver(driver)
    if isinstance(driver, ShamirDriver):
        return BatchedShamirDriver(driver)
    return driver
