"""Jit'd public API for the garbling kernels + uint64<->uint32 adapters.

The protocol driver stores labels as (m, 2) uint64; the TPU kernel wants
(m, 4) uint32 lanes.  ``interpret=None`` auto-selects: compiled on a real
XLA backend, interpret mode on CPU (see ``kernels.resolve_interpret``).
Gate ``i`` of a call is tweaked with ``2 * (gid0 + i)`` and ``+ 1`` as a
64-bit id, exactly as the numpy gates do.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .. import resolve_interpret
from . import kernel, ref

_U32 = (1 << 32) - 1


def u64_to_u32(lbl: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(lbl).astype("<u8").view("<u4").reshape(-1, 4)


def u32_to_u64(lbl: np.ndarray) -> np.ndarray:
    arr = np.ascontiguousarray(np.asarray(lbl))
    return arr.astype("<u4").view("<u8").reshape(-1, arr.shape[1] // 2)


def _pad(x: np.ndarray, block: int) -> tuple[np.ndarray, int]:
    m = x.shape[0]
    pad = (-m) % block
    if pad:
        x = np.concatenate([x, np.zeros((pad, *x.shape[1:]), x.dtype)])
    return x, m


def _tweak_words(gid0: int) -> tuple[np.uint32, np.uint32]:
    """The first gate's 64-bit tweak ``2 * gid0`` as (low, high) words."""
    t = 2 * int(gid0)
    return np.uint32(t & _U32), np.uint32(t >> 32)


def _ref_gid(gid0: int, m: int) -> int:
    """The jnp oracle tweaks with int32 ids; refuse ids it cannot carry."""
    if 2 * (int(gid0) + m) >= 1 << 31:
        raise OverflowError(f"gate ids up to {gid0 + m} exceed the int32 "
                            f"tweak of the reference path")
    return 2 * int(gid0)


def garble_and(a0_u64: np.ndarray, b0_u64: np.ndarray, r_u64: np.ndarray,
               gid0: int, *, use_kernel: bool = True,
               interpret: bool | None = None,
               block_m: int = kernel.BLOCK_M) -> tuple[np.ndarray, np.ndarray]:
    """Batch half-gates garble; uint64-pair API matching the driver.

    Returns (c0 (m,2) uint64, tables (m,4) uint64)."""
    if len(a0_u64) == 0:
        # empty batch: the grid would be 0 blocks, which pallas rejects
        return (np.zeros((0, 2), dtype=np.uint64),
                np.zeros((0, 4), dtype=np.uint64))
    interpret = resolve_interpret(interpret)
    a = u64_to_u32(a0_u64)
    b = u64_to_u32(b0_u64)
    r = u64_to_u32(r_u64.reshape(1, 2))[0]
    a, m = _pad(a, block_m)
    b, _ = _pad(b, block_m)
    if use_kernel:
        c, tab = kernel.garble_and_pallas(
            jnp.asarray(a), jnp.asarray(b), jnp.asarray(r),
            *_tweak_words(gid0), interpret=interpret, block_m=block_m)
    else:
        c, tab = ref.garble_and(jnp.asarray(a), jnp.asarray(b),
                                jnp.asarray(r), _ref_gid(gid0, len(a)))
    return (u32_to_u64(np.asarray(c))[:m],
            u32_to_u64(np.asarray(tab))[:m])


def eval_and(wa_u64: np.ndarray, wb_u64: np.ndarray, tables_u64: np.ndarray,
             gid0: int, *, use_kernel: bool = True,
             interpret: bool | None = None,
             block_m: int = kernel.BLOCK_M) -> np.ndarray:
    if len(wa_u64) == 0:
        return np.zeros((0, 2), dtype=np.uint64)
    interpret = resolve_interpret(interpret)
    wa = u64_to_u32(wa_u64)
    wb = u64_to_u32(wb_u64)
    tab = np.ascontiguousarray(tables_u64).astype("<u8").view("<u4") \
        .reshape(-1, 8)
    wa, m = _pad(wa, block_m)
    wb, _ = _pad(wb, block_m)
    tab, _ = _pad(tab, block_m)
    if use_kernel:
        c = kernel.eval_and_pallas(
            jnp.asarray(wa), jnp.asarray(wb), jnp.asarray(tab),
            *_tweak_words(gid0), interpret=interpret, block_m=block_m)
    else:
        c = ref.eval_and(jnp.asarray(wa), jnp.asarray(wb), jnp.asarray(tab),
                         _ref_gid(gid0, len(wa)))
    return u32_to_u64(np.asarray(c))[:m]
