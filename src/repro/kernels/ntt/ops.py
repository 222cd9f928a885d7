"""Jit'd public API for the NTT kernel: uint64 driver layout adapters.

The CKKS driver keeps polynomials as uint64 (numpy hot path); the TPU
kernel wants uint32 (q < 2^30 so coefficients fit).  Tables come from the
shared protocols/ckks/ntt.py cache, so all three implementations use the
same twiddle ordering.

Each launch counts what crosses between host and device (``repro.obs``):
``ntt.h2d_bytes`` for the padded input and the table sent, ``ntt.d2h_bytes``
for the output read back, and ``ntt.launches`` for the transforms.

``upload``, ``launch`` and ``download`` are the same transforms for a chain
that stays on the device between launches: one counted upload, launches
that read nothing back (their tables sent once per prime and ring, and
counted then), and one counted read-back at the end.
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from ... import obs
from .. import resolve_interpret
from . import kernel
from ...protocols.ckks.ntt import ntt_tables


def _pad(a: np.ndarray, block: int) -> tuple[np.ndarray, int]:
    b = a.shape[0]
    pad = (-b) % block
    if pad:
        a = np.concatenate([a, np.zeros((pad, a.shape[1]), a.dtype)])
    return a, b


def _launch(a32: np.ndarray, psis32: np.ndarray, **kw) -> np.ndarray:
    """One transform launch, read back to the host."""
    obs.count("ntt.launches", 1)
    obs.count("ntt.h2d_bytes", a32.nbytes + psis32.nbytes)
    out = np.asarray(kernel.ntt_pallas(a32, psis32, **kw))
    obs.count("ntt.d2h_bytes", out.nbytes)
    return out


@functools.lru_cache(maxsize=None)
def device_tables(q: int, n: int) -> tuple[jax.Array, jax.Array, int]:
    """(psis, psis_inv) as uint32 arrays on the device, and n^-1 mod q."""
    psis, psis_inv, n_inv = ntt_tables(q, n)
    tables = upload([psis.astype(np.uint32), psis_inv.astype(np.uint32)])
    return tables[0], tables[1], int(n_inv)


def upload(arrays: list[np.ndarray]) -> list[jax.Array]:
    """Host arrays to the device in one call."""
    obs.count("ntt.h2d_bytes", sum(a.nbytes for a in arrays))
    return jax.device_put(arrays)


def launch(a: jax.Array, q: int, *, inverse: bool = False,
           interpret: bool | None = None,
           block_b: int = 8) -> jax.Array:
    """One transform of device-resident (B, N) uint32 rows, B a multiple
    of ``block_b``; the result stays on the device."""
    with obs.span("ntt.inverse" if inverse else "ntt.forward"):
        interpret = resolve_interpret(interpret)
        psis, psis_inv, n_inv = device_tables(q, a.shape[-1])
        obs.count("ntt.launches", 1)
        if inverse:
            return kernel.ntt_pallas(a, psis_inv, q=q, inverse=True,
                                     n_inv=n_inv, interpret=interpret,
                                     block_b=block_b)
        return kernel.ntt_pallas(a, psis, q=q, interpret=interpret,
                                 block_b=block_b)


def download(arrays: list[jax.Array]) -> list[np.ndarray]:
    """Device arrays back to the host in one call."""
    out = jax.device_get(arrays)
    obs.count("ntt.d2h_bytes", sum(o.nbytes for o in out))
    return out


def ntt_forward(a_u64: np.ndarray, q: int, *, interpret: bool | None = None,
                block_b: int = 8) -> np.ndarray:
    """(B, N) uint64 coefficients -> bit-reversed NTT domain, via Pallas."""
    with obs.span("ntt.forward"):
        interpret = resolve_interpret(interpret)
        psis, _, _ = ntt_tables(q, a_u64.shape[-1])
        a32, b = _pad(a_u64.astype(np.uint32), block_b)
        out = _launch(a32, psis.astype(np.uint32), q=q,
                      interpret=interpret, block_b=block_b)
        return out[:b].astype(np.uint64)


def ntt_inverse(a_u64: np.ndarray, q: int, *, interpret: bool | None = None,
                block_b: int = 8) -> np.ndarray:
    with obs.span("ntt.inverse"):
        interpret = resolve_interpret(interpret)
        n = a_u64.shape[-1]
        _, psis_inv, n_inv = ntt_tables(q, n)
        a32, b = _pad(a_u64.astype(np.uint32), block_b)
        out = _launch(a32, psis_inv.astype(np.uint32), q=q, inverse=True,
                      n_inv=int(n_inv), interpret=interpret, block_b=block_b)
        return out[:b].astype(np.uint64)


def negacyclic_mul(a_u64: np.ndarray, b_u64: np.ndarray, q: int, *,
                   interpret: bool | None = None,
                   block_b: int = 8) -> np.ndarray:
    """Full polynomial multiply through the kernel path."""
    interpret = resolve_interpret(interpret)
    fa = ntt_forward(a_u64, q, interpret=interpret, block_b=block_b)
    fb = ntt_forward(b_u64, q, interpret=interpret, block_b=block_b)
    fa32, bb = _pad(fa.astype(np.uint32), block_b)
    fb32, _ = _pad(fb.astype(np.uint32), block_b)
    obs.count("ntt.h2d_bytes", fa32.nbytes + fb32.nbytes)
    prod = np.asarray(kernel.pointwise_mul_pallas(
        fa32, fb32, q=q, interpret=interpret, block_b=block_b))
    obs.count("ntt.d2h_bytes", prod.nbytes)
    return ntt_inverse(prod[:bb].astype(np.uint64), q,
                       interpret=interpret, block_b=block_b)
