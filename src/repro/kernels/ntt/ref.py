"""Pure-jnp oracle for the NTT kernel: uint64 modular arithmetic, same
Longa–Naehrig stage schedule as protocols/ckks/ntt.py (the numpy engine
path) — all three implementations must agree exactly."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...protocols.ckks.ntt import ntt_tables


def ntt_forward(a, q: int, psis_brv: np.ndarray):
    """a: (..., N) uint64 standard order -> bit-reversed NTT domain."""
    with jax.enable_x64(True):
        a = jnp.asarray(np.asarray(a))
        n = a.shape[-1]
        qq = jnp.uint64(q)
        psis = jnp.asarray(psis_brv, dtype=jnp.uint64)
        v = a.astype(jnp.uint64)
        lead = v.shape[:-1]
        t = n
        m = 1
        while m < n:
            t //= 2
            w = v.reshape(*lead, m, 2, t)
            s = psis[m:2 * m].reshape((1,) * len(lead) + (m, 1))
            u = w[..., 0, :]
            x = (w[..., 1, :] * s) % qq
            v = jnp.stack([(u + x) % qq, (u + qq - x) % qq],
                          axis=-2).reshape(*lead, n)
            m *= 2
        return v


def ntt_inverse(a, q: int, psis_inv_brv: np.ndarray, n_inv: int):
    with jax.enable_x64(True):
        a = jnp.asarray(np.asarray(a))
        n = a.shape[-1]
        qq = jnp.uint64(q)
        psis = jnp.asarray(psis_inv_brv, dtype=jnp.uint64)
        v = a.astype(jnp.uint64)
        lead = v.shape[:-1]
        t = 1
        m = n
        while m > 1:
            h = m // 2
            w = v.reshape(*lead, h, 2, t)
            s = psis[h:2 * h].reshape((1,) * len(lead) + (h, 1))
            u = w[..., 0, :]
            x = w[..., 1, :]
            v = jnp.stack([(u + x) % qq, ((u + qq - x) % qq * s) % qq],
                          axis=-2).reshape(*lead, n)
            t *= 2
            m = h
        return (v * jnp.uint64(n_inv)) % qq


def pointwise_mul(a, b, q: int):
    with jax.enable_x64(True):
        a = jnp.asarray(np.asarray(a))
        b = jnp.asarray(np.asarray(b))
        return (a.astype(jnp.uint64) * b.astype(jnp.uint64)) % jnp.uint64(q)


def tables(q: int, n: int):
    return ntt_tables(q, n)
