"""Pallas TPU kernel: negacyclic NTT for CKKS polynomial arithmetic.

TPU adaptation (DESIGN.md §3): the modular multiply is built from 32-bit
lanes only — 16-bit limb products composed into a 64-bit (hi, lo) pair and
reduced with parameterized Barrett (mu = floor(2^2k / q), k = bitlen(q)),
so nothing needs native 64-bit multiplies.  Primes are < 2^30 (the chain
primes of protocols/ckks/params.py satisfy this).

Blocking: the grid runs over batches of polynomials; each kernel instance
holds a (BLOCK_B, N/128, 128) uint32 block plus a (log2 N, N/128, 128)
table of per-stage twiddles in VMEM and executes all log2(N) Longa–Naehrig
stages on it.  A stage pairs coefficients j and j + t: the kernel rotates
the block by t (sublanes when t is whole rows, lanes otherwise) and picks
the lower or upper butterfly output by a mask, so every stage is plain
elementwise VPU work on aligned tiles — no reshapes, no gathers.  The
whole butterfly schedule is static: the BlockSpec grid is the memory
program for streaming the polynomial batch HBM -> VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_B = 8
LANES = 128


def _mul64(a, b):
    """uint32 x uint32 -> 64-bit (hi, lo) via 16-bit limbs (TPU-native)."""
    a0 = a & jnp.uint32(0xFFFF)
    a1 = a >> jnp.uint32(16)
    b0 = b & jnp.uint32(0xFFFF)
    b1 = b >> jnp.uint32(16)
    ll = a0 * b0
    lh = a0 * b1
    hl = a1 * b0
    hh = a1 * b1
    mid = lh + hl                      # < 2^32, no wrap for a,b < 2^31
    lo = ll + ((mid & jnp.uint32(0xFFFF)) << jnp.uint32(16))
    carry = (lo < ll).astype(jnp.uint32)
    hi = hh + (mid >> jnp.uint32(16)) + carry
    return hi, lo


def _modmul(a, b, q: int, mu: int, k: int):
    """a*b mod q with Barrett; q < 2^30, k = q.bit_length(), static."""
    x_hi, x_lo = _mul64(a, b)
    t1 = (x_hi << jnp.uint32(32 - (k - 1))) | (x_lo >> jnp.uint32(k - 1))
    p_hi, p_lo = _mul64(t1, jnp.uint32(mu))
    qest = (p_hi << jnp.uint32(32 - (k + 1))) | (p_lo >> jnp.uint32(k + 1))
    _, qq_lo = _mul64(qest, jnp.uint32(q))
    r = x_lo - qq_lo                   # exact in low 32 bits (r < 3q < 2^32)
    r = jnp.where(r >= jnp.uint32(q), r - jnp.uint32(q), r)
    r = jnp.where(r >= jnp.uint32(q), r - jnp.uint32(q), r)
    return r


def _addmod(a, b, q: int):
    s = a + b
    return jnp.where(s >= jnp.uint32(q), s - jnp.uint32(q), s)


def _submod(a, b, q: int):
    return jnp.where(a >= b, a - b, a + jnp.uint32(q) - b)


def _roll(x, shift: int, axis: int):
    """jnp.roll semantics (element i moves to i + shift) with a
    non-negative static shift, as the TPU rotate wants."""
    return pltpu.roll(x, shift % x.shape[axis], axis)


def _stage(shape, t: int):
    """Butterfly distance ``t`` on a (B, rows, lanes) polynomial block:
    (lower-half mask, shift, axis).  Coefficient j sits at row j // lanes,
    lane j % lanes, so a distance of whole rows is a sublane rotate and a
    shorter one a lane rotate; both keep every tile aligned."""
    lanes = shape[2]
    axis, shift = (1, t // lanes) if t >= lanes else (2, t)
    idx = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return (idx & shift) == 0, shift, axis


def _ntt_fwd_kernel(a_ref, tw_ref, o_ref, *, q: int, mu: int, k: int,
                    n: int):
    """Cooley-Tukey stages; stage s has distance t = n >> (s + 1) and
    ``tw_ref[s]`` holds each coefficient's group twiddle."""
    v = a_ref[...]                      # (B, rows, lanes) uint32
    t, s = n // 2, 0
    while t >= 1:
        lower, shift, axis = _stage(v.shape, t)
        p = _modmul(v, tw_ref[s][None], q, mu, k)     # w * x at the x slots
        v = jnp.where(lower, _addmod(v, _roll(p, -shift, axis), q),
                      _submod(_roll(v, shift, axis), p, q))
        t, s = t // 2, s + 1
    o_ref[...] = v


def _ntt_inv_kernel(a_ref, tw_ref, o_ref, *, q: int, mu: int, k: int,
                    n: int, n_inv: int):
    """Gentleman-Sande stages; stage s has distance t = 1 << s."""
    v = a_ref[...]
    t, s = 1, 0
    while t < n:
        lower, shift, axis = _stage(v.shape, t)
        d = jnp.where(lower, _addmod(v, _roll(v, -shift, axis), q),
                      _submod(_roll(v, shift, axis), v, q))
        v = jnp.where(lower, d, _modmul(d, tw_ref[s][None], q, mu, k))
        t, s = t * 2, s + 1
    o_ref[...] = _modmul(v, jnp.full_like(v, jnp.uint32(n_inv)), q, mu, k)


def _pointwise_kernel(a_ref, b_ref, o_ref, *, q: int, mu: int, k: int):
    o_ref[...] = _modmul(a_ref[...], b_ref[...], q, mu, k)


def _barrett_consts(q: int) -> tuple[int, int]:
    k = q.bit_length()
    assert q < (1 << 30), "kernel Barrett path needs q < 2^30"
    return (1 << (2 * k)) // q, k


def _stage_twiddles(psis, n: int, inverse: bool):
    """(log2 n, n): row s gives coefficient j the twiddle of its butterfly
    group at stage s, psis[groups + j // (2t)] (Longa-Naehrig order)."""
    j = jnp.arange(n, dtype=jnp.int32)
    rows = []
    for s in range(n.bit_length() - 1):
        t = (1 << s) if inverse else n >> (s + 1)
        groups = n // (2 * t)
        rows.append(psis[groups + j // (2 * t)])
    return jnp.stack(rows)


def _tiles(n: int) -> tuple[int, int]:
    """(rows, lanes) of one length-n polynomial."""
    lanes = min(LANES, n)
    return n // lanes, lanes


@functools.partial(jax.jit,
                   static_argnames=("q", "inverse", "n_inv", "interpret",
                                    "block_b"))
def ntt_pallas(a, psis_brv, *, q: int, inverse: bool = False, n_inv: int = 0,
               interpret: bool = True, block_b: int = BLOCK_B):
    """Batched negacyclic NTT: a is (B, N) uint32, psis_brv (N,) uint32."""
    bsz, n = a.shape
    assert bsz % block_b == 0, (bsz, block_b)
    mu, k = _barrett_consts(q)
    if inverse:
        body = functools.partial(_ntt_inv_kernel, q=q, mu=mu, k=k, n=n,
                                 n_inv=n_inv)
    else:
        body = functools.partial(_ntt_fwd_kernel, q=q, mu=mu, k=k, n=n)
    rows, lanes = _tiles(n)
    stages = n.bit_length() - 1
    out = pl.pallas_call(
        body,
        grid=(bsz // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, rows, lanes), lambda i: (i, 0, 0)),
            pl.BlockSpec((stages, rows, lanes), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, rows, lanes), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, rows, lanes), jnp.uint32),
        interpret=interpret,
    )(a.reshape(bsz, rows, lanes),
      _stage_twiddles(psis_brv, n, inverse).reshape(stages, rows, lanes))
    return out.reshape(bsz, n)


@functools.partial(jax.jit, static_argnames=("q", "interpret", "block_b"))
def pointwise_mul_pallas(a, b, *, q: int, interpret: bool = True,
                         block_b: int = BLOCK_B):
    bsz, n = a.shape
    assert bsz % block_b == 0
    mu, k = _barrett_consts(q)
    rows, lanes = _tiles(n)
    spec = pl.BlockSpec((block_b, rows, lanes), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_pointwise_kernel, q=q, mu=mu, k=k),
        grid=(bsz // block_b,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((bsz, rows, lanes), jnp.uint32),
        interpret=interpret,
    )(a.reshape(bsz, rows, lanes), b.reshape(bsz, rows, lanes))
    return out.reshape(bsz, n)
