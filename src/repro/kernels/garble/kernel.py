"""Pallas TPU kernel: batched half-gates garbling / evaluation.

TPU adaptation of the paper's fixed-key-AES hot loop (§7.3): instead of the
CPU-idiomatic table-lookup S-box (random gathers are hostile to the VPU),
SubBytes is computed as a CONSTANT-TIME GF(2^8) inversion — x^254 as a
product of repeated squares, all carry-less multiplies — all branch-free
bitwise ops on uint32 lanes.  Lookup-free crypto is also oblivious at the instruction level,
which matches the paper's thesis that SC execution has data-independent
behavior.

Layout: a 128-bit label is four little-endian uint32 words, and those four
words are exactly the four columns of the AES state (byte ``4c + r`` of the
block is row ``r`` of column ``c``).  So the kernel keeps each label word as
its own (rows, 128) plane with one gate per lane, and runs AES SWAR-style,
four state bytes per uint32: ShiftRows is a byte-mask blend of the four
column planes and MixColumns a byte rotation within each plane — no
gathers, no per-byte reshapes.  All four hashes of a half-gate are stacked
on the sublane axis and share ONE AES pass.  The grid streams gate blocks
HBM->VMEM exactly like MAGE streams pages: the BlockSpec index maps are the
(fully static) memory program.

The tweak is the full 64-bit gate id of the numpy gates
(``protocols.garbled.gates``): its low and high words arrive as scalar
prefetch and carry across the block's lanes, so the kernel and the host
gates agree at any gate count.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...protocols.garbled.aes import ROUND_KEYS

LANES = 128
BLOCK_M = 1024           # gates per grid step: one (8, 128) uint32 tile

# scalar-prefetch layout: the fixed public key's 44 round-key words
# (little-endian columns), then the first tweak's (low, high) words, then
# the garbler's global offset R
_RK_WORDS = tuple(int(w) for w in ROUND_KEYS.astype(np.uint8).view("<u4")
                  .ravel())
_GID = len(_RK_WORDS)
_R = _GID + 2


def _rep(byte: int) -> jnp.ndarray:
    """``byte`` replicated into all four bytes of a uint32 constant."""
    return jnp.uint32(byte * 0x01010101)


# ---------------------------------------------------------------------------
# constant-time SWAR AES core: every op is a lane-wise uint32 op, four state
# bytes per word, so it lowers to plain VPU code
# ---------------------------------------------------------------------------


def _xtime(a):
    return ((a << 1) & _rep(0xFE)) ^ (((a >> 7) & _rep(0x01)) * jnp.uint32(0x1B))


def _gmul(a, b):
    """Carry-less GF(2^8) multiply of each byte pair, branch-free."""
    acc = jnp.zeros_like(a)
    for _ in range(8):
        acc = acc ^ (a & ((b & _rep(0x01)) * jnp.uint32(0xFF)))
        b = (b >> 1) & _rep(0x7F)
        a = _xtime(a)
    return acc


def _ginv(x):
    """x^254 = x^2 * x^4 * ... * x^128 in GF(2^8) per byte: constant-time
    inverse (0 -> 0).  A loop, not an unrolled chain, keeps the kernel
    small enough to compile quickly."""
    def step(_, c):
        sq, r = c
        sq = _gmul(sq, sq)
        return sq, _gmul(r, sq)
    return jax.lax.fori_loop(0, 7, step, (x, jnp.full_like(x, _rep(0x01))))[1]


def _sbox(x):
    """SubBytes: inversion + affine transform, no lookups."""
    inv = _ginv(x)
    res = inv ^ _rep(0x63)
    for sh in range(1, 5):
        res = res ^ ((inv << sh) & _rep((0xFF << sh) & 0xFF)) \
            ^ ((inv >> (8 - sh)) & _rep((1 << sh) - 1))
    return res


def _shift_rows(cols):
    """Row r of column c comes from column c + r: a byte-mask blend."""
    return [functools.reduce(jnp.bitwise_xor, [
        cols[(c + r) % 4] & jnp.uint32(0xFF << (8 * r)) for r in range(4)])
        for c in range(4)]


def _rotr(x, s: int):
    return (x >> s) | (x << (32 - s))


def _mix_column(x):
    # out_k = 2 b_k ^ 3 b_{k+1} ^ b_{k+2} ^ b_{k+3}; byte k+1 -> k is rotr 8
    r1 = _rotr(x, 8)
    return _xtime(x ^ r1) ^ r1 ^ _rotr(x, 16) ^ _rotr(x, 24)


def aes128_words(cols, sc_ref):
    """AES-128 on four uint32 column planes; ``sc_ref`` (SMEM) starts
    with the round-key words as int32 bit patterns."""
    def add_key(s, rnd):
        return [c ^ _splat(sc_ref[4 * rnd + i], c.shape)
                for i, c in enumerate(s)]

    def sub_shift(s):
        return _shift_rows([_sbox(c) for c in s])

    def middle(rnd, s):
        return tuple(add_key([_mix_column(c) for c in sub_shift(s)], rnd))

    s = jax.lax.fori_loop(1, 10, middle, tuple(add_key(cols, 0)))
    return add_key(sub_shift(list(s)), 10)


def _double(w):
    """x -> 2x in GF(2^128) over four little-endian word planes."""
    out = [(w[i] << 1) | ((w[i - 1] >> 31) if i else jnp.uint32(0))
           for i in range(4)]
    out[0] = out[0] ^ ((w[3] >> 31) * jnp.uint32(0x87))
    return out


def _hash(words, tweak_lo, tweak_hi, sc_ref):
    """H(x, i) = AES_k(2x ^ i) ^ (2x ^ i) with a 64-bit tweak i."""
    y = _double(words)
    y[0] = y[0] ^ tweak_lo
    y[1] = y[1] ^ tweak_hi
    return [e ^ v for e, v in zip(aes128_words(y, sc_ref), y)]


def _mask(bits, lbl):
    return jnp.where(bits != 0, lbl, jnp.uint32(0))


def _splat(scalar, shape):
    """An int32 scalar from SMEM as a uint32 plane (bit pattern kept)."""
    return jnp.full(shape, scalar, jnp.int32).astype(jnp.uint32)


def _gate_ids(lo_s, hi_s, shape, block_m: int):
    """64-bit tweaks (lo, hi) of the block's even and odd half-gate
    hashes: j0 = gid0 + 2 * gate, j1 = j0 + 1, carried across words."""
    gate = (pl.program_id(0) * block_m
            + jax.lax.broadcasted_iota(jnp.int32, shape, 0) * shape[1]
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    lo = _splat(lo_s, shape)
    j0_lo = lo + (2 * gate).astype(jnp.uint32)
    j0_hi = _splat(hi_s, shape) + (j0_lo < lo).astype(jnp.uint32)
    j1_lo = j0_lo + jnp.uint32(1)
    j1_hi = j0_hi + (j1_lo == 0).astype(jnp.uint32)
    return (j0_lo, j0_hi), (j1_lo, j1_hi)


def _stack(*planes):
    return jnp.concatenate(planes, axis=0)


# ---------------------------------------------------------------------------
# kernel bodies: refs are (words, rows, 128) blocks, one gate per lane
# ---------------------------------------------------------------------------


def _garble_kernel(sc_ref, a_ref, b_ref, c_ref, tab_ref, *, block_m: int):
    shape = a_ref.shape[1:]
    rows = shape[0]
    a0 = [a_ref[w] for w in range(4)]
    b0 = [b_ref[w] for w in range(4)]
    rr = [_splat(sc_ref[_R + w], shape) for w in range(4)]
    j0, j1 = _gate_ids(sc_ref[_GID], sc_ref[_GID + 1], shape, block_m)
    # all four hashes in ONE AES pass: rows [A0 | A1 | B0 | B1]
    h = _hash([_stack(a0[w], a0[w] ^ rr[w], b0[w], b0[w] ^ rr[w])
               for w in range(4)],
              _stack(j0[0], j0[0], j1[0], j1[0]),
              _stack(j0[1], j0[1], j1[1], j1[1]), sc_ref)
    ha0, ha1, hb0, hb1 = ([x[i * rows:(i + 1) * rows] for x in h]
                          for i in range(4))
    pa = a0[0] & jnp.uint32(1)
    pb = b0[0] & jnp.uint32(1)
    for w in range(4):
        tg = ha0[w] ^ ha1[w] ^ _mask(pb, rr[w])
        wg = ha0[w] ^ _mask(pa, tg)
        te = hb0[w] ^ hb1[w] ^ a0[w]
        we = hb0[w] ^ _mask(pb, te ^ a0[w])
        c_ref[w] = wg ^ we
        tab_ref[w] = tg
        tab_ref[4 + w] = te


def _eval_kernel(sc_ref, a_ref, b_ref, tab_ref, c_ref, *, block_m: int):
    shape = a_ref.shape[1:]
    rows = shape[0]
    wa = [a_ref[w] for w in range(4)]
    wb = [b_ref[w] for w in range(4)]
    j0, j1 = _gate_ids(sc_ref[_GID], sc_ref[_GID + 1], shape, block_m)
    h = _hash([_stack(wa[w], wb[w]) for w in range(4)],
              _stack(j0[0], j1[0]), _stack(j0[1], j1[1]), sc_ref)
    sa = wa[0] & jnp.uint32(1)
    sb = wb[0] & jnp.uint32(1)
    for w in range(4):
        wg = h[w][:rows] ^ _mask(sa, tab_ref[w])
        we = h[w][rows:] ^ _mask(sb, tab_ref[4 + w] ^ wa[w])
        c_ref[w] = wg ^ we


# ---------------------------------------------------------------------------
# pallas_call wrappers (grid over gate blocks); callers see (m, words) rows
# ---------------------------------------------------------------------------


def _lanes(block_m: int) -> int:
    return LANES if block_m % LANES == 0 else block_m


def _planes(x, lanes: int):
    """(m, words) rows -> (words, m // lanes, lanes) word planes."""
    return x.T.reshape(x.shape[1], -1, lanes)


def _rows(x):
    return x.reshape(x.shape[0], -1).T


def _scalars(gid0, gid0_hi, *rest):
    """The int32 scalar-prefetch vector: round keys, tweak words, rest."""
    words = [np.asarray(_RK_WORDS, np.uint32), gid0, gid0_hi, *rest]
    return jax.lax.bitcast_convert_type(
        jnp.concatenate([jnp.ravel(jnp.asarray(v)).astype(jnp.uint32)
                         for v in words]), jnp.int32)


def _spec(words: int, block_m: int, lanes: int) -> pl.BlockSpec:
    return pl.BlockSpec((words, block_m // lanes, lanes),
                        lambda i, sc: (0, i, 0))


@functools.partial(jax.jit, static_argnames=("interpret", "block_m"))
def garble_and_pallas(a0, b0, r, gid0, gid0_hi=0, *, interpret: bool = True,
                      block_m: int = BLOCK_M):
    """Garble m AND gates: a0/b0 (m, 4) uint32 zero labels, r (4,) the
    global offset, gate i tweaked with (gid0_hi:gid0) + 2i and + 2i + 1.
    Returns (c0 (m, 4), tables (m, 8) [TG | TE])."""
    m = a0.shape[0]
    assert m % block_m == 0, (m, block_m)
    lanes = _lanes(block_m)
    c, tab = pl.pallas_call(
        functools.partial(_garble_kernel, block_m=block_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m // block_m,),
            in_specs=[_spec(4, block_m, lanes), _spec(4, block_m, lanes)],
            out_specs=[_spec(4, block_m, lanes), _spec(8, block_m, lanes)]),
        out_shape=[
            jax.ShapeDtypeStruct((4, m // lanes, lanes), jnp.uint32),
            jax.ShapeDtypeStruct((8, m // lanes, lanes), jnp.uint32),
        ],
        interpret=interpret,
    )(_scalars(gid0, gid0_hi, r), _planes(a0, lanes), _planes(b0, lanes))
    return _rows(c), _rows(tab)


@functools.partial(jax.jit, static_argnames=("interpret", "block_m"))
def eval_and_pallas(wa, wb, tables, gid0, gid0_hi=0, *,
                    interpret: bool = True, block_m: int = BLOCK_M):
    """Evaluate m garbled AND gates: active labels (m, 4) + tables (m, 8)
    -> active output labels (m, 4); same tweaks as ``garble_and_pallas``."""
    m = wa.shape[0]
    assert m % block_m == 0, (m, block_m)
    lanes = _lanes(block_m)
    c = pl.pallas_call(
        functools.partial(_eval_kernel, block_m=block_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m // block_m,),
            in_specs=[_spec(4, block_m, lanes), _spec(4, block_m, lanes),
                      _spec(8, block_m, lanes)],
            out_specs=_spec(4, block_m, lanes)),
        out_shape=jax.ShapeDtypeStruct((4, m // lanes, lanes), jnp.uint32),
        interpret=interpret,
    )(_scalars(gid0, gid0_hi), _planes(wa, lanes), _planes(wb, lanes),
      _planes(tables, lanes))
    return _rows(c)
