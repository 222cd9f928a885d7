"""Pure-jnp oracle for the half-gates garbling kernel.

Table-based AES-128 (S-box via jnp.take) over uint32-packed labels — an
independent implementation path from the Pallas kernel's constant-time
GF(2^8)-inversion S-box.  Both must agree bit-exactly with each other and
with the numpy driver implementation (protocols/garbled/aes.py), which is
itself checked against the FIPS-197 vector.

Label layout here is (m, 4) uint32 little-endian (lane 0 = bits 0..31).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ...protocols.garbled.aes import ROUND_KEYS, SBOX, SHIFT_ROWS

# host constants: importing this module must not touch a device
_SBOX = SBOX.astype(np.int32)
_SHIFT_ROWS = SHIFT_ROWS
# round keys as (11, 16) int32 byte values
_RK = ROUND_KEYS.astype(np.int32)


def labels_to_bytes(lbl: jnp.ndarray) -> jnp.ndarray:
    """(m, 4) uint32 -> (m, 16) int32 bytes, little-endian."""
    l32 = lbl.astype(jnp.uint32)
    parts = [((l32[:, i // 4] >> jnp.uint32(8 * (i % 4)))
              & jnp.uint32(0xFF)).astype(jnp.int32) for i in range(16)]
    return jnp.stack(parts, axis=1)


def bytes_to_labels(b: jnp.ndarray) -> jnp.ndarray:
    """(m, 16) int32 bytes -> (m, 4) uint32."""
    b = b.astype(jnp.uint32)
    lanes = []
    for w in range(4):
        lane = (b[:, 4 * w] | (b[:, 4 * w + 1] << jnp.uint32(8))
                | (b[:, 4 * w + 2] << jnp.uint32(16))
                | (b[:, 4 * w + 3] << jnp.uint32(24)))
        lanes.append(lane)
    return jnp.stack(lanes, axis=1)


def _xtime(b: jnp.ndarray) -> jnp.ndarray:
    return ((b << 1) ^ jnp.where(b & 0x80 != 0, 0x1B, 0)) & 0xFF


def aes128(blocks: jnp.ndarray) -> jnp.ndarray:
    """(m, 16) int32 byte state -> encrypted (m, 16) int32."""
    s = blocks ^ _RK[0]
    for rnd in range(1, 10):
        s = jnp.take(_SBOX, s, axis=0)
        s = s[:, _SHIFT_ROWS]
        v = s.reshape(-1, 4, 4)
        x = _xtime(v)
        r1 = jnp.roll(v, -1, axis=2)
        r2 = jnp.roll(v, -2, axis=2)
        r3 = jnp.roll(v, -3, axis=2)
        s = (x ^ r1 ^ _xtime(r1) ^ r2 ^ r3).reshape(-1, 16) ^ _RK[rnd]
    s = jnp.take(_SBOX, s, axis=0)
    s = s[:, _SHIFT_ROWS]
    return s ^ _RK[10]


def gf128_double(lbl: jnp.ndarray) -> jnp.ndarray:
    """x -> 2x in GF(2^128), (m, 4) uint32 little-endian lanes."""
    l = lbl.astype(jnp.uint32)
    carry_top = l[:, 3] >> jnp.uint32(31)
    out = []
    prev = jnp.zeros_like(l[:, 0])
    for i in range(4):
        cur = (l[:, i] << jnp.uint32(1)) | prev
        prev = l[:, i] >> jnp.uint32(31)
        out.append(cur)
    out[0] = out[0] ^ (carry_top * jnp.uint32(0x87))
    return jnp.stack(out, axis=1)


def hash_labels(lbl: jnp.ndarray, gate_ids: jnp.ndarray) -> jnp.ndarray:
    """H(x, i) = AES_k(2x ^ i) ^ (2x ^ i); gate_ids (m,) int32 -> lane 0."""
    y = gf128_double(lbl)
    y = y.at[:, 0].set(y[:, 0] ^ gate_ids.astype(jnp.uint32))
    enc = aes128(labels_to_bytes(y))
    return bytes_to_labels(enc) ^ y


def _maskw(bits: jnp.ndarray, lbl: jnp.ndarray) -> jnp.ndarray:
    return jnp.where((bits != 0)[:, None], lbl, jnp.uint32(0))


def garble_and(a0: jnp.ndarray, b0: jnp.ndarray, r: jnp.ndarray,
               gid0: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Half-gates garbling (ZRE15).  a0/b0: (m,4) uint32 zero labels;
    r: (4,) global offset.  Returns (c0 (m,4), tables (m,8) [TG|TE])."""
    m = a0.shape[0]
    j0 = gid0 + 2 * jnp.arange(m, dtype=jnp.int32)
    j1 = j0 + 1
    pa = a0[:, 0] & jnp.uint32(1)
    pb = b0[:, 0] & jnp.uint32(1)
    rr = jnp.broadcast_to(r, (m, 4))
    ha0 = hash_labels(a0, j0)
    ha1 = hash_labels(a0 ^ rr, j0)
    hb0 = hash_labels(b0, j1)
    hb1 = hash_labels(b0 ^ rr, j1)
    tg = ha0 ^ ha1 ^ _maskw(pb, rr)
    wg = ha0 ^ _maskw(pa, tg)
    te = hb0 ^ hb1 ^ a0
    we = hb0 ^ _maskw(pb, te ^ a0)
    return wg ^ we, jnp.concatenate([tg, te], axis=1)


def eval_and(wa: jnp.ndarray, wb: jnp.ndarray, tables: jnp.ndarray,
             gid0: int) -> jnp.ndarray:
    """Half-gates evaluation: active labels + tables -> active out label."""
    m = wa.shape[0]
    j0 = gid0 + 2 * jnp.arange(m, dtype=jnp.int32)
    j1 = j0 + 1
    sa = wa[:, 0] & jnp.uint32(1)
    sb = wb[:, 0] & jnp.uint32(1)
    tg, te = tables[:, :4], tables[:, 4:]
    wg = hash_labels(wa, j0) ^ _maskw(sa, tg)
    we = hash_labels(wb, j1) ^ _maskw(sb, te ^ wa)
    return wg ^ we
