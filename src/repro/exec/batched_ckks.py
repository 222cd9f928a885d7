"""Batched CKKS driver: vectorized RNS ciphertext arithmetic over groups.

CKKS ops are pure modular arithmetic over per-prime residue planes, and the
numpy NTT (``protocols.ckks.ntt``) already vectorizes over arbitrary
leading axes — so a batch of ``count`` independent CT_ADD / CT_ADD_PLAIN /
CT_MUL_NR instructions collapses to one broadcasted expression (or one
leading-dim NTT sweep) per prime.  All primes are < 2^31, so uint64 sums
and products of residues never overflow and the batched formulas replay the
scalar ``CkksContext`` arithmetic bit for bit.

CT_MUL / CT_RELIN / INPUT / OUTPUT stay scalar: relinearization walks the
eval-key digit structure and INPUT consumes the driver RNG, both of which
are cheaper to keep on the reference path than to batch (and INPUT must
preserve RNG order anyway — the schedule builder pins it as a barrier).

With a compiled XLA backend present (``kernels.use_pallas``), CT_MUL_NR
runs on the device as one chain per group, a group of one included (the
driver's ``solo_ops``): the operands are reduced, cast to uint32 and
uploaded once; for each prime one stacked forward NTT launch of the four
input components, the four pointwise products and one modular add, and one
stacked inverse launch of the three output components; then one read-back.
The Pallas kernels are proven bitwise-identical to the numpy transform and
every step is exact mod q, so the chain replays ``CkksContext.mul_tensor``
bit for bit.  Without such a backend the numpy path below runs instead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.bytecode import Op
from ..kernels import resolve_interpret, use_pallas
from ..kernels.ntt import kernel as ntt_kernel
from ..kernels.ntt import ops as ntt_ops
from ..protocols.ckks import ntt as ntt_np
from ..protocols.ckks.driver import CkksDriver
from ..protocols.ckks.params import CkksParams
from .base import (BatchedProtocolDriver, SpanCol, gather_spans,
                   scatter_spans)


#: multiplies one chain stacks at most: 4 x 16 = 64 rows a launch
STACK = 16


def stacked_rows(count: int) -> int:
    """Rows each of the four stacked input components takes in a chain of
    a group of ``count`` multiplies: the next power of two from 2 up to
    ``STACK``, so that few shapes ever compile; a larger group runs as
    several chains."""
    return min(STACK, max(2, 1 << (count - 1).bit_length()))


@functools.partial(jax.jit, static_argnames=("q", "m", "interpret"))
def tensor_products(f, *, q: int, m: int, interpret: bool):
    """NTT-domain tensor product of stacked rows (a0, a1, b0, b1), m rows
    each: (a0 b0, a0 b1 + a1 b0, a1 b1) mod q and m rows of zeros, so
    that the inverse launch has the forward's shape."""
    n = f.shape[-1]
    f = f.reshape(4, m, n)
    a = jnp.concatenate([f[0], f[0], f[1], f[1]])
    b = jnp.concatenate([f[2], f[3], f[2], f[3]])
    p = ntt_kernel.pointwise_mul_pallas(a, b, q=q, interpret=interpret)
    p = p.reshape(4, m, n)
    s = p[1] + p[2]                    # < 2q < 2^31: no wrap
    mid = jnp.where(s >= jnp.uint32(q), s - jnp.uint32(q), s)
    return jnp.concatenate([p[0], mid, p[3], jnp.zeros((m, n), jnp.uint32)])


def mul_tensor_device(c1: np.ndarray, c2: np.ndarray,
                      primes: list[int]) -> np.ndarray:
    """(count, 2, L, N) x (count, 2, L, N) uint64 -> (count, 3, L, N) on
    the device: one upload, then for each chain of ``stacked_rows`` and
    each prime two NTT launches and one product launch, one read-back."""
    count, _, lv, n = c1.shape
    m = stacked_rows(count)
    chains = -(-count // m)
    qs = np.asarray(primes, dtype=np.uint64)[None, None, :, None]
    x = np.zeros((chains * m, 4, lv, n), dtype=np.uint32)
    x[:count] = np.concatenate([c1, c2], axis=1) % qs
    # (chain, prime) blocks of rows a0, a1, b0, b1, m rows each
    x = x.reshape(chains, m, 4, lv, n).transpose(0, 3, 2, 1, 4)
    interpret = resolve_interpret(None)
    ys = []
    for k, xk in enumerate(ntt_ops.upload(list(x.reshape(-1, 4 * m, n)))):
        qk = primes[k % lv]
        f = ntt_ops.launch(xk, qk, interpret=interpret)
        t = tensor_products(f, q=qk, m=m, interpret=interpret)
        ys.append(ntt_ops.launch(t, qk, inverse=True, interpret=interpret))
    y = np.stack(ntt_ops.download(ys)).reshape(chains, lv, 4, m, n)
    y = y[:, :, :3].transpose(0, 3, 2, 1, 4).reshape(chains * m, 3, lv, n)
    return y[:count].astype(np.uint64)


def mul_tensor_host(c1: np.ndarray, c2: np.ndarray,
                    primes: list[int]) -> np.ndarray:
    """``mul_tensor_device`` with numpy's NTT over the leading axis."""
    out = np.zeros(c1.shape[:1] + (3,) + c1.shape[2:], dtype=np.uint64)
    for j, qj in enumerate(primes):
        qq = np.uint64(qj)
        a0 = ntt_np.ntt_forward(c1[:, 0, j] % qq, qj)
        a1 = ntt_np.ntt_forward(c1[:, 1, j] % qq, qj)
        b0 = ntt_np.ntt_forward(c2[:, 0, j] % qq, qj)
        b1 = ntt_np.ntt_forward(c2[:, 1, j] % qq, qj)
        out[:, 0, j] = ntt_np.ntt_inverse((a0 * b0) % qq, qj)
        out[:, 1, j] = ntt_np.ntt_inverse(
            ((a0 * b1) % qq + (a1 * b0) % qq) % qq, qj)
        out[:, 2, j] = ntt_np.ntt_inverse((a1 * b1) % qq, qj)
    return out


def warm(params: CkksParams, schedules) -> list[int]:
    """Run the device chain once on zeros at every stacked size that the
    schedules' CT_MUL_NR groups and a lone multiply need, so that
    executing them compiles nothing.  Returns those sizes."""
    sizes = {stacked_rows(1)}
    for s in schedules:
        mul = np.asarray(s.group_op) == int(Op.CT_MUL_NR)
        sizes.update(stacked_rows(int(c)) for c in np.diff(s.bounds)[mul])
    primes = params.level_primes(params.levels)
    for m in sorted(sizes):
        zero = np.zeros((m, 2, len(primes), params.n_ring), dtype=np.uint64)
        mul_tensor_device(zero, zero, primes)
    return sorted(sizes)


class BatchedCkksDriver(BatchedProtocolDriver):
    batch_ops = frozenset({Op.COPY, Op.CT_ADD, Op.CT_ADD_PLAIN,
                           Op.CT_MUL_NR})

    def __init__(self, inner: CkksDriver):
        super().__init__(inner)
        self.p = inner.p
        self.device = use_pallas()
        # on the device a lone multiply takes the chain too
        self.solo_ops = (frozenset({Op.CT_MUL_NR}) if self.device
                         else frozenset())

    def _cts(self, memory: np.ndarray, col: SpanCol, level: int,
             ncomp: int = 2) -> np.ndarray:
        """(count, ncomp, level+1, n_ring) gathered ciphertext columns."""
        count = len(col[0])
        return gather_spans(memory, col)[:, :, 0].reshape(
            count, ncomp, level + 1, self.p.n_ring)

    def execute_batch(self, op: Op, imm: tuple, out_idx: list[SpanCol],
                      in_idx: list[SpanCol], memory: np.ndarray) -> None:
        p = self.p
        if op == Op.COPY:
            scatter_spans(memory, out_idx[0],
                          gather_spans(memory, in_idx[0]))
            return
        level = imm[0]
        primes = p.level_primes(level)
        count = len(out_idx[0][0])
        # (1, level+1, 1): broadcasts over (count, level+1, n_ring) planes
        qs = np.asarray(primes, dtype=np.uint64)[None, :, None]
        if op == Op.CT_ADD:
            nc1, nc2 = imm[1], imm[2]
            sub = bool(imm[3]) if len(imm) > 3 else False
            A = self._cts(memory, in_idx[0], level, nc1)
            B = self._cts(memory, in_idx[1], level, nc2)
            nc = max(nc1, nc2)
            out = np.zeros((count, nc, level + 1, p.n_ring),
                           dtype=np.uint64)
            for k in range(nc):
                x = A[:, k] if k < nc1 else np.uint64(0)
                y = B[:, k] if k < nc2 else np.uint64(0)
                out[:, k] = ((x + qs - y % qs) if sub else (x + y)) % qs
            scatter_spans(memory, out_idx[0],
                          out.reshape(count, -1, 1))
        elif op == Op.CT_ADD_PLAIN:
            ct = self._cts(memory, in_idx[0], level)
            # encoded plaintexts span the FULL prime chain; add uses the
            # first level+1 planes (scalar add_plain indexes per level prime)
            pt = gather_spans(memory, in_idx[1])[:, :, 0].reshape(
                count, p.levels + 1, p.n_ring)[:, :level + 1]
            out = ct.copy()
            out[:, 0] = (ct[:, 0] + pt) % qs
            scatter_spans(memory, out_idx[0],
                          out.reshape(count, -1, 1))
        elif op == Op.CT_MUL_NR:
            c1 = self._cts(memory, in_idx[0], level)
            c2 = self._cts(memory, in_idx[1], level)
            if self.device:
                out = mul_tensor_device(c1, c2, primes)
            else:
                out = mul_tensor_host(c1, c2, primes)
            scatter_spans(memory, out_idx[0],
                          out.reshape(count, -1, 1))
        else:  # pragma: no cover - engine checks batch_ops first
            raise NotImplementedError(f"batched ckks: {op}")
