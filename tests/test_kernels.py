"""Pallas kernels (interpret mode) vs pure-jnp oracles vs the numpy protocol
implementations — shape/dtype sweeps + truth tables."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.garble import kernel as gk, ops as gops, ref as gref
from repro.kernels.ntt import ops as nops, ref as nref
from repro.kernels.paged_attn import ops as pops, ref as pref
from repro.protocols.ckks import ntt as npntt
from repro.protocols.ckks.params import gen_primes
from repro.protocols.garbled import aes as npaes


# ---------------------------------------------------------------------------
# garble kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,block", [(32, 16), (64, 32), (128, 64)])
def test_garble_kernel_matches_ref_and_numpy(m, block):
    rng = np.random.default_rng(m)
    a64 = rng.integers(0, 2**63, (m, 2), dtype=np.uint64)
    b64 = rng.integers(0, 2**63, (m, 2), dtype=np.uint64)
    r64 = rng.integers(0, 2**63, 2, dtype=np.uint64)
    r64[0] |= 1
    a32, b32 = gops.u64_to_u32(a64), gops.u64_to_u32(b64)
    r32 = gops.u64_to_u32(r64.reshape(1, 2))[0]
    c_ref, t_ref = gref.garble_and(jnp.asarray(a32), jnp.asarray(b32),
                                   jnp.asarray(r32), 10)
    c_k, t_k = gk.garble_and_pallas(jnp.asarray(a32), jnp.asarray(b32),
                                    jnp.asarray(r32), jnp.int32(10),
                                    interpret=True, block_m=block)
    assert np.array_equal(np.asarray(c_ref), np.asarray(c_k))
    assert np.array_equal(np.asarray(t_ref), np.asarray(t_k))
    # jnp ref hash == numpy protocol hash (independent implementations)
    h_ref = gref.hash_labels(jnp.asarray(a32),
                             jnp.arange(m, dtype=jnp.int32))
    h_np = npaes.hash_labels(a64, np.arange(m, dtype=np.int64))
    assert np.array_equal(gops.u32_to_u64(np.asarray(h_ref)), h_np)


@pytest.mark.parametrize("bit_a,bit_b", [(0, 0), (0, 1), (1, 0), (1, 1)])
def test_garble_eval_kernel_truth_table(bit_a, bit_b):
    rng = np.random.default_rng(bit_a * 2 + bit_b)
    m = 32
    a64 = rng.integers(0, 2**63, (m, 2), dtype=np.uint64)
    b64 = rng.integers(0, 2**63, (m, 2), dtype=np.uint64)
    r64 = rng.integers(0, 2**63, 2, dtype=np.uint64)
    r64[0] |= 1
    c0, tab = gops.garble_and(a64, b64, r64, 0, block_m=16)
    wa = a64 ^ (r64[None] * np.uint64(bit_a))
    wb = b64 ^ (r64[None] * np.uint64(bit_b))
    wc = gops.eval_and(wa, wb, tab, 0, block_m=16)
    expect = c0 ^ (r64[None] * np.uint64(bit_a & bit_b))
    assert np.array_equal(wc, expect)


def test_garble_ops_match_driver_gates():
    from repro.protocols.garbled.gates import GarblerGates, PartyChannel
    ch = PartyChannel()
    g = GarblerGates(ch, seed=9)
    m = 64
    A0, B0 = g._fresh(m), g._fresh(m)
    C0 = g.and_(A0.copy(), B0.copy())
    tab = ch.recv("tab")
    c_ops, t_ops = gops.garble_and(A0, B0, g.R, 0, block_m=32)
    assert np.array_equal(c_ops, C0)
    assert np.array_equal(t_ops, tab)


@pytest.mark.parametrize("gid0", [(1 << 31) - 20, (1 << 32) - 3])
def test_garble_ops_carry_64bit_gate_ids(gid0):
    # the numpy gates tweak with int64 ids; past 2^31 the kernel must carry
    # the same id into the label's second word, never wrap at 32 bits
    from repro.protocols.garbled.gates import (EvaluatorGates, GarblerGates,
                                               PartyChannel)
    m = 64
    ch = PartyChannel()
    g = GarblerGates(ch, seed=3)
    g.gid = gid0
    A, B = g._fresh(m), g._fresh(m)
    C = g.and_(A.copy(), B.copy())
    tab = ch.recv("tab")
    c_ops, t_ops = gops.garble_and(A, B, g.R, gid0, block_m=32)
    assert np.array_equal(c_ops, C)
    assert np.array_equal(t_ops, tab)
    e = EvaluatorGates(ch)
    e.gid = gid0
    ch.send("tab", tab)
    wa = A ^ g.R[None]
    assert np.array_equal(gops.eval_and(wa, B, tab, gid0, block_m=32),
                          e.and_(wa, B))
    with pytest.raises(OverflowError):   # the int32 oracle refuses instead
        gops.garble_and(A, B, g.R, gid0, use_kernel=False, block_m=32)


# ---------------------------------------------------------------------------
# ntt kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,bits", [(64, 25), (64, 29), (256, 25),
                                    (256, 29), (512, 28)])
def test_ntt_kernel_sweep(n, bits):
    q = gen_primes(n, [bits])[0]
    rng = np.random.default_rng(n + bits)
    a = rng.integers(0, q, (8, n), dtype=np.uint64)
    b = rng.integers(0, q, (8, n), dtype=np.uint64)
    f_np = npntt.ntt_forward(a, q)
    assert np.array_equal(nops.ntt_forward(a, q), f_np)
    assert np.array_equal(nops.ntt_inverse(f_np, q), a)
    c_k = nops.negacyclic_mul(a, b, q)
    c_np = np.stack([npntt.negacyclic_mul(a[i], b[i], q) for i in range(8)])
    assert np.array_equal(c_k, c_np)


def test_ntt_ref_matches_numpy():
    n, q = 128, gen_primes(128, [29])[0]
    rng = np.random.default_rng(0)
    a = rng.integers(0, q, (4, n), dtype=np.uint64)
    psis, psis_inv, n_inv = npntt.ntt_tables(q, n)
    f = nref.ntt_forward(a, q, psis)
    assert np.array_equal(np.asarray(f), npntt.ntt_forward(a, q))
    back = nref.ntt_inverse(np.asarray(f), q, psis_inv, int(n_inv))
    assert np.array_equal(np.asarray(back), a)


def test_ntt_barrett_guard():
    with pytest.raises(AssertionError):
        from repro.kernels.ntt.kernel import _barrett_consts
        _barrett_consts((1 << 30) + 1)


# ---------------------------------------------------------------------------
# paged attention kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("batch,qh,kvh,hd,psz,mp", [
    (2, 8, 2, 64, 16, 4), (3, 4, 4, 32, 8, 3), (1, 16, 8, 128, 32, 2),
    (4, 2, 1, 64, 8, 5)])
def test_paged_attention_sweep(batch, qh, kvh, hd, psz, mp):
    rng = np.random.default_rng(batch * 100 + qh)
    num_pages = batch * mp + 2
    q = rng.normal(0, 1, (batch, qh, hd)).astype(np.float32)
    kp = rng.normal(0, 1, (num_pages, psz, kvh, hd)).astype(np.float32)
    vp = rng.normal(0, 1, (num_pages, psz, kvh, hd)).astype(np.float32)
    bt = rng.permutation(num_pages)[:batch * mp].reshape(batch, mp) \
        .astype(np.int32)
    sl = rng.integers(1, mp * psz + 1, batch).astype(np.int32)
    out_ref = np.asarray(pref.paged_decode_attention(q, kp, vp, bt, sl))
    out_k = np.asarray(pops.paged_decode_attention(q, kp, vp, bt, sl,
                                                   use_kernel=True))
    np.testing.assert_allclose(out_k, out_ref, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# odd shapes: the batched exec drivers hand the ops whatever group sizes
# the schedule produced — singletons, empty tails, non-block multiples
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m", [0, 1, 37])
def test_garble_ops_odd_batch(m):
    rng = np.random.default_rng(m)
    a = rng.integers(0, 2**63, (m, 2), dtype=np.uint64)
    b = rng.integers(0, 2**63, (m, 2), dtype=np.uint64)
    r = rng.integers(0, 2**63, 2, dtype=np.uint64)
    r[0] |= 1
    c0, tab = gops.garble_and(a, b, r, 5, block_m=16)
    assert c0.shape == (m, 2) and tab.shape == (m, 4)
    # evaluate all four truth-table rows against the garbler's c0
    for bit_a in (0, 1):
        for bit_b in (0, 1):
            wa = a ^ (r[None] * np.uint64(bit_a))
            wb = b ^ (r[None] * np.uint64(bit_b))
            wc = gops.eval_and(wa, wb, tab, 5, block_m=16)
            assert np.array_equal(
                wc, c0 ^ (r[None] * np.uint64(bit_a & bit_b)))


@pytest.mark.parametrize("k", [1, 3, 5])
def test_ntt_odd_batch(k):
    q = gen_primes(64, [29])[0]
    rng = np.random.default_rng(k)
    a = rng.integers(0, q, (k, 64), dtype=np.uint64)
    b = rng.integers(0, q, (k, 64), dtype=np.uint64)
    f = nops.ntt_forward(a, q)
    assert np.array_equal(nops.ntt_inverse(f, q), a)
    c = nops.negacyclic_mul(a, b, q)
    c_np = np.stack([npntt.negacyclic_mul(a[i], b[i], q) for i in range(k)])
    assert np.array_equal(c, c_np)


def test_paged_attention_single_query():
    rng = np.random.default_rng(7)
    qh, kvh, hd, psz = 4, 2, 32, 8
    q = rng.normal(0, 1, (1, qh, hd)).astype(np.float32)
    kp = rng.normal(0, 1, (2, psz, kvh, hd)).astype(np.float32)
    vp = rng.normal(0, 1, (2, psz, kvh, hd)).astype(np.float32)
    bt = np.array([[0, 1]], dtype=np.int32)
    sl = np.array([3], dtype=np.int32)   # ragged: mid-page sequence end
    out_ref = np.asarray(pref.paged_decode_attention(q, kp, vp, bt, sl))
    out_k = np.asarray(pops.paged_decode_attention(q, kp, vp, bt, sl,
                                                   use_kernel=True))
    np.testing.assert_allclose(out_k, out_ref, rtol=1e-4, atol=1e-5)


def test_paged_attention_bf16():
    rng = np.random.default_rng(0)
    batch, qh, kvh, hd, psz, mp = 2, 4, 2, 64, 16, 3
    num_pages = batch * mp
    q = rng.normal(0, 1, (batch, qh, hd)).astype(np.float32)
    kp = jnp.asarray(rng.normal(0, 1, (num_pages, psz, kvh, hd)),
                     dtype=jnp.bfloat16)
    vp = jnp.asarray(rng.normal(0, 1, (num_pages, psz, kvh, hd)),
                     dtype=jnp.bfloat16)
    bt = np.arange(num_pages).reshape(batch, mp).astype(np.int32)
    sl = np.full(batch, mp * psz, dtype=np.int32)
    out_ref = np.asarray(pref.paged_decode_attention(
        np.asarray(q), np.asarray(kp, dtype=np.float32),
        np.asarray(vp, dtype=np.float32), bt, sl))
    out_k = np.asarray(pops.paged_decode_attention(q, kp, vp, bt, sl))
    np.testing.assert_allclose(out_k, out_ref, rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# process hygiene: importing claims no device; the compile cache location
# ---------------------------------------------------------------------------


def _python(code: str, **env) -> str:
    import os
    import subprocess
    import sys
    full = {**os.environ, "PYTHONPATH": os.path.join(
        os.path.dirname(__file__), "..", "src"), **env}
    full = {k: v for k, v in full.items() if v is not None}
    return subprocess.run([sys.executable, "-c", code], env=full, check=True,
                          capture_output=True, text=True).stdout.strip()


def test_import_allocates_nothing_on_a_device():
    out = _python("import repro.api, repro.exec, jax; "
                  "print(len(jax.live_arrays()))")
    assert out == "0"


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_location(tmp_path, env_dir):
    import os
    from repro.kernels import CACHE_DIR
    given = str(tmp_path / "cache") if env_dir else None
    out = _python("import jax, repro.kernels as k; "
                  "print(k.configure_compile_cache()); "
                  "print(jax.config.jax_compilation_cache_dir)",
                  JAX_COMPILATION_CACHE_DIR=given)
    chosen, in_effect = out.splitlines()
    if given:
        assert chosen == in_effect == given   # JAX reads the variable
    else:
        root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
        assert chosen == in_effect == CACHE_DIR == \
            os.path.join(root, ".jax_cache")
