"""Compile-only checks of the main-path Pallas kernels for a TPU v5e chip
that is described, not attached: the chip's compiler refuses here what
interpret mode lets through (unaligned blocks, scatters, unsupported shape
casts, too much VMEM).  Shapes are those ``chip_smoke.py`` runs.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  All such tests live in this one file."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.exec import batched_ckks
from repro.kernels.garble import kernel as gk
from repro.kernels.ntt import kernel as nk
from repro.protocols.ckks.params import CkksParams

GATES = 65536
NTT_BLOCK = nk.BLOCK_B     # chip_smoke's batch pads to one block


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # executables for a described chip cannot be read back from the
    # persistent cache; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, args, one_chip, **kw) -> str:
    shaped = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
              for shape, dtype in args]
    return fn.lower(*shaped, interpret=False, **kw).compile().as_text()


U32 = jnp.uint32


@pytest.mark.parametrize("which", ["garble", "eval"])
def test_garble_kernels_compile(one_chip, which):
    labels = ((GATES, 4), U32)
    word = ((), U32)
    if which == "garble":
        fn, args = gk.garble_and_pallas, [labels, labels, ((4,), U32),
                                          word, word]
    else:
        fn, args = gk.eval_and_pallas, [labels, labels, ((GATES, 8), U32),
                                        word, word]
    assert "tpu_custom_call" in _compile_text(fn, args, one_chip)


@pytest.mark.parametrize("n", [4096, 8192])
@pytest.mark.parametrize("inverse", [False, True])
def test_ntt_kernel_compiles(one_chip, n, inverse):
    q = CkksParams(n_ring=n).primes[0]
    text = _compile_text(nk.ntt_pallas, [((NTT_BLOCK, n), U32), ((n,), U32)],
                         one_chip, q=q, inverse=inverse, n_inv=1)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [4096, 8192])
def test_pointwise_kernel_compiles(one_chip, n):
    q = CkksParams(n_ring=n).primes[0]
    text = _compile_text(nk.pointwise_mul_pallas,
                         [((NTT_BLOCK, n), U32), ((NTT_BLOCK, n), U32)],
                         one_chip, q=q)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("count", [1, 58])
def test_mul_chain_shapes_compile(one_chip, count):
    # the batched multiply's device chain at ring 4096: the stacked forward
    # and inverse launches and the product step, for a lone multiply and
    # for the group of 58 of the resident n_rmatmul plan (chains of 16)
    n = 4096
    q = CkksParams(n_ring=n).primes[0]
    m = batched_ckks.stacked_rows(count)
    rows_in = (4 * m, n)
    rows_out = jax.eval_shape(
        lambda f: batched_ckks.tensor_products(f, q=q, m=m, interpret=True),
        jax.ShapeDtypeStruct(rows_in, U32)).shape
    assert rows_out == rows_in
    texts = [
        _compile_text(nk.ntt_pallas, [(rows_in, U32), ((n,), U32)],
                      one_chip, q=q),
        _compile_text(batched_ckks.tensor_products, [(rows_in, U32)],
                      one_chip, q=q, m=m),
        _compile_text(nk.ntt_pallas, [(rows_out, U32), ((n,), U32)],
                      one_chip, q=q, inverse=True, n_inv=1),
    ]
    assert all("tpu_custom_call" in t for t in texts)
