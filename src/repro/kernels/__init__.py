"""Pallas TPU kernels for the paper's compute hot-spots (+ the serving tie-in):

  garble/     batched half-gates garbling/evaluation with constant-time
              (lookup-free) AES — the fixed-key AES hot loop of §7.3
  ntt/        negacyclic NTT for CKKS polynomial arithmetic, 32-bit-limb
              Barrett modmul (no native 64-bit multiplies needed)
  paged_attn/ flash-decoding over a scalar-prefetched block table — MAGE's
              paged-KV memory program at the kernel level

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
public API + layout adapters), ref.py (pure-jnp oracle).  Validated in
interpret mode on CPU; compiled for the TPU by ``tests/test_tpu_compile.py``
and run there by ``chip_smoke.py``.

On the main path, the batched CKKS driver runs every ``CT_MUL_NR``, a
lone one included, through ``ntt/`` when :func:`use_pallas` is true.  The
batched GC driver sends groups of bare AND/OR instructions through
``garble/``, but no registered workload traces such a group yet: their
word-level ops run on the numpy gates.

Interpret-mode selection: compiled ``pallas_call`` cannot lower on the CPU
backend, so every ops.py entry point defaults ``interpret=None`` and
resolves it through :func:`resolve_interpret` — compiled when a real XLA
accelerator backend is present, interpret otherwise.  Tests that want the
interpreter pass ``interpret=True`` themselves.  A JAX that fails to
initialize raises; it never falls back to the CPU.
"""

from __future__ import annotations

import functools
import os

#: Where compiled kernels persist between runs when the environment names
#: no ``JAX_COMPILATION_CACHE_DIR``: a fixed directory of the checkout, so
#: a later run in the same tree finds them (the path is part of the key).
CACHE_DIR = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", "..", ".jax_cache"))


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``CACHE_DIR`` unless
    ``JAX_COMPILATION_CACHE_DIR`` is set (JAX then reads it itself).
    Returns the directory in effect.  Must run before the first compile."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


@functools.lru_cache(maxsize=1)
def _default_backend() -> str:
    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        configure_compile_cache()
    return backend


def use_pallas() -> bool:
    """True when compiled ``pallas_call`` can actually lower here: a
    non-CPU XLA backend is present."""
    return _default_backend() != "cpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> auto (compiled iff a real backend is present); an
    explicit bool is honored as-is."""
    return (not use_pallas()) if interpret is None else interpret


from . import garble, ntt, paged_attn  # noqa: E402

__all__ = ["CACHE_DIR", "configure_compile_cache", "garble", "ntt",
           "paged_attn", "resolve_interpret", "use_pallas"]
