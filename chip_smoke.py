"""Bring-up check of the main path on one TPU chip, in one process.

    python chip_smoke.py

Phases, in order; each prints its wall time and result:

  a) the device: JAX must report a TPU (no fallback to the CPU);
  b) kernel parity at real shapes: half-gates garble and eval at 65536 gates,
     and the CKKS NTT forward and inverse at N=4096 and N=8192 for every
     chain prime, each bitwise equal to the numpy protocol code;
  c) one CKKS job through ``repro.Session``, the call the CLI and the serve
     daemon make: ``n_rmatmul`` n=8 at ring N=4096 (fig8's size) with a
     memory budget under the working set, executed by the batched engine
     (NTTs on the chip), checked against the plaintext oracle and against
     the output digest of the scalar host engine.

Any failure exits non-zero.  The last line of standard output is one JSON
object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

GATES = 65536
NTT_RINGS = (4096, 8192)
NTT_BATCH = 2             # the CT_MUL_NR group size phase (c) batches
JOB = dict(workload="n_rmatmul", n=8, ckks_ring=4096, memory_budget=0.4,
           plan_mode="memory")


def check(ok: bool, what: str) -> None:
    """Fail the run (``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise SystemExit(f"chip_smoke: {what}")


def phase(name: str):
    def wrap(fn):
        def run(*args):
            t0 = time.perf_counter()
            result = fn(*args)
            print(f"[{name}] ok in {time.perf_counter() - t0:.3f} s: "
                  f"{result}", flush=True)
            return result
        return run
    return wrap


@phase("a device")
def check_device() -> dict:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev.platform!r}); refusing to fall back")
    from repro.kernels import use_pallas
    if not use_pallas():
        raise SystemExit("chip_smoke: kernels would not compile for the TPU")
    cache = jax.config.jax_compilation_cache_dir
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "compile_cache": cache}


@phase("b garble/eval parity")
def check_garble() -> str:
    from repro.kernels.garble import ops
    from repro.protocols.garbled.gates import (EvaluatorGates,
                                               GarblerGates, PartyChannel)
    rng = np.random.default_rng(11)
    a = rng.integers(0, 1 << 63, (GATES, 2), dtype=np.uint64)
    b = rng.integers(0, 1 << 63, (GATES, 2), dtype=np.uint64)
    # start just below 2^31 gates so the 64-bit tweak carries mid-batch
    gid0 = (1 << 31) - GATES // 2
    ch = PartyChannel()
    g = GarblerGates(ch, seed=12)
    g.gid = gid0
    c0_np = g.and_(a.copy(), b.copy())
    tab_np = ch.recv("tab")
    c0, tab = ops.garble_and(a, b, g.R, gid0, interpret=False)
    check(np.array_equal(c0, c0_np), "garble: output labels differ")
    check(np.array_equal(tab, tab_np), "garble: tables differ")

    bits_a = rng.integers(0, 2, GATES, dtype=np.uint64)[:, None]
    bits_b = rng.integers(0, 2, GATES, dtype=np.uint64)[:, None]
    wa = a ^ (bits_a * g.R[None])
    wb = b ^ (bits_b * g.R[None])
    e = EvaluatorGates(ch)
    e.gid = gid0
    ch.send("tab", tab_np)
    wc_np = e.and_(wa, wb)
    wc = ops.eval_and(wa, wb, tab, gid0, interpret=False)
    check(np.array_equal(wc, wc_np), "eval: labels differ from numpy")
    check(np.array_equal(wc, c0 ^ ((bits_a & bits_b) * g.R[None])),
          "eval: not the AND of the garbled inputs")
    return f"{GATES} gates bitwise equal to numpy (gid0={gid0})"


@phase("b ntt parity")
def check_ntt() -> str:
    from repro.kernels.ntt import ops
    from repro.protocols.ckks import ntt as ntt_np
    from repro.protocols.ckks.params import CkksParams
    rng = np.random.default_rng(13)
    done = []
    for n in NTT_RINGS:
        p = CkksParams(n_ring=n)
        for q in p.primes:
            a = rng.integers(0, q, (NTT_BATCH, n), dtype=np.uint64)
            f = ops.ntt_forward(a, q, interpret=False)
            check(np.array_equal(f, ntt_np.ntt_forward(a, q)),
                  f"forward NTT differs at N={n} q={q}")
            back = ops.ntt_inverse(f, q, interpret=False)
            check(np.array_equal(back, ntt_np.ntt_inverse(f, q)),
                  f"inverse NTT differs at N={n} q={q}")
            check(np.array_equal(back, a), f"round trip at N={n} q={q}")
            done.append(f"N={n}/q={q}")
    return f"batch {NTT_BATCH} bitwise equal to numpy: {', '.join(done)}"


def _digest(outputs: dict) -> str:
    h = hashlib.sha256()
    for tag in sorted(outputs):
        h.update(str(tag).encode())
        h.update(np.ascontiguousarray(outputs[tag]).tobytes())
    return h.hexdigest()[:16]


def _run_job(backend: str) -> tuple[str, object, object]:
    from repro.api import JobSpec, Session
    with Session(JobSpec(exec_backend=backend, **JOB)) as s:
        outputs = s.execute(check=True)
        return _digest(outputs), s.engine_stats[0], s.plan_reports[0]


@phase("c ckks job, batched on the chip")
def check_job_batched() -> dict:
    from repro.kernels import use_pallas
    from repro.kernels.ntt import kernel
    launch = kernel.ntt_pallas
    calls = []

    def counted(*args, **kw):       # observe the launches; change nothing
        calls.append(kw.get("interpret"))
        return launch(*args, **kw)

    kernel.ntt_pallas = counted
    try:
        digest, stats, report = _run_job("batched")
    finally:
        kernel.ntt_pallas = launch
    check(use_pallas(), "batched engine did not use the Pallas kernels")
    check(stats.batched_instructions > 0, "no instruction was batched")
    check(calls and not any(calls), "no compiled NTT ran on the chip")
    rep = report.replacement
    check(rep.swap_ins > 0, "budget did not force any swap")
    return {"digest": digest, "oracle": "ok", "ntt_launches": len(calls),
            "batched_instructions": stats.batched_instructions,
            "batches": stats.batches, "instructions": stats.instructions,
            "frames": rep.num_frames, "vpages": rep.num_vpages,
            "swap_ins": rep.swap_ins, "swap_outs": rep.swap_outs}


@phase("c ckks job, scalar host reference")
def check_job_scalar(batched: dict) -> dict:
    digest, _, _ = _run_job("scalar")
    check(digest == batched["digest"],
          f"batched digest {batched['digest']} != scalar {digest}")
    return {"digest": digest, "oracle": "ok", "matches_batched": True}


def main() -> int:
    dev = check_device()
    check_garble()
    check_ntt()
    check_job_scalar(check_job_batched())
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
