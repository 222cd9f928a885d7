"""exec/ subsystem: batch-schedule structure, batched-vs-scalar digest
equality across drivers and worker counts, the schedule sidecar cache."""

import collections
import hashlib
import os
from unittest import mock

import numpy as np
import pytest

import repro.exec
from repro.api import JobSpec, Session
from repro.core.bytecode import (_IMM_OFF, _IN_OFF, _OUT_OFF, Op,
                                 iter_record_chunks, unpack_heads)
from repro.exec import (BatchedCkksDriver, batched_ckks, build_batch_schedule,
                        build_overlap_schedule)
from repro.exec.batching import _BARRIER_OPS, BatchSchedule
from repro.protocols.ckks import CkksDriver, CkksParams


def _digest(outputs) -> str:
    h = hashlib.sha256()
    for tag in sorted(outputs):
        h.update(str(tag).encode())
        h.update(np.ascontiguousarray(outputs[tag]).tobytes())
    return h.hexdigest()[:16]


def _outputs(spec: JobSpec):
    with Session(spec) as sess:
        outs = sess.execute(check=True)
        stats = sess.engine_stats
    return _digest(outs), stats


# ---------------------------------------------------------------------------
# schedule structure
# ---------------------------------------------------------------------------


def _plan_one(**kw):
    sess = Session(JobSpec(**kw))
    prog = sess.plan()[0]
    return prog, build_batch_schedule(prog, sess.spec.chunk_instrs)


def _row_spans(rec, r, n_outs, n_ins):
    spans = []
    for j in range(n_outs[r]):
        a, ln = int(rec[r, _OUT_OFF + 2 * j]), \
            int(rec[r, _OUT_OFF + 1 + 2 * j])
        if ln > 0:
            spans.append((a, ln, True))
    for j in range(n_ins[r]):
        a, ln = int(rec[r, _IN_OFF + 2 * j]), int(rec[r, _IN_OFF + 1 + 2 * j])
        if ln > 0:
            spans.append((a, ln, False))
    return spans


@pytest.mark.parametrize("kw", [
    dict(workload="sort", n=512, memory_budget=64),
    dict(workload="merge", n=256, memory_budget=64),
    dict(workload="merge", n=256, plan_mode="unbounded"),
])
def test_schedule_is_valid_topological_order(kw):
    prog, sched = _plan_one(**kw)
    sched.validate_for(prog)
    ci = 0
    covered = 0
    for start, rec, _instrs in iter_record_chunks(prog, sched.chunk_instrs):
        m = rec.shape[0]
        op, n_outs, n_ins, _ = unpack_heads(rec[:, 0])
        pos = np.full(m, -1, dtype=np.int64)   # group index per row
        for g in range(sched.chunk_groups[ci], sched.chunk_groups[ci + 1]):
            rows = sched.order[sched.bounds[g]:sched.bounds[g + 1]]
            assert np.all(pos[rows] == -1), "row scheduled twice"
            pos[rows] = g
            gop = int(sched.group_op[g])
            if gop >= 0:
                # group uniformity: shared packed word0 (op, arity,
                # float mask) and shared immediates
                assert np.all(rec[rows, 0] == rec[rows[0], 0])
                assert gop == int(op[rows[0]])
                assert np.all(rec[np.ix_(rows, range(_IMM_OFF,
                                                     _IMM_OFF + 6))]
                              == rec[rows[0], _IMM_OFF:_IMM_OFF + 6])
        assert np.all(pos >= 0), "row missing from schedule"
        covered += m
        # dependency validity: any two rows whose spans overlap must be
        # scheduled in program order (RAW, WAR and WAW all count)
        spans = [_row_spans(rec, r, n_outs, n_ins) for r in range(m)]
        for i in range(m):
            for (a1, l1, w1) in spans[i]:
                for j in range(i + 1, m):
                    if pos[j] > pos[i]:
                        continue
                    for (a2, l2, w2) in spans[j]:
                        if (w1 or w2) and a1 < a2 + l2 and a2 < a1 + l1:
                            assert pos[i] < pos[j], \
                                f"conflicting rows {i},{j} reordered"
        # barriers stay singleton-scalar in program order
        barrier = np.isin(op, list(_BARRIER_OPS))
        bpos = pos[barrier]
        assert np.all(sched.group_op[bpos] == -1)
        assert np.all(np.diff(bpos) >= 0)
        ci += 1
    assert covered == sched.n_records == len(prog.instrs)


def test_schedule_roundtrip_and_validate(tmp_path):
    prog, sched = _plan_one(workload="sort", n=512, memory_budget=64)
    p = tmp_path / "w0.batch.npz"
    sched.save(p)
    got = BatchSchedule.load(p)
    assert got.chunk_instrs == sched.chunk_instrs
    assert got.n_records == sched.n_records
    for f in ("order", "bounds", "group_op", "chunk_groups"):
        assert np.array_equal(getattr(got, f), getattr(sched, f))
    got.n_records += 1
    with pytest.raises(ValueError, match="stale sidecar"):
        got.validate_for(prog)


def test_schedule_finds_batches_on_sort():
    _, sched = _plan_one(workload="sort", n=1024, memory_budget=128)
    st = sched.stats()
    assert st["batchable_instructions"] > st["scalar_instructions"]
    assert st["max_batch"] >= 32


# ---------------------------------------------------------------------------
# batched == scalar, bitwise
# ---------------------------------------------------------------------------


def _check_equal(**kw):
    d_scalar, _ = _outputs(JobSpec(exec_backend="scalar", **kw))
    d_batched, stats = _outputs(JobSpec(exec_backend="batched", **kw))
    assert d_scalar == d_batched
    return stats


def test_batched_matches_scalar_gc_plaintext():
    stats = _check_equal(workload="sort", n=1024, memory_budget=128)
    assert sum(s.batched_instructions for s in stats) > 0
    assert sum(s.batches for s in stats) > 0


def test_batched_matches_scalar_gc_two_party():
    stats = _check_equal(workload="merge", n=128, memory_budget=32,
                         driver="gc-2party")
    # both parties batch in lockstep off the same schedule
    assert all(s.batched_instructions > 0 for s in stats)


def test_batched_matches_scalar_gc_unbounded():
    _check_equal(workload="merge", n=1024, plan_mode="unbounded")


def test_batched_matches_scalar_ckks():
    stats = _check_equal(workload="rmvmul", n=32, memory_budget=32)
    assert sum(s.batched_instructions for s in stats) > 0


def test_ckks_executes_at_the_spec_ring():
    # inputs, oracle and pages follow the spec's ring, not the N=128 default
    stats = _check_equal(workload="n_rmatmul", n=4, ckks_ring=1024,
                         memory_budget=0.4)
    assert sum(s.directives for s in stats) > 0


@pytest.mark.parametrize("ring,shift", [(128, 14), (1024, 14), (4096, 16)])
def test_ckks_page_holds_a_product_ciphertext(ring, shift):
    sess = Session(JobSpec(workload="n_rmatmul", n=2, ckks_ring=ring,
                           memory_budget=0.4))
    prog = sess.trace()[0]
    assert prog.page_shift == shift
    assert prog.page_slots >= sess.ckks_params().ct_slots(2, ncomp=3)


def test_batched_matches_scalar_two_workers_net():
    # NET_SEND/NET_RECV barriers interleave the two workers' programs;
    # the schedules must keep that traffic in program order
    for wl, n in (("rsum", 64), ("merge", 512)):
        _check_equal(workload=wl, n=n, memory_budget=32, num_workers=2)


def test_exec_backend_spec_validation():
    with pytest.raises(ValueError, match="exec_backend"):
        JobSpec(workload="sort", n=256, memory_budget=64,
                exec_backend="vector")


# ---------------------------------------------------------------------------
# CT_MUL_NR: the device chain replays mul_tensor; lone rows by solo_ops
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("count", [1, 2, 9, 33])
@pytest.mark.parametrize("path", ["numpy", "pallas"])
def test_batched_ckks_mul_matches_mul_tensor(monkeypatch, path, count):
    # "pallas": the device chain, its kernels interpreted on the CPU
    monkeypatch.setattr(batched_ckks, "use_pallas", lambda: path == "pallas")
    params = CkksParams(n_ring=128, levels=2)
    drv = BatchedCkksDriver(CkksDriver(params))
    assert drv.solo_ops == ({Op.CT_MUL_NR} if path == "pallas" else set())
    ctx, level = drv.inner.ctx, params.levels
    rng = np.random.default_rng(count)
    cts = [ctx.encrypt(ctx.encode(rng.uniform(-1, 1, params.n_ring // 2)))
           for _ in range(2 * count)]
    ct2, ct3 = cts[0].size, 3 * cts[0].size // 2
    # operands and results scattered over memory in no particular order
    slots = rng.permutation(3 * count)
    a_at, b_at, out_at = (slots[k * count:(k + 1) * count] * ct3
                          for k in range(3))
    memory = np.zeros((3 * count * ct3, 1), dtype=np.uint64)
    for i in range(count):
        memory[a_at[i]:a_at[i] + ct2, 0] = cts[i].reshape(-1)
        memory[b_at[i]:b_at[i] + ct2, 0] = cts[count + i].reshape(-1)
    drv.execute_batch(Op.CT_MUL_NR, (level,), [(out_at, ct3)],
                      [(a_at, ct2), (b_at, ct2)], memory)
    for i in range(count):
        want = ctx.mul_tensor(cts[i], cts[count + i], level)
        got = memory[out_at[i]:out_at[i] + ct3, 0].reshape(want.shape)
        assert np.array_equal(got, want), i


def _expected_counts(prog, sched, batch_ops, solo) -> tuple[int, int]:
    """(batchable_scalar, batched_instructions) of one engine's run, from
    its program and schedule alone: the rows of batched groups are batched,
    and so are the other rows of a ``solo`` op; the other rows of
    ``batch_ops`` run alone."""
    total = collections.Counter(ins.op for ins in prog.instrs)
    sizes = np.diff(sched.bounds)
    grouped = collections.Counter()
    for g in np.flatnonzero((sched.group_op >= 0) & (sizes >= 2)):
        op = Op(int(sched.group_op[g]))
        if op in batch_ops:
            grouped[op] += int(sizes[g])
    alone = {op: total[op] - grouped[op] for op in batch_ops}
    lone_solo = sum(alone[op] for op in solo)
    return (sum(alone.values()) - lone_solo,
            sum(grouped.values()) + lone_solo)


@pytest.mark.parametrize("backend", ["batched", "overlap"])
@pytest.mark.parametrize("kw,solo", [
    (dict(workload="n_rmatmul", n=4, ckks_ring=128, memory_budget=0.4),
     {Op.CT_MUL_NR}),
    (dict(workload="sort", n=512, memory_budget=64), set()),
], ids=["ckks-solo", "gc"])
def test_solo_ops_run_lone_rows_as_batches_of_one(monkeypatch, backend, kw,
                                                  solo):
    # a stub CKKS driver declares solo_ops on the CPU; GC declares none
    make, drivers = repro.exec.make_batched, []

    def declaring(drv):
        drivers.append(make(drv))
        if solo:
            drivers[-1].solo_ops = frozenset(solo)
        return drivers[-1]

    monkeypatch.setattr(repro.exec, "make_batched", declaring)
    spec = JobSpec(exec_backend=backend, **kw)
    with Session(spec) as sess:
        digest = _digest(sess.execute(check=True))
        (stats,) = sess.engine_stats
        prog = sess.plan()[0]
    build = (build_batch_schedule if backend == "batched"
             else build_overlap_schedule)
    sched = build(prog, spec.chunk_instrs)
    alone, batched = _expected_counts(prog, sched, drivers[0].batch_ops,
                                      solo)
    assert (stats.batchable_scalar, stats.batched_instructions) \
        == (alone, batched)
    if solo:
        assert batched > _expected_counts(prog, sched,
                                          drivers[0].batch_ops, set())[1]
        assert digest == _outputs(JobSpec(exec_backend="scalar", **kw))[0]


def test_prepare_runs_the_chain_once_per_plan_shape(monkeypatch):
    # as on an accelerator, with the chain's kernels interpreted here
    import repro.api
    import repro.kernels
    monkeypatch.setattr(repro.kernels, "use_pallas", lambda: True)
    monkeypatch.setattr(batched_ckks, "resolve_interpret", lambda _: True)
    monkeypatch.setattr(repro.api, "_PREPARED", set())
    run, sizes = batched_ckks.mul_tensor_device, []

    def counted(c1, c2, primes):
        sizes.append(len(c1))
        return run(c1, c2, primes)

    monkeypatch.setattr(batched_ckks, "mul_tensor_device", counted)
    spec = JobSpec(workload="n_rmatmul", n=4, ckks_ring=128,
                   memory_budget=0.4, exec_backend="batched")
    for _ in range(2):
        with Session(spec) as sess:
            sess.prepare()
            prog = sess.plan()[0]
    sched = build_batch_schedule(prog, spec.chunk_instrs)
    groups = np.diff(sched.bounds)[sched.group_op == int(Op.CT_MUL_NR)]
    assert sizes == sorted({batched_ckks.stacked_rows(int(c))
                            for c in [1, *groups]})


# ---------------------------------------------------------------------------
# sidecar cache: schedules are built once per plan, then served
# ---------------------------------------------------------------------------


def test_batch_schedule_cache_hit_and_no_rebatching(tmp_path):
    from repro.serve_daemon.cache import ArtifactCache
    cache = ArtifactCache(tmp_path / "cache")
    kw = dict(workload="sort", n=512, memory_budget=64,
              exec_backend="batched")

    with Session(JobSpec(**kw), cache=cache) as sess:
        cold = _digest(sess.execute(check=True))
        assert sess.cache_events.get("batch") == "miss"
    assert cache.stats.batch_misses == 1

    import repro.exec.batching as batching
    real_build = batching.build_batch_schedule
    calls = {"n": 0}

    def counting_build(*a, **k):
        calls["n"] += 1
        return real_build(*a, **k)

    with mock.patch.object(batching, "build_batch_schedule",
                           counting_build):
        with Session(JobSpec(**kw), cache=cache) as sess:
            hot = _digest(sess.execute(check=True))
            assert sess.cache_events.get("batch") == "hit"
    assert calls["n"] == 0, "hot submit re-built the batch schedule"
    assert cache.stats.batch_hits == 1
    assert hot == cold
    # the sidecar is a real on-disk artifact under <root>/batch/
    entries = os.listdir(tmp_path / "cache" / "batch")
    assert len(entries) == 1


def test_serve_daemon_reports_batch_cache(tmp_path):
    from repro.serve_daemon.client import serve_client
    from repro.serve_daemon.server import ServeDaemon
    daemon = ServeDaemon(tmp_path / "cache",
                         socket_path=str(tmp_path / "sock"))
    daemon.start()
    try:
        spec = JobSpec(workload="sort", n=256, memory_budget=64,
                       exec_backend="batched")
        with serve_client(daemon.address) as c:
            r1 = c.submit(spec, execute=True)
            r2 = c.submit(spec, execute=True)
            import dataclasses
            r3 = c.submit(dataclasses.replace(spec, exec_backend="scalar"),
                          execute=True)
        assert r1["ok"] and r2["ok"] and r3["ok"]
        assert r1["cache"]["batch"] == "miss"
        assert r2["cache"]["batch"] == "hit"
        assert "batch" not in r3["cache"]          # scalar never consults it
        assert r1["outputs_digest"] == r2["outputs_digest"] \
            == r3["outputs_digest"]
    finally:
        daemon.shutdown()
