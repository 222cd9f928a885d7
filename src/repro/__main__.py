"""``python -m repro``: the deployment CLI (paper §6 — ``mage plan`` then
execute; §8.2 — the scenario benchmarks).

    python -m repro plan  --workload merge -n 4096 --budget 0.25 --out job/
    python -m repro run   job/ --check [--storage memmap] [--real]
    python -m repro run   job/ --worker 1 --peers h0:9000,h1:9001 [--json o.json]
    python -m repro fabric job/ [--check] [--real] [--json merged.json]
    python -m repro bench [--tiny] [--streaming] [--json out.json]
    python -m repro serve  --cache ~/.cache/mage --socket /tmp/mage.sock
    python -m repro submit --connect /tmp/mage.sock --workload merge \
                           -n 4096 --budget 64 --execute

``plan`` writes memory-program files through the out-of-core streaming
pipeline plus a ``job.json`` manifest; the spec hash is stamped into every
program's header so ``run`` validates artifacts before executing them and
rejects stale or tampered plans (SpecMismatchError, exit code 2).

``run --worker K`` is the §5.2 deployment unit: ONE engine (global rank K =
party*num_workers + worker) against remote peers over the TCP transport
fabric; ``fabric`` launches the whole fleet as N localhost processes,
merges their outputs, and can check them against the oracle.

``serve`` runs the multi-tenant plan-cache daemon and ``submit`` sends it
jobs (docs/SERVE.md).  Every ``--json`` output is wrapped as
``{"schema_version": N, ...}``; stage cores are selected uniformly with
``--plan-core`` / ``--sim-core`` on every subcommand (``--core`` is a
deprecated alias for ``--plan-core``).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

import numpy as np

from .api import (SCHEMA_VERSION, FabricSpec, JobSpec, Session,
                  SpecMismatchError, driver_parties, run_job)
from .core.transport import TransportError, pick_free_ports
from .workloads import get as get_workload


def _parse_budget(text: str) -> int | float:
    """``12`` → 12 frames; ``0.25`` → fraction of the working set."""
    if any(c in text for c in ".eE"):
        return float(text)
    return int(text)


class _DeprecatedCore(argparse.Action):
    """``--core`` → ``--plan-core`` rename shim (kept one release)."""

    def __call__(self, parser, namespace, values, option_string=None):
        print(f"warning: {option_string} is deprecated, use --plan-core",
              file=sys.stderr)
        setattr(namespace, self.dest, values)


def _add_core_args(ap: argparse.ArgumentParser, default="array") -> None:
    """The uniform stage-core knobs every subcommand takes.

    ``default=None`` (run/serve) means "keep what the manifest/spec says"
    instead of forcing the array cores."""
    ap.add_argument("--plan-core", dest="plan_core", default=default,
                    choices=("array", "scalar"),
                    help="planner core: vectorized record arrays (default) "
                         "or the scalar reference; outputs are identical")
    ap.add_argument("--core", dest="plan_core", action=_DeprecatedCore,
                    choices=("array", "scalar"), help=argparse.SUPPRESS)
    ap.add_argument("--sim-core", dest="sim_core", default=default,
                    choices=("array", "scalar"),
                    help="timing-simulator core: vectorized record-chunk "
                         "replay (default) or the scalar reference; results "
                         "are identical (docs/SIMULATOR.md)")


def _add_cache_arg(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--cache", metavar="DIR", default=None,
                    help="artifact-cache root: reuse traced bytecode and "
                         "plans across invocations (docs/SERVE.md)")


def _add_spec_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--workload", default=None,
                    help="workload name (see repro.list_workloads())")
    ap.add_argument("-n", type=int, default=None,
                    help="problem size (default: workload default)")
    ap.add_argument("--workers", type=int, default=1,
                    help="workers per party (§5.1)")
    ap.add_argument("--budget", type=_parse_budget, default=None,
                    help="memory budget: frames (int) or working-set "
                         "fraction (float); omit for unbounded")
    ap.add_argument("--lookahead", type=int, default=10_000)
    ap.add_argument("--prefetch", type=int, default=0,
                    help="prefetch buffer pages B (0 = replacement only)")
    ap.add_argument("--policy", default="min",
                    help="eviction policy (min, min_clean, lru, fifo)")
    _add_core_args(ap)
    ap.add_argument("--mode", default=None,
                    choices=("memory", "streaming", "unbounded"),
                    help="plan mode (default: streaming for plan, "
                         "memory for exec)")
    ap.add_argument("--parallel", default="serial",
                    choices=("serial", "thread", "process"),
                    help="per-worker planning executor")
    ap.add_argument("--ckks-ring", type=int, default=None)
    ap.add_argument("--ckks-levels", type=int, default=None)
    ap.add_argument("--exec-backend", dest="exec_backend", default="scalar",
                    choices=("scalar", "batched", "overlap"),
                    help="engine backend: per-instruction reference loop, "
                         "plan-derived batched dispatch (docs/ENGINE.md), or "
                         "planned out-of-order NET overlap (docs/OVERLAP.md); "
                         "outputs are identical")


def _spec_from_args(args, default_mode: str) -> JobSpec:
    if args.workload is None:
        raise SystemExit("error: --workload is required")
    mode = args.mode or (default_mode if args.budget is not None
                         else "unbounded")
    return JobSpec(workload=args.workload, n=args.n,
                   num_workers=args.workers, memory_budget=args.budget,
                   lookahead=args.lookahead, prefetch_pages=args.prefetch,
                   policy=args.policy, plan_mode=mode,
                   plan_core=args.plan_core, sim_core=args.sim_core,
                   parallel_plan=args.parallel,
                   exec_backend=args.exec_backend,
                   ckks_ring=args.ckks_ring, ckks_levels=args.ckks_levels)


def cmd_plan(args) -> int:
    spec = _spec_from_args(args, default_mode="streaming")
    with Session(spec, cache=args.cache) as s:
        manifest = s.save_plan(args.out)
        planned = s.plan()
        for i, p in enumerate(planned):
            print(f"worker{i}: {len(p)} instructions -> "
                  f"{getattr(p, 'path', '(in-memory)')}")
        if s.cache_events:
            print(f"cache: {s.cache_events}")
    print(f"spec hash {spec.plan_hash()}; manifest: {manifest}")
    return 0


def cmd_run(args) -> int:
    transport = args.transport
    fabric = None
    if transport in ("shaped", "shaped+tcp"):
        fabric = FabricSpec(latency_s=args.latency,
                            bandwidth=args.bandwidth)
    elif args.latency or args.bandwidth:
        raise SystemExit("error: --latency/--bandwidth need "
                         "--transport shaped or shaped+tcp")
    if args.worker is not None:
        if not args.peers:
            raise SystemExit("error: --worker needs --peers host:port,... "
                             "(one address per global rank)")
        if args.check:
            raise SystemExit("error: --check needs the full outputs; a "
                             "--worker rank only holds its own (use "
                             "`python -m repro fabric` instead)")
        transport = transport or "tcp"
        fabric = FabricSpec(rank=args.worker,
                            peers=tuple(args.peers.split(",")),
                            latency_s=args.latency,
                            bandwidth=args.bandwidth)
    sess = Session.from_plan(args.jobdir, storage=args.storage,
                             driver=args.driver, transport=transport,
                             fabric=fabric)
    # core/backend knobs never change outputs (and are not plan-hashed),
    # so they may be overridden on an already-planned job
    import dataclasses
    overrides = {k: v for k, v in (("plan_core", args.plan_core),
                                   ("sim_core", args.sim_core),
                                   ("exec_backend", args.exec_backend))
                 if v is not None}
    if overrides:
        sess.spec = dataclasses.replace(sess.spec, **overrides)
    with sess:
        outputs = sess.execute(real=args.real or None, check=args.check)
    for tag in sorted(outputs):
        v = outputs[tag]
        head = ", ".join(str(x) for x in list(v.flat[:4]))
        print(f"output[{tag}]: shape={getattr(v, 'shape', ())} "
              f"[{head}{', ...' if v.size > 4 else ''}]")
    if args.json:
        _dump_outputs(args.json, outputs)
        print(f"wrote {args.json}")
    if args.check:
        print("oracle check OK")
    return 0


def _dump_outputs(path: str, outputs: dict) -> None:
    with open(path, "w") as f:
        json.dump({"schema_version": SCHEMA_VERSION,
                   "outputs": {str(tag): np.asarray(v).tolist()
                               for tag, v in outputs.items()}}, f)


def _load_outputs(path: str, protocol: str) -> dict:
    dtype = np.uint64 if protocol in ("gc", "shamir") else np.float64
    with open(path) as f:
        doc = json.load(f)
    if "schema_version" in doc:          # v1 envelope
        doc = doc["outputs"]
    return {int(tag): np.asarray(v, dtype=dtype)
            for tag, v in doc.items()}


def _tpu_visible(env: dict) -> bool:
    """Would a child process with ``env`` load the TPU library?  Decided
    without touching JAX here: the parent must not claim the chip."""
    platforms = env.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return False
    import importlib.util
    return importlib.util.find_spec("libtpu") is not None


def cmd_fabric(args) -> int:
    """Launch one `run --worker K` process per global rank on localhost."""
    with open(os.path.join(args.jobdir, "job.json")) as f:
        spec = JobSpec.from_dict(json.load(f)["spec"]).normalized()
    w = get_workload(spec.workload)
    driver = args.driver or spec.driver
    if args.real and w.protocol == "gc":
        driver = "gc-2party"
    n_ranks = driver_parties(driver) * spec.num_workers
    env = dict(os.environ)
    if n_ranks > 1 and spec.exec_backend != "scalar" and _tpu_visible(env):
        # a TPU belongs to one process: a second rank would fail on
        # libtpu's lock or hang waiting for the chip
        raise SystemExit(
            f"error: fabric would start {n_ranks} {spec.exec_backend} ranks "
            f"on one host, and each would claim the TPU; plan with "
            f"--exec-backend scalar, run one rank per host, or set "
            f"JAX_PLATFORMS=cpu")
    peers = ",".join(f"127.0.0.1:{p}" for p in pick_free_ports(n_ranks))
    print(f"fabric: {n_ranks} ranks ({driver}) over {peers}")

    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
    outputs: dict = {}
    with tempfile.TemporaryDirectory(prefix="mage_fabric_") as outdir:
        procs = []
        for rank in range(n_ranks):
            out_json = os.path.join(outdir, f"rank{rank}.json")
            cmd = [sys.executable, "-m", "repro", "run", args.jobdir,
                   "--worker", str(rank), "--peers", peers,
                   "--json", out_json]
            if driver != spec.driver:
                cmd += ["--driver", driver]
            if args.storage:
                cmd += ["--storage", args.storage]
            procs.append((rank, out_json,
                          subprocess.Popen(cmd, env=env)))
        failed = []
        try:
            for rank, _, proc in procs:
                try:
                    rc = proc.wait(timeout=args.timeout)
                except subprocess.TimeoutExpired:
                    failed.append((rank, f"timeout after {args.timeout}s"))
                    # peers block on the stuck rank's traffic: kill the
                    # whole fleet now, not after n_ranks x timeout
                    for _, _, p in procs:
                        if p.poll() is None:
                            p.kill()
                    continue
                if rc != 0:
                    failed.append((rank, rc))
        finally:
            for rank, _, proc in procs:  # don't leak ranks on error/timeout
                if proc.poll() is None:
                    proc.kill()
        if failed:
            raise SystemExit(f"error: fabric ranks failed: {failed}")
        for rank, out_json, _ in procs:
            outputs.update(_load_outputs(out_json, w.protocol))
    print(f"fabric: merged {len(outputs)} outputs from {n_ranks} ranks")
    if args.json:
        _dump_outputs(args.json, outputs)
        print(f"wrote {args.json}")
    if args.check:
        Session(spec).check(outputs)
        print("oracle check OK")
    return 0


def cmd_exec(args) -> int:
    spec = _spec_from_args(args, default_mode="memory")
    outputs = run_job(spec, real=args.real or None, check=args.check,
                      cache=args.cache)
    print(f"{len(outputs)} outputs"
          + (", oracle check OK" if args.check else ""))
    return 0


def cmd_bench(args) -> int:
    from .scenarios import (BENCH_CASES, STREAMING_CASE, SWEEP_BUDGETS,
                            SWEEP_LOOKAHEADS, TINY_BENCH_CASES,
                            TINY_STREAMING_CASE, run_bench, run_sweep)
    if args.cases:
        cases = []
        for item in args.cases.split(","):
            name, _, n = item.partition("=")
            if not name or not n.isdigit():
                raise SystemExit(
                    f"error: bad --cases entry {item!r} (want workload=n, "
                    f"e.g. merge=16384)")
            cases.append((name, int(n)))
    else:
        cases = TINY_BENCH_CASES if args.tiny else BENCH_CASES
    if args.sweep:
        budgets = tuple(float(b) for b in args.budgets.split(",")) \
            if args.budgets else SWEEP_BUDGETS
        lookaheads = tuple(int(x) for x in args.lookaheads.split(",")) \
            if args.lookaheads else SWEEP_LOOKAHEADS
        rows = run_sweep(cases=cases, budgets=budgets,
                         lookaheads=lookaheads, sim_core=args.sim_core,
                         plan_core=args.plan_core, cache_dir=args.cache)
        if args.json:
            with open(args.json, "w") as f:
                json.dump({"schema_version": SCHEMA_VERSION,
                           "benchmark": "bench_sweep",
                           "sweep": {"budgets": list(budgets),
                                     "lookaheads": list(lookaheads)},
                           "rows": rows}, f, indent=2)
            print(f"wrote {args.json}")
        return 0
    streaming_case = None
    if args.streaming or args.tiny:
        streaming_case = TINY_STREAMING_CASE if args.tiny else STREAMING_CASE
    rows = run_bench(cases=cases, budget_frac=args.budget_frac,
                     check=not args.no_check and not args.tiny,
                     streaming_case=streaming_case, sim_core=args.sim_core,
                     plan_core=args.plan_core, cache_dir=args.cache)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"schema_version": SCHEMA_VERSION, "rows": rows},
                      f, indent=2)
        print(f"wrote {args.json}")
    return 0


def _parse_drop(items) -> list[tuple[int, int]]:
    """``--drop R:c1,c2`` → [(R, c1), (R, c2)] straggler pairs."""
    out: list[tuple[int, int]] = []
    for item in items or ():
        rnd, sep, rest = item.partition(":")
        if not sep or not rnd.isdigit():
            raise SystemExit(f"error: bad --drop entry {item!r} "
                             f"(want ROUND:client,client,...)")
        for c in rest.split(","):
            if not c.isdigit():
                raise SystemExit(f"error: bad --drop client {c!r} in "
                                 f"{item!r}")
            out.append((int(rnd), int(c)))
    return out


def cmd_agg(args) -> int:
    """Secure aggregation: N input-only clients → a small compute fleet
    (docs/AGGREGATE.md)."""
    from .aggregate import AggSpec, run_aggregation, verify_aggregates
    spec = AggSpec(clients=args.clients, vec_len=args.vec_len,
                   rounds=args.rounds, servers=args.servers,
                   gateways=args.gateways, seed=args.seed,
                   max_inflight_msgs=args.max_inflight_msgs,
                   max_inflight_bytes=args.max_inflight_bytes,
                   round_timeout_s=args.round_timeout)
    transport = args.transport
    if args.rank is not None:
        if not args.peers:
            raise SystemExit("error: --rank needs --peers host:port,... "
                             "(one address per fabric rank: servers then "
                             "gateways)")
        transport = transport or "tcp"
        fabric = FabricSpec(rank=args.rank,
                            peers=tuple(args.peers.split(",")),
                            latency_s=args.latency,
                            bandwidth=args.bandwidth)
    else:
        transport = transport or "inproc"
        fabric = FabricSpec(latency_s=args.latency,
                            bandwidth=args.bandwidth)
    cache = None
    if args.cache:
        from .serve_daemon.cache import ArtifactCache
        cache = ArtifactCache(args.cache)
    res = run_aggregation(spec, transport=transport, fabric_spec=fabric,
                          cache=cache, drop=_parse_drop(args.drop))
    for r in res.rounds:
        head = ", ".join(str(int(v)) for v in r.total[:4])
        note = (f" DEGRADED ({spec.clients - len(r.survivors)} dropped)"
                if r.degraded else "")
        print(f"round {r.rnd}: {len(r.survivors)}/{spec.clients} clients, "
              f"aggregate [{head}{', ...' if len(r.total) > 4 else ''}]"
              f"{note}")
    if res.rounds:
        print(f"{res.clients_per_s:.0f} clients/s over {res.seconds:.3f}s; "
              f"plan events: {res.plan_events}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"schema_version": SCHEMA_VERSION, **res.to_doc()}, f)
        print(f"wrote {args.json}")
    if args.check and res.rounds:
        try:
            verify_aggregates(res)
        except AssertionError as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
        print("aggregate check OK")
    return 0


def cmd_serve(args) -> int:
    from .serve_daemon.server import ServeDaemon
    d = ServeDaemon(args.cache, socket_path=args.socket,
                    host=args.host, port=args.port,
                    frame_pool=args.frame_pool,
                    memory_bytes=args.memory_bytes,
                    cache_bytes=args.cache_bytes,
                    max_queue=args.max_queue,
                    plan_core=args.plan_core, sim_core=args.sim_core)
    addr = d.address if isinstance(d.address, str) \
        else f"{d.address[0]}:{d.address[1]}"
    print(f"serving on {addr} (cache: {d.cache.root}, "
          f"frame pool: {d.admission.frame_pool})", flush=True)
    try:
        d.serve_forever()
    except KeyboardInterrupt:
        d.shutdown()
    return 0


def cmd_submit(args) -> int:
    from .serve_daemon.client import ServeError, serve_client
    with serve_client(args.connect, timeout=args.timeout) as c:
        if args.status:
            resp = c.status()
        elif args.shutdown:
            resp = c.shutdown()
        else:
            spec = _spec_from_args(args, default_mode="streaming")
            try:
                resp = c.submit(spec, execute=args.execute,
                                check=args.check,
                                queue=not args.no_queue,
                                timeout=args.timeout,
                                use_cache=not args.no_cache)
            except ServeError as e:
                print(f"error: {e}", file=sys.stderr)
                return 3 if e.rejected else 1
    text = json.dumps(resp, indent=2)
    print(text)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro", description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("plan", help="plan memory programs to a directory")
    _add_spec_args(p)
    _add_cache_arg(p)
    p.add_argument("--out", required=True, help="output directory")
    p.set_defaults(fn=cmd_plan)

    p = sub.add_parser("run", help="execute a planned job directory")
    p.add_argument("jobdir")
    p.add_argument("--check", action="store_true",
                   help="verify outputs against the numpy oracle")
    p.add_argument("--real", action="store_true",
                   help="GC: run real two-party crypto")
    p.add_argument("--storage", default=None, choices=("ram", "memmap"))
    p.add_argument("--driver", default=None)
    p.add_argument("--worker", type=int, default=None, metavar="K",
                   help="distributed mode: run ONLY global rank K "
                        "(party*workers + worker) against --peers")
    p.add_argument("--peers", default=None,
                   help="comma list of host:port, one per global rank")
    p.add_argument("--transport", default=None,
                   choices=("inproc", "tcp", "shaped", "shaped+tcp"),
                   help="transport backend (default: inproc; "
                        "--worker defaults to tcp)")
    p.add_argument("--latency", type=float, default=0.0,
                   help="shaped: per-link one-way latency (s)")
    p.add_argument("--bandwidth", type=float, default=None,
                   help="shaped: per-link bandwidth (bytes/s)")
    p.add_argument("--json", metavar="PATH",
                   help="write this process's outputs as JSON")
    p.add_argument("--exec-backend", dest="exec_backend", default=None,
                   choices=("scalar", "batched", "overlap"),
                   help="override the engine backend for this run "
                        "(docs/ENGINE.md, docs/OVERLAP.md); outputs are "
                        "identical")
    _add_core_args(p, default=None)
    p.set_defaults(fn=cmd_run)

    p = sub.add_parser("fabric", help="run a planned job as an N-process "
                                      "localhost TCP fleet")
    p.add_argument("jobdir")
    p.add_argument("--check", action="store_true",
                   help="verify the merged outputs against the oracle")
    p.add_argument("--real", action="store_true",
                   help="GC: run real two-party crypto (2x the ranks)")
    p.add_argument("--storage", default=None, choices=("ram", "memmap"))
    p.add_argument("--driver", default=None)
    p.add_argument("--json", metavar="PATH",
                   help="write the merged outputs as JSON")
    p.add_argument("--timeout", type=float, default=300.0,
                   help="per-rank process timeout (s)")
    p.set_defaults(fn=cmd_fabric)

    p = sub.add_parser("exec", help="trace+plan+execute in one shot")
    _add_spec_args(p)
    _add_cache_arg(p)
    p.add_argument("--check", action="store_true")
    p.add_argument("--real", action="store_true")
    p.set_defaults(fn=cmd_exec)

    p = sub.add_parser("bench", help="drive the §8.2 scenario benchmarks")
    p.add_argument("--cases", default=None,
                   help="comma list of workload=n (default: fig8 sweep)")
    p.add_argument("--budget-frac", type=float, default=0.4)
    p.add_argument("--tiny", action="store_true",
                   help="small sizes + no claim assertions (CI smoke)")
    p.add_argument("--streaming", action="store_true",
                   help="add a past-planner-cap case via the file pipeline")
    p.add_argument("--sweep", action="store_true",
                   help="budget x lookahead grid instead of the fixed "
                        "scenario run (rows carry both knob values)")
    p.add_argument("--budgets", default=None,
                   help="comma list of budget fractions for --sweep")
    p.add_argument("--lookaheads", default=None,
                   help="comma list of planner lookaheads for --sweep")
    _add_core_args(p)
    _add_cache_arg(p)
    p.add_argument("--no-check", action="store_true")
    p.add_argument("--json", metavar="PATH",
                   help="write rows as JSON (CI artifact)")
    p.set_defaults(fn=cmd_bench)

    p = sub.add_parser("agg", help="secure aggregation: many input-only "
                                   "clients stream additive shares to a "
                                   "compute fleet (docs/AGGREGATE.md)")
    p.add_argument("--clients", type=int, required=True,
                   help="number of simulated input-only clients")
    p.add_argument("--rounds", type=int, default=1)
    p.add_argument("--vec-len", type=int, default=64,
                   help="per-client uint64 vector length")
    p.add_argument("--servers", type=int, default=2,
                   help="compute-fleet size (fabric ranks [0, S))")
    p.add_argument("--gateways", type=int, default=2,
                   help="client-side fabric endpoints (ranks [S, S+G))")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--transport", default=None,
                   choices=("inproc", "tcp", "shaped", "shaped+tcp"),
                   help="transport backend (default: inproc; "
                        "--rank defaults to tcp)")
    p.add_argument("--rank", type=int, default=None, metavar="K",
                   help="distributed mode: host ONLY fabric rank K "
                        "against --peers")
    p.add_argument("--peers", default=None,
                   help="comma list of host:port, one per fabric rank")
    p.add_argument("--latency", type=float, default=0.0,
                   help="shaped: per-link one-way latency (s)")
    p.add_argument("--bandwidth", type=float, default=None,
                   help="shaped: per-link bandwidth (bytes/s)")
    p.add_argument("--max-inflight-msgs", type=int, default=0,
                   help="per-link reorder-buffer message bound (0 = off)")
    p.add_argument("--max-inflight-bytes", type=int, default=1 << 20,
                   help="per-link reorder-buffer byte bound (backpressure)")
    p.add_argument("--round-timeout", type=float, default=30.0,
                   help="straggler timeout per round (s); late clients "
                        "degrade the round to the surviving subset")
    p.add_argument("--drop", action="append", metavar="R:c1,c2",
                   help="simulate stragglers: these clients never send in "
                        "round R (repeatable)")
    _add_cache_arg(p)
    p.add_argument("--check", action="store_true",
                   help="verify every revealed aggregate against the "
                        "oracle over its surviving subset")
    p.add_argument("--json", metavar="PATH",
                   help="write the full result envelope as JSON")
    p.set_defaults(fn=cmd_agg)

    p = sub.add_parser("serve", help="run the multi-tenant plan-cache "
                                     "daemon (docs/SERVE.md)")
    p.add_argument("--cache", required=True, metavar="DIR",
                   help="artifact-cache root the daemon owns")
    p.add_argument("--socket", default=None, metavar="PATH",
                   help="unix socket path to listen on (default: TCP)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=None,
                   help="TCP port (default: OS-assigned, printed on start)")
    p.add_argument("--frame-pool", type=int, default=1 << 16,
                   help="shared frame budget across concurrent jobs")
    p.add_argument("--memory-bytes", type=int, default=None,
                   help="optional cap on summed per-job memory estimates")
    p.add_argument("--cache-bytes", type=int, default=None,
                   help="LRU-evict cache entries beyond this many bytes")
    p.add_argument("--max-queue", type=int, default=64,
                   help="max jobs waiting for admission before rejecting")
    _add_core_args(p, default=None)
    p.set_defaults(fn=cmd_serve)

    p = sub.add_parser("submit", help="submit a job to a serve daemon")
    p.add_argument("--connect", required=True, metavar="ADDR",
                   help="daemon address: unix socket path or host:port")
    _add_spec_args(p)
    p.add_argument("--execute", action="store_true",
                   help="also execute the planned job on the daemon")
    p.add_argument("--check", action="store_true",
                   help="with --execute: verify against the oracle")
    p.add_argument("--no-queue", action="store_true",
                   help="reject (exit 3) instead of waiting for admission")
    p.add_argument("--no-cache", action="store_true",
                   help="bypass the daemon's artifact cache (cold run)")
    p.add_argument("--timeout", type=float, default=None,
                   help="admission + socket timeout (s)")
    p.add_argument("--status", action="store_true",
                   help="just print the daemon's status JSON")
    p.add_argument("--shutdown", action="store_true",
                   help="ask the daemon to shut down")
    p.add_argument("--json", metavar="PATH",
                   help="also write the response JSON here")
    p.set_defaults(fn=cmd_submit)

    args = ap.parse_args(argv)
    try:
        return args.fn(args)
    except SpecMismatchError as e:
        print(f"error: {e}", file=sys.stderr)
        raise SystemExit(2)
    except (ValueError, KeyError, TransportError) as e:
        # predictable spec/registry/fabric errors: clean CLI message,
        # not a trace
        print(f"error: {e}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    sys.exit(main())
