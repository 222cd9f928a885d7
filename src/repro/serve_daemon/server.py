"""The multi-tenant serving daemon behind ``python -m repro serve``.

One long-running process owns an :class:`~.cache.ArtifactCache` and an
:class:`~.admission.AdmissionController` and answers line-delimited JSON
requests over a local socket (unix path, or TCP on localhost):

    {"op": "submit", "spec": {...}, "execute": true, ...}\\n
    {"op": "status"}\\n | {"op": "ping"}\\n | {"op": "shutdown"}\\n

Each connection is served by its own thread and may pipeline many
requests; a ``submit`` runs the staged Session pipeline with the shared
cache, so a repeated job shape is served from cached artifacts with
zero tracing and zero planning (the whole point — tracing is the
slowest §8.2 stage).  The expensive stages (planning + execution) only
run under an admission reservation sized by the job's resolved frame
count, so concurrent tenants cannot overcommit the shared frame pool.

A submit is one job: its id is drawn when the request is read, and the
daemon's stages are spans of the job (``repro.obs``): ``daemon.job`` from
the request read to the reply sent, holding ``daemon.session``,
``daemon.admit``, ``daemon.plan``, ``daemon.execute``, ``daemon.encode``
(digest and outputs as lists) and ``daemon.send`` (JSON and socket).  The
stages that the reply's ``timings`` report are timed by the same clock
readings whether the recorder is on or not.

See docs/SERVE.md for the full protocol.
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time
import traceback

from .. import obs
from ..api import (SCHEMA_VERSION, JobSpec, Session, SpecMismatchError,
                   estimate_job_resources)
from ..core.bytecode import ProgramFile, iter_record_chunks
from ..core.liveness import file_digest, records_digest
from .admission import AdmissionController, AdmissionError
from .cache import ArtifactCache

#: request fields a submit accepts (anything else is rejected — the
#: protocol is versioned via schema_version, not silently lenient)
_SUBMIT_FIELDS = {"op", "spec", "execute", "check", "queue", "timeout",
                  "use_cache", "return_outputs"}


def program_digest(p) -> str:
    """Chunk-size-independent record digest of a planned program, hex.

    Equal iff the programs are bitwise-identical record streams — the
    hot-vs-cold acceptance check of the cache."""
    if isinstance(p, ProgramFile):
        return f"{file_digest(p) & (1 << 64) - 1:016x}"
    d = 0
    for s, rec, _instrs in iter_record_chunks(p):
        d = records_digest(d, rec, s)
    return f"{d & (1 << 64) - 1:016x}"


def _outputs_digest(outputs) -> str:
    import hashlib
    import numpy as np
    h = hashlib.sha256()
    for tag in sorted(outputs):
        h.update(str(tag).encode())
        h.update(np.ascontiguousarray(outputs[tag]).tobytes())
    return h.hexdigest()[:16]


class ServeDaemon:
    """Accept loop + per-connection request threads over one cache."""

    def __init__(self, cache_dir: str | os.PathLike,
                 socket_path: str | None = None,
                 host: str = "127.0.0.1", port: int | None = None,
                 frame_pool: int = 1 << 16,
                 memory_bytes: int | None = None,
                 cache_bytes: int | None = None,
                 max_queue: int = 64,
                 plan_core: str | None = None,
                 sim_core: str | None = None):
        self.cache = ArtifactCache(cache_dir, max_bytes=cache_bytes)
        self.admission = AdmissionController(frame_pool,
                                             memory_bytes=memory_bytes,
                                             max_queue=max_queue)
        self._core_overrides = {}
        if plan_core is not None:
            self._core_overrides["plan_core"] = plan_core
        if sim_core is not None:
            self._core_overrides["sim_core"] = sim_core
        self._t0 = time.monotonic()
        self._lock = threading.Lock()
        self._jobs = {"submitted": 0, "completed": 0, "failed": 0,
                      "rejected": 0}
        self._job_seq = 0
        self._stop = threading.Event()
        if socket_path is not None:
            self._sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            if os.path.exists(socket_path):
                os.unlink(socket_path)      # stale socket from a dead daemon
            self._sock.bind(socket_path)
            self.address: str | tuple[str, int] = socket_path
        else:
            self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            self._sock.bind((host, port or 0))
            self.address = self._sock.getsockname()
        self._sock.listen(64)

    # -- lifecycle -----------------------------------------------------------

    def serve_forever(self) -> None:
        """Accept connections until :meth:`shutdown`; blocks the caller."""
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                break                      # listener closed by shutdown()
            t = threading.Thread(target=self._serve_conn, args=(conn,),
                                 daemon=True)
            t.start()
        self._sock.close()

    def start(self) -> threading.Thread:
        """Run :meth:`serve_forever` on a background thread (tests/bench)."""
        t = threading.Thread(target=self.serve_forever, daemon=True)
        t.start()
        return t

    def shutdown(self) -> None:
        self._stop.set()
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self._sock.close()
        if isinstance(self.address, str) and os.path.exists(self.address):
            os.unlink(self.address)

    # -- request handling ----------------------------------------------------

    def _serve_conn(self, conn: socket.socket) -> None:
        with conn, conn.makefile("r", encoding="utf-8") as rf:
            for line in rf:
                line = line.strip()
                if line and not self._answer(conn, line):
                    return

    def _answer(self, conn: socket.socket, line: str) -> bool:
        """Answer one request line; False once the connection is done."""
        try:
            req = json.loads(line)
            if req.get("op") == "submit":
                return self._answer_submit(conn, req)
            resp = self._dispatch(req)
        except Exception as e:         # noqa: BLE001 — protocol boundary
            resp = _failure(e)
        return self._reply(conn, resp)

    def _answer_submit(self, conn: socket.socket, req: dict) -> bool:
        """A submit, from the request read to the reply sent."""
        with self._lock:
            self._job_seq += 1
            job_id = self._job_seq
        with obs.job(job_id), obs.timed("daemon.job") as whole:
            try:
                resp = self._submit(req, job_id, whole)
            except Exception as e:     # noqa: BLE001 — protocol boundary
                resp = _failure(e)
            with obs.span("daemon.send"):
                return self._reply(conn, resp)

    def _reply(self, conn: socket.socket, resp: dict) -> bool:
        """Send one response line; False once the connection is done."""
        resp.setdefault("schema_version", SCHEMA_VERSION)
        try:
            conn.sendall((json.dumps(resp) + "\n").encode())
        except OSError:
            return False
        if resp.get("op") == "shutdown":
            self.shutdown()
            return False
        return True

    def _dispatch(self, req: dict) -> dict:
        op = req.get("op")
        if op == "ping":
            return {"ok": True, "op": "ping"}
        if op == "shutdown":
            return {"ok": True, "op": "shutdown"}
        if op == "status":
            return self.status()
        return {"ok": False, "error": f"unknown op {op!r} (expected "
                                      f"submit|status|ping|shutdown)"}

    def status(self) -> dict:
        with self._lock:
            jobs = dict(self._jobs)
        return {"ok": True, "op": "status",
                "uptime_s": time.monotonic() - self._t0,
                "jobs": jobs, "cache": self.cache.status(),
                "admission": self.admission.status()}

    def _count(self, key: str) -> None:
        with self._lock:
            self._jobs[key] += 1

    def _submit(self, req: dict, job_id: int, whole: obs.Stage) -> dict:
        """One job inside its ``daemon.job`` stage ``whole``."""
        unknown = set(req) - _SUBMIT_FIELDS
        if unknown:
            return {"ok": False,
                    "error": f"unknown submit fields {sorted(unknown)}"}
        if not isinstance(req.get("spec"), dict):
            return {"ok": False, "error": "submit needs a 'spec' object"}
        spec = JobSpec.from_dict(req["spec"])
        if self._core_overrides:
            import dataclasses
            spec = dataclasses.replace(spec, **self._core_overrides)
        self._count("submitted")
        cache = self.cache if req.get("use_cache", True) else None
        try:
            with Session(spec, cache=cache) as sess:
                with obs.span("daemon.session"):
                    frames, mem_bytes = estimate_job_resources(sess)
                try:
                    with obs.timed("daemon.admit") as admit:
                        grant = self.admission.admit(
                            frames, mem_bytes, queue=req.get("queue", True),
                            timeout=req.get("timeout"))
                except AdmissionError as e:
                    self._count("rejected")
                    return {"ok": False, "op": "submit", "job_id": job_id,
                            "rejected": True, "error": str(e)}
                with grant:
                    with obs.timed("daemon.plan") as plan:
                        planned = sess.plan()
                        sess.prepare()
                    digests = [program_digest(p) for p in planned]
                    resp = {
                        "ok": True, "op": "submit", "job_id": job_id,
                        "spec_hash": sess.spec.plan_hash(sess.workload),
                        "trace_hash": sess.spec.trace_hash(sess.workload),
                        "cache": {"trace": sess.cache_events.get(
                                      "trace", "skipped"),
                                  "plan": sess.cache_events.get(
                                      "plan", "skipped")},
                        "frames": frames,
                        "memory_estimate_bytes": mem_bytes,
                        "digests": {"plan": digests},
                        "timings": {"queued_s": admit.seconds,
                                    "plan_s": plan.seconds},
                    }
                    if req.get("execute", False):
                        with obs.timed("daemon.execute") as ex:
                            outputs = sess.execute(
                                check=req.get("check", False))
                        resp["timings"]["execute_s"] = ex.seconds
                        if sess.spec.exec_backend == "batched":
                            # batch-schedule sidecar cache outcome; only
                            # batched executes consult that cache kind
                            resp["cache"]["batch"] = \
                                sess.cache_events.get("batch", "skipped")
                        if sess.spec.exec_backend == "overlap":
                            resp["cache"]["overlap"] = \
                                sess.cache_events.get("overlap", "skipped")
                        with obs.span("daemon.encode"):
                            resp["outputs_digest"] = _outputs_digest(outputs)
                            if req.get("return_outputs", False):
                                resp["outputs"] = {
                                    str(t): v.tolist()
                                    for t, v in outputs.items()}
            resp["timings"]["total_s"] = whole.elapsed()
            self._count("completed")
            return resp
        except (SpecMismatchError, ValueError, KeyError,
                AssertionError) as e:
            self._count("failed")
            return {"ok": False, "op": "submit", "job_id": job_id,
                    "error": f"{type(e).__name__}: {e}"}


def _failure(e: Exception) -> dict:
    """The reply to a request that raised ``e``, from inside its handler."""
    return {"ok": False, "error": f"{type(e).__name__}: {e}",
            "trace": traceback.format_exc(limit=4)}
