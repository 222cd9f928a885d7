"""What the benchmark watches in its own process, changing nothing.

Every run records, for each job, the engine's statistics, the plan's
report and the frames each engine holds (the budget check needs them).
A traced run also records host spans around the calls into each layer,
written into the profiler's trace as ``TraceAnnotation``s so that device
idle gaps can be put down to what the host was doing, and counts the NTT
kernel's launches with their shapes, as ``chip_smoke.py`` does.
"""

from __future__ import annotations

import contextlib
import time


class Watch:
    """Installs the observers on the program's classes; ``close`` puts
    the originals back."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.executes: list[dict] = []
        self.engines: list[dict] = []
        self.spans: list[tuple[str, int, int]] = []
        self.launches: list[tuple[int, int]] = []
        self._undo: list[tuple[object, str, object]] = []

    def clear(self) -> None:
        for lst in (self.executes, self.engines, self.spans, self.launches):
            lst.clear()

    # -- patching ------------------------------------------------------------

    def _patch(self, owner, attr: str, make) -> None:
        orig = getattr(owner, attr)
        setattr(owner, attr, make(orig))
        self._undo.append((owner, attr, orig))

    def close(self) -> None:
        while self._undo:
            owner, attr, orig = self._undo.pop()
            setattr(owner, attr, orig)

    def __enter__(self) -> "Watch":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.close()

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span recorded here and in the profiler's trace."""
        from jax.profiler import TraceAnnotation
        with TraceAnnotation(name):
            t0 = time.perf_counter_ns()
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter_ns()))

    def _spanned(self, label):
        """Wrap a function so each call whose ``label`` is not None runs
        inside a span of that name."""
        def make(orig):
            def wrapped(*args, **kw):
                name = label(*args, **kw)
                if name is None:
                    return orig(*args, **kw)
                with self.span(name):
                    return orig(*args, **kw)
            return wrapped
        return make

    def install(self) -> "Watch":
        from repro.api import Session
        from repro.core.engine import Engine

        def execute(orig):
            def wrapped(sess, *args, **kw):
                out = orig(sess, *args, **kw)
                self.executes.append({"stats": list(sess.engine_stats),
                                      "reports": list(sess.plan_reports)})
                return out
            return wrapped

        def engine_init(orig):
            def wrapped(eng, program, *args, **kw):
                orig(eng, program, *args, **kw)
                psize = program.page_slots
                self.engines.append({
                    "phase": program.phase,
                    "pages": eng.memory.shape[0] // psize,
                    "prefetch_pages": int(program.prefetch_slots)})
            return wrapped

        self._patch(Session, "execute", execute)
        self._patch(Engine, "__init__", engine_init)
        return self

    def install_spans(self) -> None:
        """The traced run's spans and launch counter.  Installed after the
        kernels are warm: a kernel first compiled under these wrappers
        lowers with other source locations, so its persistent-cache key
        would differ from an untraced run's."""
        if not self.traced:
            return
        from repro.api import Session
        from repro.core.bytecode import Op
        from repro.core.engine import Engine
        from repro.exec.batched_ckks import BatchedCkksDriver
        from repro.kernels.ntt import kernel, ops
        from repro.protocols.ckks.driver import CkksDriver
        from repro.serve_daemon.server import ServeDaemon

        swaps = {Op.SWAP_IN, Op.SWAP_OUT, Op.ISSUE_SWAP_IN,
                 Op.FINISH_SWAP_IN, Op.COPY_OUT, Op.ISSUE_SWAP_OUT,
                 Op.FINISH_SWAP_OUT}
        fixed = lambda name: lambda *a, **kw: name          # noqa: E731
        self._patch(ServeDaemon, "_submit",
                    self._spanned(fixed("daemon.submit")))
        self._patch(Session, "plan", self._spanned(fixed("session.plan")))
        self._patch(Session, "execute",
                    self._spanned(fixed("engine.execute")))
        self._patch(CkksDriver, "execute", self._spanned(
            lambda drv, op, *a, **kw: "ckks." + op.name))
        self._patch(BatchedCkksDriver, "execute_batch", self._spanned(
            lambda drv, op, *a, **kw: "batched." + op.name))
        self._patch(Engine, "_exec_one", self._spanned(
            lambda eng, instr, *a, **kw:
            "storage." + instr.op.name if instr.op in swaps else None))
        self._patch(ops, "ntt_forward", self._spanned(fixed("ntt.forward")))
        self._patch(ops, "ntt_inverse", self._spanned(fixed("ntt.inverse")))

        def launch(orig):
            def counted(a, *args, **kw):
                self.launches.append(tuple(int(d) for d in a.shape))
                return orig(a, *args, **kw)
            return counted
        self._patch(kernel, "ntt_pallas", launch)
