"""MAGE's execution engine (§5, §7.1).

An interpreter for memory programs: program data lives in a flat array (the
MAGE-physical address space); each instruction's operands are views into that
array; swap directives are handled by the engine itself via async I/O, and
everything else is delegated to the protocol driver.  Network directives move
spans between workers of the same party over the transport fabric
(``core.transport``): the engine addresses peers by worker id through a
:class:`~repro.core.transport.PartyView`, so the same bytecode runs over
in-process queues, localhost TCP, or a WAN-shaped link unmodified.

The engine runs programs in any phase:
  * 'virtual'  — Unbounded scenario: memory sized to the whole vspace;
  * 'physical' — replacement only (synchronous swaps);
  * 'memory'   — the full scheduled memory program (async swaps).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import numpy as np

from .. import obs
from .bytecode import (_IMM_OFF, _IN_OFF, _OUT_OFF, Instr, Op, Program,
                       ProgramFile, decode_chunk, iter_instructions,
                       iter_record_chunks, unpack_heads)
from .storage import AsyncIO, MemmapStorage, RamStorage, StorageBackend
from .transport import PartyView, TransportError


class ProtocolDriver:
    """Lower layer of the interpreter (§4.3): executes ops with the SC scheme.

    ``lane``/``dtype`` define the engine array's slot layout; e.g. the garbled
    circuit driver uses lane=2, uint64 (one 128-bit wire label per slot).
    Drivers must keep all state *inside the spans* they are handed — no
    pointers to driver-owned memory may live in the array (§7.1), which is
    what makes engine-level swapping sound.
    """

    lane: int = 1
    dtype: Any = np.uint64
    name: str = "abstract"

    def execute(self, op: Op, imm: tuple, outs: list[np.ndarray],
                ins: list[np.ndarray]) -> None:
        raise NotImplementedError

    def cost(self, instr: Instr) -> float:
        """Estimated compute seconds (feeds the timing simulator)."""
        raise NotImplementedError

    def finalize(self) -> None:
        pass


@dataclasses.dataclass
class EngineStats:
    instructions: int = 0
    directives: int = 0
    io_read_bytes: int = 0
    io_write_bytes: int = 0
    net_messages: int = 0
    net_sent_bytes: int = 0
    net_recv_bytes: int = 0
    #: instructions executed through driver.execute_batch (exec/ backend)
    batched_instructions: int = 0
    #: number of execute_batch calls those instructions collapsed into
    batches: int = 0
    #: instructions whose op is in the driver's batch_ops that still ran
    #: one by one (singleton or scalar groups of the batched/overlap loops)
    batchable_scalar: int = 0
    #: NET_RECVs posted as deferred completion handles (overlap backend)
    posted_recvs: int = 0
    #: peak simultaneously outstanding recv handles (overlap backend)
    max_inflight_recvs: int = 0
    #: per-link totals, (src_worker, dst_worker) -> [messages, bytes]; a key
    #: with src == this worker is outgoing traffic, dst == this worker
    #: incoming.  Counted by the engine thread itself (thread-confined, so
    #: no races even when many engines share one transport).
    net_links: dict = dataclasses.field(default_factory=dict)

    def _net_count(self, src: int, dst: int, nbytes: int) -> None:
        link = self.net_links.setdefault((src, dst), [0, 0])
        link[0] += 1
        link[1] += nbytes


#: swap directives: executed by the engine itself, against its storage
_SWAPS = frozenset({Op.SWAP_IN, Op.SWAP_OUT, Op.ISSUE_SWAP_IN,
                    Op.FINISH_SWAP_IN, Op.COPY_OUT, Op.ISSUE_SWAP_OUT,
                    Op.FINISH_SWAP_OUT})
_WAIT = "storage.wait"


@functools.lru_cache(maxsize=None)
def op_spans(prefix: str) -> dict[Op, str]:
    """Span names ``<prefix>.<OP>`` of every op, built once per prefix."""
    return {op: f"{prefix}.{op.name}" for op in Op}


_STORAGE_SPANS = op_spans("storage")
_BATCHED_SPANS = op_spans("batched")


class Engine:
    """Interprets a memory program — in-memory ``Program`` or on-disk
    ``ProgramFile``.  With a ProgramFile the engine is a *streaming
    executor*: instructions are decoded chunk-by-chunk straight from the
    file, so executing a paper-scale memory program costs O(chunk) planner-
    side memory on top of the engine's own frames (§7.1)."""

    def __init__(self, program: Program | ProgramFile, driver: ProtocolDriver,
                 storage: StorageBackend | None = None,
                 net: PartyView | None = None,
                 io_threads: int = 2,
                 use_memmap: bool = False,
                 batch_schedule: Any = None,
                 overlap_schedule: Any = None):
        self.prog = program
        self.driver = driver
        self.batch_schedule = batch_schedule
        self.overlap_schedule = overlap_schedule
        psize = program.page_slots
        page_shape = (psize, driver.lane)
        if program.phase == "virtual":
            n_slots = max(program.vspace_slots, 1)
        else:
            n_slots = max(program.num_frames, 1) * psize
        self.memory = np.zeros((n_slots, driver.lane), dtype=driver.dtype)
        B = program.prefetch_slots
        self.pf = np.zeros((max(B, 1), psize, driver.lane), dtype=driver.dtype)
        if storage is None:
            storage = (MemmapStorage(page_shape, driver.dtype) if use_memmap
                       else RamStorage(page_shape, driver.dtype))
        self.io = AsyncIO(storage, threads=io_threads)
        self.net = net
        self._slot_future: dict[int, Any] = {}
        self.stats = EngineStats()
        self._page_shape = page_shape
        # a batched driver runs its scalar calls on the driver it wraps
        self._drv_spans = op_spans(getattr(driver, "inner", driver).name)
        self._solo_ops = getattr(driver, "solo_ops", frozenset())

    # -- helpers ---------------------------------------------------------------

    def _view(self, span) -> np.ndarray:
        addr, n = span
        return self.memory[addr:addr + n]

    def _frame_page(self, span) -> np.ndarray:
        # a directive frame span always covers exactly one page
        return self._view(span)

    def _wait_slot(self, slot: int) -> None:
        fut = self._slot_future.pop(slot, None)
        if fut is None:
            return
        if fut.done():
            fut.result()
        else:
            with obs.span(_WAIT):
                fut.result()

    def _instructions(self):
        return iter_instructions(self.prog)

    def _net(self) -> PartyView:
        if self.net is None:
            raise TransportError(
                "program has NET_* directives but the engine has no fabric "
                "attached (pass net=PartyView(...))")
        return self.net

    # -- main loop ---------------------------------------------------------------

    def run(self, on_output: Callable[[Instr, list[np.ndarray]], None] | None = None
            ) -> EngineStats:
        # try/finally: a mid-run driver/storage exception must not leak the
        # AsyncIO thread pool or an open (possibly temp-file) backend.
        try:
            with obs.span("engine.run"):
                if self.overlap_schedule is not None:
                    self._run_loop_overlap(on_output)
                elif self.batch_schedule is not None \
                        and hasattr(self.driver, "execute_batch"):
                    self._run_loop_batched(on_output)
                else:
                    self._run_loop(on_output)
        finally:
            self.stats.io_read_bytes = self.io.bytes_read
            self.stats.io_write_bytes = self.io.bytes_written
            self.io.close()
        return self.stats

    def _run_loop(self, on_output) -> None:
        exec_one = self._exec_one
        for instr in self._instructions():
            exec_one(instr, on_output)
        self.driver.finalize()

    def _run_loop_batched(self, on_output) -> None:
        """The exec/ fast path: walk the precomputed batch schedule.

        Batchable groups (same op, uniform shape, mutually independent;
        see exec/batching.py) go through ``driver.execute_batch`` as
        gathered span columns; everything else — barriers, ops outside
        the driver's ``batch_ops``, singleton groups — replays through
        the scalar ``_exec_one`` reference path in schedule order."""
        drv = self.driver
        sched = self.batch_schedule
        sched.validate_for(self.prog)
        batch_ops = getattr(drv, "batch_ops", frozenset())
        order, bounds = sched.order, sched.bounds
        group_op, chunk_groups = sched.group_op, sched.chunk_groups
        ci = 0
        for start, rec, instrs in iter_record_chunks(self.prog,
                                                     sched.chunk_instrs,
                                                     cache=True):
            for g in range(chunk_groups[ci], chunk_groups[ci + 1]):
                rows = order[bounds[g]:bounds[g + 1]]
                gop = int(group_op[g])
                if gop >= 0 and len(rows) >= 2 and rec is not None \
                        and Op(gop) in batch_ops:
                    self._exec_batch(Op(gop), rec, rows)
                else:
                    self._exec_rows(rec, instrs, rows, batch_ops, on_output)
            ci += 1
        drv.finalize()

    def _net_row(self, rec, instrs, r: int, is_send: bool):
        """(peer, tag, span-view) for a NET_SEND/NET_RECV row, straight
        from the record columns when no decoded Instr list is around."""
        if instrs is not None:
            ins = instrs[r]
            span = ins.ins[0] if is_send else ins.outs[0]
            return int(ins.imm[0]), int(ins.imm[1]), self._view(span)
        row = rec[r]
        off = _IN_OFF if is_send else _OUT_OFF
        return (int(row[_IMM_OFF]), int(row[_IMM_OFF + 1]),
                self._view((int(row[off]), int(row[off + 1]))))

    def _run_loop_overlap(self, on_output) -> None:
        """The planned out-of-order issue path (exec/overlap.py): walk the
        OverlapSchedule's groups — NET_SENDs issued at their hoisted
        position, NET_RECVs posted as deferred completion handles
        (``recv_async``) and completed only at their K_RECV_WAIT group,
        with independent local work (batched where the driver allows)
        filling the latency gap.  Dataflow order is schedule-enforced, so
        results are bitwise-identical to the scalar reference."""
        from ..exec.overlap import K_LOCAL, K_RECV_WAIT, K_SEND
        drv = self.driver
        sched = self.overlap_schedule
        sched.validate_for(self.prog)
        batch_ops = (getattr(drv, "batch_ops", frozenset())
                     if hasattr(drv, "execute_batch") else frozenset())
        order, bounds = sched.order, sched.bounds
        group_kind, group_op = sched.group_kind, sched.group_op
        chunk_groups = sched.chunk_groups
        stats = self.stats
        w = self.prog.worker
        ci = 0
        for start, rec, instrs in iter_record_chunks(self.prog,
                                                     sched.chunk_instrs,
                                                     cache=True):
            handles: dict[int, tuple] = {}
            for g in range(chunk_groups[ci], chunk_groups[ci + 1]):
                rows = order[bounds[g]:bounds[g + 1]]
                kind = int(group_kind[g])
                if kind == K_LOCAL:
                    gop = int(group_op[g])
                    if gop >= 0 and len(rows) >= 2 and rec is not None \
                            and Op(gop) in batch_ops:
                        self._exec_batch(Op(gop), rec, rows)
                    else:
                        self._exec_rows(rec, instrs, rows, batch_ops,
                                        on_output)
                elif kind == K_SEND:
                    net = self._net()
                    for r in rows:
                        dst, tag, view = self._net_row(rec, instrs, r, True)
                        net.send_async(w, dst, tag, view)
                        stats.directives += 1
                        stats.net_messages += 1
                        stats.net_sent_bytes += view.nbytes
                        stats._net_count(w, dst, view.nbytes)
                elif kind == K_RECV_WAIT:
                    for r in rows:
                        h, src, nbytes = handles.pop(int(r))
                        h.wait()
                        stats.directives += 1
                        stats.net_messages += 1
                        stats.net_recv_bytes += nbytes
                        stats._net_count(src, w, nbytes)
                else:  # K_RECV_POST
                    net = self._net()
                    for r in rows:
                        src, tag, view = self._net_row(rec, instrs, r, False)
                        handles[int(r)] = (
                            net.recv_async(src, w, tag, out=view),
                            src, view.nbytes)
                        stats.posted_recvs += 1
                    if len(handles) > stats.max_inflight_recvs:
                        stats.max_inflight_recvs = len(handles)
            if handles:  # pragma: no cover - builder waits inside the chunk
                raise AssertionError(
                    f"{len(handles)} recv handles leaked past chunk {ci}")
            ci += 1
        drv.finalize()

    def _exec_rows(self, rec, instrs, rows, batch_ops, on_output) -> None:
        """A group's rows one by one, in stored order: a row whose op the
        driver takes alone (``solo_ops``) as a batch of one, the rest
        through ``_exec_one``, counting those the driver could have
        batched."""
        seq = ((instrs[r] for r in rows) if instrs is not None
               else decode_chunk(rec[rows]))
        solo = self._solo_ops if rec is not None else ()
        for k, ins in enumerate(seq):
            if ins.op in solo:
                self._exec_batch(ins.op, rec, rows[k:k + 1])
                continue
            if ins.op in batch_ops:
                self.stats.batchable_scalar += 1
            self._exec_one(ins, on_output)

    def _exec_batch(self, op: Op, rec: np.ndarray, rows: np.ndarray) -> None:
        r0 = rec[rows[0]]
        _, n_outs, n_ins, n_imm = unpack_heads(r0[0])
        imm = tuple(int(r0[_IMM_OFF + j]) for j in range(n_imm))
        out_idx = [(rec[rows, _OUT_OFF + 2 * j],
                    int(r0[_OUT_OFF + 1 + 2 * j])) for j in range(n_outs)]
        in_idx = [(rec[rows, _IN_OFF + 2 * j],
                   int(r0[_IN_OFF + 1 + 2 * j])) for j in range(n_ins)]
        with obs.span(_BATCHED_SPANS[op]):
            self.driver.execute_batch(op, imm, out_idx, in_idx, self.memory)
        self.stats.instructions += len(rows)
        self.stats.batched_instructions += len(rows)
        self.stats.batches += 1

    def _swap(self, instr: Instr) -> None:
        """One swap directive; only waiting on the storage is
        ``storage.wait``."""
        self.stats.directives += 1
        op = instr.op
        if op == Op.SWAP_IN:
            fut = self.io.issue_read(instr.imm[0],
                                     self._frame_page(instr.outs[0]))
            with obs.span(_WAIT):
                fut.result()
        elif op == Op.SWAP_OUT:
            fut = self.io.issue_write(
                instr.imm[0],
                np.array(self._frame_page(instr.ins[0]), copy=True))
            with obs.span(_WAIT):
                fut.result()
        elif op == Op.ISSUE_SWAP_IN:
            vpage, slot = instr.imm
            self._wait_slot(slot)
            self._slot_future[slot] = self.io.issue_read(vpage,
                                                         self.pf[slot])
        elif op == Op.FINISH_SWAP_IN:
            slot = instr.imm[1]
            self._wait_slot(slot)
            self._frame_page(instr.outs[0])[...] = self.pf[slot]
        elif op == Op.COPY_OUT:
            slot = instr.imm[0]
            self._wait_slot(slot)
            self.pf[slot][...] = self._frame_page(instr.ins[0])
        elif op == Op.ISSUE_SWAP_OUT:
            vpage, slot = instr.imm
            self._slot_future[slot] = self.io.issue_write(vpage,
                                                          self.pf[slot])
        else:  # FINISH_SWAP_OUT
            self._wait_slot(instr.imm[0])

    def _exec_one(self, instr: Instr, on_output) -> None:
        drv = self.driver
        w = self.prog.worker
        op = instr.op
        if op in _SWAPS:
            with obs.span(_STORAGE_SPANS[op]):
                self._swap(instr)
        elif op == Op.NET_SEND:
            self.stats.directives += 1
            dst, tag = instr.imm[0], instr.imm[1]
            view = self._view(instr.ins[0])
            self._net().send(w, dst, tag, view)
            self.stats.net_messages += 1
            self.stats.net_sent_bytes += view.nbytes
            self.stats._net_count(w, dst, view.nbytes)
        elif op == Op.NET_RECV:
            self.stats.directives += 1
            src, tag = instr.imm[0], instr.imm[1]
            view = self._view(instr.outs[0])
            self._net().recv(src, w, tag, out=view)
            self.stats.net_messages += 1
            self.stats.net_recv_bytes += view.nbytes
            self.stats._net_count(src, w, view.nbytes)
        elif op == Op.NET_BARRIER:
            # documented as "wait until posted send/recv with tag done"
            # (bytecode.py) — this engine's NET ops are synchronous, so
            # the completion wait is a no-op.  Collective sync is the
            # fabric's job (PartyView.barrier / Fabric.barrier), not an
            # instruction semantic.
            self.stats.directives += 1
        elif op == Op.FREE:
            pass
        elif op == Op.OUTPUT:
            self.stats.instructions += 1
            views = [self._view(s) for s in instr.ins]
            with obs.span(self._drv_spans[op]):
                drv.execute(op, instr.imm, [], views)
            if on_output is not None:
                on_output(instr, views)
        else:
            self.stats.instructions += 1
            with obs.span(self._drv_spans[op]):
                drv.execute(op, instr.imm,
                            [self._view(s) for s in instr.outs],
                            [self._view(s) for s in instr.ins])
