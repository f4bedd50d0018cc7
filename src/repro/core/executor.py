"""Phase 4d — code generation: the ``CompiledExecutor``.

The JAX analogue of the paper's ``CompiledNPUExecutor`` (Listing 9): a
flat, pre-scheduled instruction stream executed with

* **no attribute lookup** — callables pre-resolved at lowering time,
* **no graph traversal** — straight loop over ``self.ops``,
* **physical-buffer register file** — values are stored under the buffer
  slot assigned by linear-scan allocation, so the executor *exercises*
  the allocation (a double-booked buffer corrupts results and is caught
  by the property tests),
* **eager GC** — ``dead_after`` frees buffers the moment their register's
  last reader retires, bounding peak live memory (paper: "eager GC").

Two execution modes:

``execute(*flat_inputs)``
    interpreted per-instruction Python dispatch — the measurable analogue
    of the paper's per-dispatch NPU round-trip world; used by the latency
    and scheduling benchmarks.

``as_fn()``
    a JAX-traceable callable replaying the same stream under ``jax.jit`` /
    ``pjit`` — one fused XLA program (the NNFactory compile-then-run
    model); used by the train/serve paths and the multi-pod dry-run.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field, replace as _dc_replace
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.runtime import chaos

from .bufalloc import AllocationResult, allocate_from_liveness
from .liveness import LivenessInfo, analyze_liveness
from .lowering import RGIRProgram, lower_to_rgir
from .scheduler import (
    ScheduleResult,
    compute_segments,
    schedule,
    verify_topological,
)


@dataclass
class ExecutorStats:
    n_instructions: int = 0
    n_accel: int = 0
    n_host: int = 0
    n_vregs: int = 0
    n_buffers: int = 0
    rho_buf: float = 0.0
    delta_before: int = 0
    delta_after: int = 0
    #: all-time high-water mark of the physical buffer file (max over calls)
    peak_live_buffers: int = 0
    #: high-water mark of the most recent ``execute()`` call only
    last_peak_live_buffers: int = 0
    #: total ``execute()`` calls on this executor (bucket accounting: the
    #: per-bucket executors' totals sum to the BucketedModule's calls)
    total_calls: int = 0
    # -- pad-and-mask (bucketed execution) counters -----------------------
    #: ``execute_padded`` calls routed through this executor
    padded_calls: int = 0
    #: real (valid) batch rows executed via ``execute_padded``
    rows_valid_total: int = 0
    #: padding rows executed via ``execute_padded`` (pad waste numerator)
    rows_padded_total: int = 0
    # -- segment backend statistics (zero for per-op backends) ------------
    n_segments: int = 0
    n_compiled_segments: int = 0
    #: registers whose whole life is inside one segment (never hit a slot)
    n_internal_regs: int = 0
    #: segments dispatched by the most recent ``execute()`` call
    last_segments_executed: int = 0
    #: segments dispatched across all calls
    total_segments_executed: int = 0
    # -- donation statistics (segment_jit backend) -------------------------
    #: accel segments compiled with a non-empty ``donate_argnums``
    n_donating_segments: int = 0
    #: donated argument positions across all segments (static)
    n_donated_args: int = 0
    #: donated args across all ``execute()`` calls (runtime accumulation)
    total_donated_args: int = 0
    # -- flat-buffer-file pool counters (zero-copy dispatch plans) ---------
    #: calls that reused a pooled buffer file (no Python-side allocation)
    file_pool_hits: int = 0
    #: calls that had to materialize a fresh buffer file (first call /
    #: concurrent overlap); steady-state replay keeps this flat
    file_pool_misses: int = 0

    def __post_init__(self) -> None:
        # per-call counters are folded in under a lock so a shared stats
        # object stays consistent when the batched server runs concurrent
        # requests against one compiled executor
        self._lock = threading.Lock()

    def note_call(
        self,
        peak: int,
        segments_executed: int = 0,
        donated_args: int = 0,
        file_pool_hit: Optional[bool] = None,
    ) -> None:
        """Record one ``execute()`` call's per-call counters (thread-safe)."""
        with self._lock:
            self.total_calls += 1
            self.last_peak_live_buffers = peak
            self.peak_live_buffers = max(self.peak_live_buffers, peak)
            self.last_segments_executed = segments_executed
            self.total_segments_executed += segments_executed
            self.total_donated_args += donated_args
            if file_pool_hit is not None:
                if file_pool_hit:
                    self.file_pool_hits += 1
                else:
                    self.file_pool_misses += 1

    def note_padding(self, rows_valid: int, rows_padded: int) -> None:
        """Record one pad-and-mask call's row accounting (thread-safe)."""
        with self._lock:
            self.padded_calls += 1
            self.rows_valid_total += rows_valid
            self.rows_padded_total += rows_padded

    @property
    def pad_waste(self) -> float:
        """Fraction of executed batch rows that were padding."""
        total = self.rows_valid_total + self.rows_padded_total
        return self.rows_padded_total / total if total else 0.0

    @property
    def transition_reduction(self) -> float:
        if self.delta_before == 0:
            return 0.0
        return 1.0 - self.delta_after / self.delta_before

    def fresh_snapshot(self) -> "ExecutorStats":
        """Copy with run counters zeroed (static analysis fields kept).

        A compile-cache hit hands a *shared* executor to a new module;
        its CompilationResult must not report execution history that
        other modules accumulated on that executor.
        """
        return _dc_replace(
            self,
            peak_live_buffers=0,
            last_peak_live_buffers=0,
            last_segments_executed=0,
            total_segments_executed=0,
            total_calls=0,
            padded_calls=0,
            rows_valid_total=0,
            rows_padded_total=0,
            total_donated_args=0,
            file_pool_hits=0,
            file_pool_misses=0,
        )


class BufferFilePoolMixin:
    """Pooled flat buffer file: the zero-copy replacement for the
    per-call ``bufs`` dict (DESIGN.md §Dispatch plans).

    The buffer file is a plain list indexed by physical slot, with
    constant slots pre-filled.  ``execute()`` acquires a file from a
    small free-list and returns it when done, so steady-state replay
    performs **zero** per-call Python-side buffer-container allocations:
    a fresh file is only materialized on the first call or when
    concurrent calls overlap (both counted on ``ExecutorStats``).
    Acquire/release are single list ``pop``/``append`` operations —
    atomic under the GIL, so concurrent server threads never share one
    file.
    """

    #: files kept per executor; overlap beyond this just allocates
    _FILE_POOL_CAP = 8

    def _init_buffer_file(
        self, n_slots: int, const_slot_items: Sequence[Tuple[int, Any]]
    ) -> None:
        self._n_slots = n_slots
        self._const_slot_items = tuple(const_slot_items)
        const_slots = {b for b, _ in self._const_slot_items}
        #: every non-constant slot, cleared on release so a pooled file
        #: never pins dead device buffers between calls
        self._volatile_slots = tuple(
            b for b in range(n_slots) if b not in const_slots
        )
        self._file_pool: List[List[Any]] = []

    def _acquire_file(self) -> Tuple[List[Any], bool]:
        try:
            return self._file_pool.pop(), True
        except IndexError:
            file: List[Any] = [None] * self._n_slots
            for b, v in self._const_slot_items:
                file[b] = v
            return file, False

    def _release_file(self, file: List[Any]) -> None:
        for b in self._volatile_slots:
            file[b] = None
        if len(self._file_pool) < self._FILE_POOL_CAP:
            self._file_pool.append(file)


class PaddedExecutionMixin:
    """Pad-and-mask execution: run a bucket-shaped program on narrower
    inputs (DESIGN.md §Shape generalization).

    The program was compiled for canonical bucket extents — one per
    polymorphic axis (batch, and for prefill programs also sequence); a
    concrete call with fewer rows/columns is padded up along every
    polymorphic axis (plan-supplied), executed full-width, and its
    outputs sliced back to the valid region — the "mask".  Pad waste is
    folded into the stats as *cells* (the product over axes, plain rows
    for 1-D fronts) so bucket-policy cost is observable.  Shared by
    every backend executor (``interpret``'s CompiledExecutor,
    ``segment_jit``, ``reference``).
    """

    def execute_padded(
        self, flat_inputs: Sequence[Any], *, plan: Any
    ) -> List[Any]:
        outs = self.execute(*plan.pad(flat_inputs))
        self.stats.note_padding(plan.n_valid_cells, plan.n_padded)
        return plan.unpad(outs)


@dataclass
class AnalyzedProgram:
    """Phase-4 analysis product shared by every backend.

    Scheduling runs *first*, then liveness and linear-scan allocation are
    recomputed on the scheduled order (see DESIGN.md for the soundness
    argument) — ``prog`` is already renumbered into schedule order.
    """

    prog: RGIRProgram
    sched: ScheduleResult
    live: LivenessInfo
    alloc: AllocationResult


def analyze_program(
    prog: RGIRProgram, *, reorder: bool = True, validate: bool = True
) -> AnalyzedProgram:
    """Run Phase 4a-c: schedule, then liveness + allocation on that order."""
    sched = schedule(prog)
    if not reorder:
        identity = list(range(len(prog.ops)))
        sched = ScheduleResult(
            order=identity,
            delta_before=sched.delta_before,
            delta_after=sched.delta_before,
            segments=compute_segments([op.device for op in prog.ops]),
        )
    if validate:
        verify_topological(prog, sched.order)
    scheduled = prog.renumber(sched.order)
    live = analyze_liveness(scheduled)
    alloc = allocate_from_liveness(live)
    return AnalyzedProgram(prog=scheduled, sched=sched, live=live, alloc=alloc)


def analyzed_from_persisted(
    prog: RGIRProgram,
    sched: ScheduleResult,
    live: LivenessInfo,
    alloc: AllocationResult,
    *,
    validate: bool = True,
) -> Optional[AnalyzedProgram]:
    """Rehydrate Phase-4 analysis from a disk-cache entry.

    ``prog`` is a freshly lowered program whose fingerprint matched the
    persisted entry's cache key; ``renumber`` keeps register ids, so the
    stored schedule/liveness/allocation (all keyed by register id and
    scheduled instruction index) apply verbatim.  Returns ``None`` on
    any inconsistency — the caller falls back to a full analysis, never
    trusts a stale entry.
    """
    n = len(prog.ops)
    if sorted(sched.order) != list(range(n)):
        return None
    if sched.segments and sched.segments[-1].stop != n:
        return None
    try:
        if validate:
            verify_topological(prog, sched.order)
        scheduled = prog.renumber(sched.order)
        regs = set(scheduled.input_regs) | set(scheduled.constants)
        for op in scheduled.ops:
            regs.update(op.output_regs)
        if not regs.issubset(live.intervals.keys()):
            return None
    except Exception:
        return None
    return AnalyzedProgram(prog=scheduled, sched=sched, live=live, alloc=alloc)


class CompiledExecutor(BufferFilePoolMixin, PaddedExecutionMixin):
    """Flat instruction-stream executor over a physical buffer file."""

    def __init__(
        self,
        prog: RGIRProgram,
        *,
        reorder: bool = True,
        validate: bool = True,
        analyzed: Optional[AnalyzedProgram] = None,
    ):
        if analyzed is None:
            analyzed = analyze_program(prog, reorder=reorder, validate=validate)
        self.prog = analyzed.prog
        self.sched = analyzed.sched

        # liveness + allocation on the *scheduled* stream (soundness)
        self.live: LivenessInfo = analyzed.live
        self.alloc: AllocationResult = analyzed.alloc
        self._r2b = self.alloc.reg_to_buf
        self.dead_after = self.live.dead_after

        # pre-loaded constant buffers (device constants, paper Listing 9)
        self._const_buf: Dict[int, Any] = {
            self._r2b[r]: v for r, v in self.prog.constants.items()
        }
        self._input_bufs = [self._r2b[r] for r in self.prog.input_regs]
        self._output_bufs = [self._r2b[r] for r in self.prog.output_regs]

        # precompiled dispatch plan: per-op output/free slot indices plus
        # the statically-known occupancy peak, computed once here so the
        # hot loop does no reg->slot dict walking for stores/frees and no
        # per-call dict bookkeeping at all
        r2b = self._r2b
        # constant slots are never cleared: their values are pinned on the
        # executor for its whole life and pooled buffer files rely on them
        # surviving across calls (dedicated slots, so filtering is exact)
        const_slots = set(self._const_buf)
        self._op_plans = tuple(
            (
                op,
                tuple(r2b[r] for r in op.output_regs),
                tuple(
                    b
                    for b in (r2b[r] for r in self.dead_after.get(idx, ()))
                    if b not in const_slots
                ),
            )
            for idx, op in enumerate(self.prog.ops)
        )
        # the simulation frees dying const slots (matching the old
        # per-call dict accounting, which popped them) even though the
        # runtime plan above never clears them — peak continuity for the
        # Table-16 benchmark series matters, pooled files don't
        occupied = set(self._const_buf) | set(self._input_bufs)
        peak = len(occupied)
        for idx, op in enumerate(self.prog.ops):
            occupied.update(r2b[r] for r in op.output_regs)
            peak = max(peak, len(occupied))
            occupied.difference_update(
                r2b[r] for r in self.dead_after.get(idx, ())
            )
        self._static_peak = peak
        self._init_buffer_file(self.alloc.n_buffers, self._const_buf.items())

        self.stats = ExecutorStats(
            n_instructions=len(self.prog.ops),
            n_accel=sum(1 for op in self.prog.ops if op.device == "accel"),
            n_host=sum(1 for op in self.prog.ops if op.device == "host"),
            n_vregs=self.alloc.n_vregs,
            n_buffers=self.alloc.n_buffers,
            rho_buf=self.alloc.rho_buf,
            delta_before=self.sched.delta_before,
            delta_after=self.sched.delta_after,
            n_segments=self.sched.n_segments,
        )

    # -- interpreted mode ------------------------------------------------------

    def execute(self, *flat_inputs: Any) -> List[Any]:
        """Run the compiled program (paper Listing 9's ``execute``)."""
        if len(flat_inputs) != len(self._input_bufs):
            raise TypeError(
                f"executor expects {len(self._input_bufs)} inputs, "
                f"got {len(flat_inputs)}"
            )
        # injection granularity is one *program* execution (mirrors the
        # per-segment hook in segment_jit), not one op — per-op rates
        # would compound over hundreds of ops; fires before any register
        # write, and the finally releases the pooled file, so the caller
        # may retry the same dispatch
        chaos.maybe_fault(chaos.SITE_DISPATCH)
        file, pool_hit = self._acquire_file()
        try:
            for b, v in zip(self._input_bufs, flat_inputs):
                file[b] = v
            r2b = self._r2b
            read = lambda r: file[r2b[r]]  # noqa: E731
            for op, out_slots, free_slots in self._op_plans:
                results = op.execute(read)
                for b, v in zip(out_slots, results):
                    file[b] = v
                # eager GC: free buffers whose register died here
                for b in free_slots:  # pragma: no branch
                    file[b] = None
            outs = [file[b] for b in self._output_bufs]
        finally:
            self._release_file(file)
        self.stats.note_call(self._static_peak, file_pool_hit=pool_hit)
        return outs

    # -- traced mode -----------------------------------------------------------

    def as_fn(self) -> Callable:
        """A JAX-traceable callable replaying the instruction stream."""

        def fn(*flat_inputs):
            outs = self.execute(*flat_inputs)
            return outs

        return fn

    # -- profiling helpers -------------------------------------------------------

    def timed_execute(self, *flat_inputs: Any) -> Tuple[List[Any], float, Dict[str, float]]:
        """Execute with wall-clock + per-device dispatch-time accounting."""
        if len(flat_inputs) != len(self._input_bufs):
            raise TypeError("bad arity")
        bufs: Dict[int, Any] = dict(self._const_buf)
        for b, v in zip(self._input_bufs, flat_inputs):
            bufs[b] = v
        r2b = self._r2b
        read = lambda r: bufs[r2b[r]]  # noqa: E731
        per_dev = {"accel": 0.0, "host": 0.0}
        t_all = time.perf_counter()
        for idx, op in enumerate(self.prog.ops):
            t0 = time.perf_counter()
            results = op.execute(read)
            results = [
                r.block_until_ready() if hasattr(r, "block_until_ready") else r
                for r in results
            ]
            per_dev[op.device] += time.perf_counter() - t0
            for r, v in zip(op.output_regs, results):
                bufs[r2b[r]] = v
            for r in self.dead_after.get(idx, ()):
                bufs.pop(r2b[r], None)
        total = time.perf_counter() - t_all
        return [bufs[b] for b in self._output_bufs], total * 1e3, per_dev


def build_executor(
    g,
    *,
    reorder: bool = True,
    validate: bool = True,
    backend: str = "interpret",
):
    """Lower a Phase-2 graph and build an executor (Phases 3+4)."""
    prog = lower_to_rgir(g)
    from .backends import get_backend  # local: backends import this module

    return get_backend(backend).build(prog, reorder=reorder, validate=validate)
