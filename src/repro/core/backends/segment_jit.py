"""The ``segment_jit`` backend — device-affine segment codegen.

The device-affinity schedule (Phase 4c) leaves the RGIR stream as
``δ_after + 1`` maximal same-device runs.  Instead of dispatching each
instruction from Python (the ``interpret`` backend), this backend hands
every *segment* to XLA as one compiled unit — the nGraph / oneDNN-graph
"contiguous device partition" model:

* each **accel** segment becomes one ``jax.jit`` callable whose signature
  is the segment's live-in / live-out register sets (derived from the
  existing liveness intervals),
* **host** segments replay per-op in Python (glue primitives; jitting
  them would only add trace overhead),
* buffer allocation stays linear-scan but becomes **segment-aware**:
  registers born and killed inside a single segment never occupy a
  physical slot — they exist only in the segment callable's local
  environment (and therefore only as XLA temporaries),
* live-ins that **die inside** their segment are passed to XLA as
  ``donate_argnums`` when a live-out of identical aval exists
  (``bufalloc.segment_donations``), so XLA reuses the dying buffer for
  the output instead of re-materializing every live-out,
* replay runs over a pooled **flat buffer file** with per-segment
  integer dispatch plans (gather live-ins / scatter live-outs / clear
  frees by slot index) computed once at build — steady-state calls do
  zero Python-side buffer-dict allocations.

Per call, exactly ``δ_after + 1`` segment dispatches happen, which is the
paper's dispatch-overhead claim reduced to its mechanism: dispatch cost
scales with δ, not with instruction count.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

import jax

from repro.runtime import chaos
from repro.runtime.trace import span

from .._jax_internal import trace_state_clean
from ..bufalloc import allocate, segment_donations
from ..executor import (
    AnalyzedProgram,
    BufferFilePoolMixin,
    ExecutorStats,
    PaddedExecutionMixin,
    analyze_program,
    analyzed_from_persisted,
)
from ..lowering import RGIROp, RGIRProgram
from .base import Backend, register_backend, xla_compiling


def _spec(aval: Any) -> jax.ShapeDtypeStruct:
    """The abstract argument a segment program is compiled for."""
    return jax.ShapeDtypeStruct(
        aval.shape, aval.dtype, weak_type=getattr(aval, "weak_type", False)
    )


def _restore_segment_export(blob: bytes) -> Optional[Callable]:
    """Deserialize one AOT-exported segment; None on any failure."""
    try:
        from jax import export as jax_export

        exp = jax_export.deserialize(bytearray(blob))
        return exp.call
    except Exception:
        return None


def _serialize_segment(seg: "CompiledSegment", avals: List[Any]) -> Optional[bytes]:
    """``jax.export`` one compiled segment at its live-in avals."""
    try:
        from jax import export as jax_export

        specs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in avals]
        # export the non-donating twin: donate_argnums are recomputed
        # deterministically at load time and re-applied by jax.jit
        exp = jax_export.export(seg.fn_nodonate)(*specs)
        return bytes(exp.serialize())
    except Exception:
        return None


@dataclass
class CompiledSegment:
    """One schedulable unit: a maximal device-affine instruction run."""

    index: int
    device: str
    start: int  # scheduled-order instruction range [start, stop)
    stop: int
    live_in: Tuple[int, ...]  # registers read from the buffer file
    live_out: Tuple[int, ...]  # registers written back to the buffer file
    free_after: Tuple[int, ...]  # buffer-file registers that die here
    fn: Callable  # (*live_in values) -> tuple of live_out values
    compiled: bool  # True when fn is a jax.jit program
    #: positions in ``live_in`` donated to XLA (dying intermediates whose
    #: buffers are reused in place for a live-out of identical aval)
    donate_argnums: Tuple[int, ...] = ()
    #: non-donating twin of ``fn``, dispatched instead whenever replay
    #: runs under an active JAX trace: jvp/vjp linearization evaluates
    #: primals *concretely* through the segment programs, and donating
    #: those buffers would delete arrays the autodiff residuals (or a
    #: replayed primal) still reference.  Equal to ``fn`` when the
    #: segment donates nothing.
    fn_nodonate: Callable = None  # type: ignore[assignment]
    # -- dispatch plan: slot indices into the flat buffer file ------------
    in_slots: Tuple[int, ...] = ()
    out_slots: Tuple[int, ...] = ()
    free_slots: Tuple[int, ...] = ()

    @property
    def n_ops(self) -> int:
        return self.stop - self.start


def _make_segment_fn(
    ops: Sequence[RGIROp], live_in: Tuple[int, ...], live_out: Tuple[int, ...]
) -> Callable:
    """Replay ``ops`` over a local register env: the segment's program."""

    def seg_fn(*vals):
        env: Dict[int, Any] = dict(zip(live_in, vals))
        read = env.__getitem__
        for op in ops:
            results = op.execute(read)
            for r, v in zip(op.output_regs, results):
                env[r] = v
        return tuple(env[r] for r in live_out)

    return seg_fn


class SegmentExecutor(BufferFilePoolMixin, PaddedExecutionMixin):
    """Segment-at-a-time executor over the physical buffer file.

    Bucketed (pad-and-mask) calls arrive via ``execute_padded``: the
    segment programs were traced/XLA-compiled at the bucket shapes, so a
    narrower concrete call is padded up to the bucket extents along
    every polymorphic axis (batch, and sequence for 2-D prefill
    programs) — keeping every per-segment jit cache at exactly one
    entry per bucket cell — and the masked rows/columns are sliced off
    the outputs.
    """

    def __init__(
        self,
        analyzed: AnalyzedProgram,
        *,
        warmup: bool = True,
        donate: bool = True,
        exports: Optional[Dict[int, bytes]] = None,
    ):
        self.prog = analyzed.prog
        self.sched = analyzed.sched
        self.live = analyzed.live
        n = len(self.prog.ops)
        segments = self.sched.segments

        seg_of = [0] * n
        for si, seg in enumerate(segments):
            for i in range(seg.start, seg.stop):
                seg_of[i] = si

        # registers whose entire life [s, e] sits inside one segment never
        # touch the buffer file — they are XLA temporaries of that segment
        intervals = self.live.intervals
        internal: Set[int] = set()
        for r, (s, e) in intervals.items():
            if s < 0 or e >= n or r in self.live.pinned:
                continue
            if seg_of[s] == seg_of[e]:
                internal.add(r)
        self._internal = internal

        # segment-aware linear scan: only buffer-file registers get slots
        lifetimes = {r: iv for r, iv in intervals.items() if r not in internal}
        pinned = set(self.live.pinned)
        for r, (s, _) in lifetimes.items():
            if s < 0:
                pinned.add(r)
        self.alloc = allocate(lifetimes, pinned)
        self._r2b = self.alloc.reg_to_buf

        self._const_buf: Dict[int, Any] = {
            self._r2b[r]: v for r, v in self.prog.constants.items()
        }
        self._input_bufs = [self._r2b[r] for r in self.prog.input_regs]
        self._output_bufs = [self._r2b[r] for r in self.prog.output_regs]
        # constant slots are never cleared: the executor pins their values
        # for its whole life, and a pooled buffer file relies on them
        # surviving across calls (dedicated slots, so filtering is exact)
        const_slots = set(self._const_buf)

        # build one callable per segment
        dead_after = self.live.dead_after
        reg_avals = self.prog.reg_avals
        self.segments: List[CompiledSegment] = []
        for si, seg in enumerate(segments):
            ops = self.prog.ops[seg.start : seg.stop]
            live_in_set: Set[int] = set()
            defined_here: Set[int] = set()
            for op in ops:
                for r in op.input_regs:
                    if intervals[r][0] < seg.start:
                        live_in_set.add(r)
                defined_here.update(op.output_regs)
            live_out = tuple(
                sorted(r for r in defined_here if r not in internal)
            )
            live_in = tuple(sorted(live_in_set))
            free_after = tuple(
                sorted(
                    r
                    for idx in range(seg.start, seg.stop)
                    for r in dead_after.get(idx, ())
                    if r not in internal
                )
            )
            fn = _make_segment_fn(ops, live_in, live_out)
            compiled = seg.device == "accel"
            donate_argnums: Tuple[int, ...] = ()
            fn_nodonate = fn
            if compiled:
                if donate:
                    donate_argnums = segment_donations(
                        self.live,
                        reg_avals,
                        live_in=live_in,
                        live_out=live_out,
                        free_after=free_after,
                    )
                # a persisted jax.export blob replaces re-tracing the
                # Python replay closure through jit; deserialization
                # failure (platform drift, format change) silently falls
                # back to the fresh trace — never a wrong program
                if exports and si in exports:
                    restored = _restore_segment_export(exports[si])
                    if restored is not None:
                        fn = restored
                fn_nodonate = jax.jit(fn)
                fn = (
                    jax.jit(fn, donate_argnums=donate_argnums)
                    if donate_argnums
                    else fn_nodonate
                )
            self.segments.append(
                CompiledSegment(
                    index=si,
                    device=seg.device,
                    start=seg.start,
                    stop=seg.stop,
                    live_in=live_in,
                    live_out=live_out,
                    free_after=free_after,
                    fn=fn,
                    compiled=compiled,
                    donate_argnums=donate_argnums,
                    fn_nodonate=fn_nodonate,
                    in_slots=tuple(self._r2b[r] for r in live_in),
                    out_slots=tuple(self._r2b[r] for r in live_out),
                    free_slots=tuple(
                        b
                        for b in (self._r2b[r] for r in free_after)
                        if b not in const_slots
                    ),
                )
            )

        # precompiled dispatch plan: the per-call loop touches only these
        # tuples (fns + slot indices) — no reg->slot lookups, no dict
        self._plans = tuple(
            (s.fn, s.fn_nodonate, s.in_slots, s.free_slots, s.out_slots,
             s.index, s.device)
            for s in self.segments
        )
        self._n_donated_args = sum(
            len(s.donate_argnums) for s in self.segments
        )
        # static occupancy peak: the store/free sequence is deterministic,
        # so the per-call dict-size high-water mark is known at build
        # time.  The simulation frees dying const slots (matching the old
        # per-call dict accounting, which popped them) even though the
        # runtime plan never clears them — peak continuity for the
        # benchmark series matters, pooled files don't
        occupied = set(self._const_buf) | set(self._input_bufs)
        peak = len(occupied)
        for s in self.segments:
            occupied.difference_update(self._r2b[r] for r in s.free_after)
            occupied.update(s.out_slots)
            peak = max(peak, len(occupied))
        self._static_peak = peak
        self._init_buffer_file(self.alloc.n_buffers, self._const_buf.items())

        # AOT warmup: lower and compile every accel segment from its
        # live-in avals now (compile-then-run), so build cost is paid here
        # once and the first serving request sees no jit-compile latency
        # spike.  ``jit(...).lower(...).compile()`` fills the same
        # executable cache that dispatch reads, and needs no data: no
        # buffer the size of a weight is ever built for it.
        if warmup:
            for seg in self.segments:
                if seg.compiled:
                    with xla_compiling():
                        seg.fn.lower(
                            *(_spec(reg_avals[r]) for r in seg.live_in)
                        ).compile()

        self.stats = ExecutorStats(
            n_instructions=n,
            n_accel=sum(1 for op in self.prog.ops if op.device == "accel"),
            n_host=sum(1 for op in self.prog.ops if op.device == "host"),
            n_vregs=self.prog.n_vregs,
            n_buffers=self.alloc.n_buffers,
            rho_buf=(
                1.0 - self.alloc.n_buffers / self.prog.n_vregs
                if self.prog.n_vregs
                else 0.0
            ),
            delta_before=self.sched.delta_before,
            delta_after=self.sched.delta_after,
            n_segments=len(self.segments),
            n_compiled_segments=sum(1 for s in self.segments if s.compiled),
            n_internal_regs=len(internal),
            n_donating_segments=sum(
                1 for s in self.segments if s.donate_argnums
            ),
            n_donated_args=self._n_donated_args,
        )

    # -- execution -------------------------------------------------------

    def execute(self, *flat_inputs: Any) -> List[Any]:
        """Run segment-at-a-time: exactly n_segments dispatches.

        Allocation-free on the Python side: the buffer file comes from
        the executor's pool and every gather/scatter/clear is an integer
        slot index from the precompiled dispatch plan.
        """
        if len(flat_inputs) != len(self._input_bufs):
            raise TypeError(
                f"executor expects {len(self._input_bufs)} inputs, "
                f"got {len(flat_inputs)}"
            )
        # donation is only legal on a clean trace state: jvp/vjp
        # linearization pushes *concrete* primal buffers through the
        # segment programs while keeping residual references to them
        donate_ok = trace_state_clean()
        file, pool_hit = self._acquire_file()
        try:
            for b, v in zip(self._input_bufs, flat_inputs):
                file[b] = v
            executed = 0
            for (fn, fn_plain, in_slots, free_slots, out_slots, index,
                 device) in self._plans:
                with span("forge.segment", index=index, device=device):
                    # chaos: fires BEFORE the segment runs, so no donation
                    # has consumed this call's buffers yet; program inputs
                    # are never donated, so the caller may retry the call
                    chaos.maybe_fault(chaos.SITE_DISPATCH)
                    f = fn if donate_ok else fn_plain
                    out_vals = f(*[file[b] for b in in_slots])
                    executed += 1
                    # clear BEFORE the stores: a register dying inside this
                    # segment may share its slot with a live-out born later
                    # in it (and its buffer may just have been donated)
                    for b in free_slots:
                        file[b] = None
                    for b, v in zip(out_slots, out_vals):
                        file[b] = v
            outs = [file[b] for b in self._output_bufs]
        finally:
            self._release_file(file)
        self.stats.note_call(
            self._static_peak,
            segments_executed=executed,
            donated_args=self._n_donated_args if donate_ok else 0,
            file_pool_hit=pool_hit,
        )
        return outs

    def as_fn(self) -> Callable:
        """JAX-traceable replay: under any active trace (jit tracing,
        jvp/vjp linearization) ``execute`` dispatches each segment's
        non-donating twin, so inlining and autodiff never run donated
        executables over concrete primal buffers."""

        def fn(*flat_inputs):
            return self.execute(*flat_inputs)

        return fn

@register_backend
class SegmentJitBackend(Backend):
    name = "segment_jit"

    def build(
        self,
        prog: RGIRProgram,
        *,
        reorder: bool = True,
        validate: bool = True,
    ) -> SegmentExecutor:
        analyzed = analyze_program(prog, reorder=reorder, validate=validate)
        return SegmentExecutor(analyzed)

    # -- persistence (DESIGN.md §Async compilation & persistent cache) --

    def export_entry(
        self, prog: RGIRProgram, executor: Any
    ) -> Optional[Dict[str, Any]]:
        if not isinstance(executor, SegmentExecutor):
            return None
        reg_avals = executor.prog.reg_avals
        exports: Dict[int, bytes] = {}
        for seg in executor.segments:
            if not seg.compiled:
                continue
            blob = _serialize_segment(
                seg, [reg_avals[r] for r in seg.live_in]
            )
            if blob is not None:
                exports[seg.index] = blob
        return {
            "kind": self.name,
            "n_ops": len(executor.prog.ops),
            "sched": executor.sched,
            "live": executor.live,
            # carried for AnalyzedProgram completeness only: the rebuilt
            # executor recomputes its segment-aware scan from ``live``
            # exactly as a fresh build does
            "alloc": executor.alloc,
            "exports": exports,
        }

    def build_from_entry(
        self,
        prog: RGIRProgram,
        entry: Dict[str, Any],
        *,
        reorder: bool = True,
        validate: bool = True,
    ) -> Optional[SegmentExecutor]:
        if entry.get("kind") != self.name:
            return None
        if entry.get("n_ops") != len(prog.ops):
            return None
        analyzed = analyzed_from_persisted(
            prog,
            entry["sched"],
            entry["live"],
            entry["alloc"],
            validate=validate,
        )
        if analyzed is None:
            return None
        try:
            return SegmentExecutor(analyzed, exports=entry.get("exports"))
        except Exception:
            return None
