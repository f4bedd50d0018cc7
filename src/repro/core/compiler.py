"""The ForgeCompiler — four-phase orchestration (paper Figure 1).

``ForgeCompiler.compile(fn, *example_args)`` runs

  Phase 1  capture          trace_to_graph (tied-weight resolution)
  Phase 2  optimization     run_forge_passes (six passes, fixpoint)
  Phase 3  lowering         lower_to_rgir (typed register IR)
  Phase 4  analysis+codegen CompiledExecutor (liveness, linear-scan
                            allocation, device-affinity scheduling)

and returns a :class:`CompiledModule` exposing both execution modes plus
the fully transparent :class:`CompilationResult` — the paper's
``CompilationResult`` struct (nodes before/after, fused-op counts,
per-pass profile, buffer/transition statistics, phase timings).
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from repro.runtime.trace import span

from .backends import ExecutorLike, get_backend
from .backends.base import xla_compile_seconds
from .cache import (
    CompileCache,
    UncacheableProgram,
    fingerprint_program,
    get_compile_cache,
    make_cache_key,
)
from .compile_service import CompileService, get_compile_service
from .capture import CaptureResult, trace_to_graph
from .cost_model import CostBreakdown, score_graph
from .executor import CompiledExecutor, ExecutorStats
from .graph import Graph
from .lowering import RGIRProgram, lower_to_rgir
from .passes import PassRecord, PipelineConfig, run_forge_passes
from .shapekey import (
    AxisKey,
    AxisSpec,
    BucketPolicy,
    BucketStats,
    PadPlan,
    PolyAxis,
    ShapeKey,
    flatten_axes,
    get_bucket_policy,
    infer_extent,
    pad_args,
)


@dataclass
class CompilationResult:
    """The paper's transparency struct (§1.3 Limitation 2)."""

    nodes_before: int = 0
    nodes_after: int = 0
    fused_ops: int = 0
    attention_fused: int = 0
    pass_records: List[PassRecord] = field(default_factory=list)
    # phase timings (ms)
    capture_ms: float = 0.0
    optimize_ms: float = 0.0
    lower_ms: float = 0.0
    backend_ms: float = 0.0  # schedule + alloc + codegen (or cache lookup)
    #: XLA's compile of backend programs anywhere in this compile (nested
    #: block-body compiles inside capture included): the part of the four
    #: phases that is not Forge's own
    xla_ms: float = 0.0
    total_ms: float = 0.0
    # Phase-4 statistics
    executor_stats: Optional[ExecutorStats] = None
    cost: Optional[CostBreakdown] = None
    tied_weights: int = 0
    config: Optional[PipelineConfig] = None
    # Phase-4 backend + compile-cache provenance
    backend: str = "interpret"
    cache_hit: bool = False
    #: the hit was served by the persistent tier (executor rebuilt from
    #: a disk entry rather than found in the memory LRU)
    cache_disk_hit: bool = False
    cache_key: Optional[str] = None
    cache_hits: int = 0  # global counter snapshots at compile time
    cache_misses: int = 0
    #: canonical bucket ShapeKey string for bucketed compiles (None = exact)
    shape_key: Optional[str] = None

    @property
    def forge_phases_ms(self) -> float:
        """Phases 1-4 without XLA's compile."""
        return (self.capture_ms + self.optimize_ms + self.lower_ms
                + self.backend_ms - self.xla_ms)

    @property
    def node_reduction(self) -> float:
        if self.nodes_before == 0:
            return 0.0
        return 1.0 - self.nodes_after / self.nodes_before

    def pass_table(self) -> List[Dict[str, Any]]:
        """Aggregated per-pass rows (paper Table 10)."""
        agg: Dict[str, Dict[str, Any]] = {}
        for r in self.pass_records:
            row = agg.setdefault(
                r.name, {"pass": r.name, "time_ms": 0.0, "delta_nodes": 0,
                         "runs": 0, "detail": {}}
            )
            row["time_ms"] += r.time_ms
            row["delta_nodes"] += r.node_delta
            row["runs"] += 1
            for k, v in r.detail.items():
                if isinstance(v, (int, float)):
                    row["detail"][k] = row["detail"].get(k, 0) + v
        return list(agg.values())

    def summary(self) -> str:
        lines = [
            f"nodes: {self.nodes_before} -> {self.nodes_after} "
            f"({-100 * self.node_reduction:+.1f}%)",
            f"fused ops: {self.fused_ops} (attention: {self.attention_fused})",
            f"phases (ms): capture={self.capture_ms:.1f} "
            f"optimize={self.optimize_ms:.1f} lower={self.lower_ms:.1f} "
            f"backend={self.backend_ms:.1f} total={self.total_ms:.1f}",
        ]
        if self.executor_stats:
            s = self.executor_stats
            lines.append(
                f"vregs={s.n_vregs} buffers={s.n_buffers} "
                f"rho_buf={s.rho_buf:.1%} delta {s.delta_before}->"
                f"{s.delta_after} (-{s.transition_reduction:.1%})"
            )
            seg_note = (
                f" segments={s.n_segments} "
                f"(compiled={s.n_compiled_segments}, "
                f"internal_regs={s.n_internal_regs})"
                if s.n_compiled_segments
                else ""
            )
            bucket_note = f" bucket={self.shape_key}" if self.shape_key else ""
            lines.append(
                f"backend={self.backend} "
                f"cache={'hit' if self.cache_hit else 'miss'}"
                f"{seg_note}{bucket_note}"
            )
        if self.cost:
            lines.append(f"cost score: {self.cost.score:.2f}")
        return "\n".join(lines)


class CompiledModule:
    """A compiled function: pytree-aware wrapper over the executor."""

    def __init__(
        self,
        executor: ExecutorLike,
        capture: CaptureResult,
        result: CompilationResult,
        graph: Graph,
    ):
        self.executor = executor
        self.capture = capture
        self.result = result
        self.graph = graph
        self._jitted: Optional[Callable] = None

    # -- pytree plumbing -------------------------------------------------------

    def _flatten_inputs(self, args: Sequence[Any]) -> List[Any]:
        flat, tree = jax.tree_util.tree_flatten(tuple(args))
        return self._filter_flat_inputs(flat, tree)

    def _filter_flat_inputs(self, flat: List[Any], tree: Any) -> List[Any]:
        """Validate a pre-flattened input list and drop tied duplicates."""
        if tree != self.capture.in_tree:
            raise TypeError(
                f"input pytree mismatch: expected {self.capture.in_tree}, "
                f"got {tree}"
            )
        tied = self.capture.tied_map
        if tied:
            flat = [x for i, x in enumerate(flat) if i not in tied]
        return flat

    def _unflatten_outputs(self, outs: List[Any]) -> Any:
        return jax.tree_util.tree_unflatten(self.capture.out_tree, outs)

    # -- execution modes ----------------------------------------------------------

    def __call__(self, *args: Any) -> Any:
        """Interpreted flat-dispatch execution (paper Listing 9)."""
        outs = self.executor.execute(*self._flatten_inputs(args))
        return self._unflatten_outputs(outs)

    def as_fn(self) -> Callable:
        """Traceable callable on the original pytree signature."""

        def fn(*args):
            outs = self.executor.as_fn()(*self._flatten_inputs(args))
            return self._unflatten_outputs(outs)

        return fn

    def jit(self) -> Callable:
        """One-XLA-program execution (the NNFactory compile-then-run mode)."""
        if self._jitted is None:
            self._jitted = jax.jit(self.as_fn())
        return self._jitted

    @property
    def stats(self) -> ExecutorStats:
        return self.executor.stats


def _tree_nbytes(tree: Any) -> int:
    """Total device bytes of a pytree of arrays (best-effort)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is None:
            shape = getattr(leaf, "shape", ())
            dtype = getattr(leaf, "dtype", None)
            itemsize = getattr(dtype, "itemsize", 0) if dtype is not None else 0
            nbytes = int(np.prod(shape or (1,))) * itemsize
        total += int(nbytes)
    return total


def bucket_pool_key(key: ShapeKey) -> Any:
    """Canonical :class:`BufferPool` keying for one bucket program.

    The single contract shared by pool writers and reapers: the serve
    path parks caches under the bucket's batch extent (``key.extent``,
    a plain int — what ``policy.bucket(B)`` hands it before a ShapeKey
    exists), N-D fronts under the full extents tuple.
    :meth:`BucketedModule.evict_cold` releases through the same helper,
    so a keying change cannot silently strand pooled buffers.
    """
    return key.extent if key.n_axes == 1 else key.extents


class BufferPool:
    """Per-bucket device-buffer pool (DESIGN.md §Buffer pooling).

    Repeat admissions to a bucket re-materialize bucket-sized pytrees
    (the serve path's KV cache, program I/O staging buffers) on every
    acquisition; this pool keeps released sets on a per-key free list so
    the next admission to the same bucket reuses the device buffers.
    Keys are arbitrary hashables — the serve path keys by bucket extent.

    ``acquire(key, build, reset=...)`` pops a pooled set and passes it
    through ``reset`` (typically a donating jitted zero-fill, so the
    device buffers are recycled *in place*); a miss — cold bucket, or
    more concurrent generations than pooled sets — calls ``build()``.
    A failing ``reset`` (e.g. XLA aliased two released leaves onto one
    buffer, which a donating reset cannot accept) falls back to
    ``build()`` rather than poisoning the admission.  Hit/miss/bytes
    counters fold into the owning :class:`BucketStats`.
    """

    def __init__(
        self,
        stats: Optional[BucketStats] = None,
        *,
        max_per_key: int = 4,
    ):
        self.stats = stats if stats is not None else BucketStats()
        self.max_per_key = max_per_key
        self._free: Dict[Any, List[Any]] = {}
        self._nbytes: Dict[Any, int] = {}
        self._lock = threading.Lock()

    def acquire(
        self,
        key: Any,
        build: Callable[[], Any],
        reset: Optional[Callable[[Any], Any]] = None,
    ) -> Any:
        with self._lock:
            entries = self._free.get(key)
            tree = entries.pop() if entries else None
        if tree is not None and reset is not None:
            try:
                tree = reset(tree)
            except Exception:  # unresettable buffers: rebuild below
                tree = None
        if tree is None:
            tree = build()
            with self._lock:
                self._nbytes.setdefault(key, _tree_nbytes(tree))
            self.stats.note_pool(hit=False)
            return tree
        self.stats.note_pool(hit=True, nbytes=self._nbytes.get(key, 0))
        return tree

    def release(self, key: Any, tree: Any) -> None:
        """Return a buffer set to ``key``'s free list (drop when full)."""
        if tree is None:
            return
        with self._lock:
            entries = self._free.setdefault(key, [])
            if len(entries) < self.max_per_key:
                entries.append(tree)

    def pooled(self, key: Any) -> int:
        """Free-list depth for ``key`` (transparency / tests)."""
        with self._lock:
            entries = self._free.get(key)
            return len(entries) if entries else 0

    def drop(self, key: Any) -> int:
        """Release ``key``'s free list (cold-bucket eviction).

        Returns the number of buffer sets dropped; the device buffers
        are freed when the last reference dies.  A no-op for unknown
        keys, so callers may drop every plausible keying of an evicted
        bucket.
        """
        with self._lock:
            entries = self._free.pop(key, None)
            self._nbytes.pop(key, None)
        return len(entries) if entries else 0


class BucketedModule:
    """Shape-generalized multi-program front (DESIGN.md §Shape).

    Holds a per-bucket program table over N polymorphic axes: a call
    with concrete extents ``(n_1, …, n_N)`` is dispatched by its
    :class:`ShapeKey` (per-axis ``policy.bucket(n_i)``) to the cell's
    compiled program — compiling Phases 1-4 on the first miss only —
    and executed pad-and-mask: inputs padded up to the bucket extents
    along every polymorphic axis, outputs sliced back to the valid
    rows/columns.  The program table is bounded by the product of the
    per-axis policies (log-many entries for ``pow2``, #rungs for
    ``ladder``), so a server front absorbs arbitrary batch shapes —
    and, for 2-D prefill fronts, arbitrary prompt lengths — with a
    small fixed grid of compiled programs.

    Construct either from ``axes=(PolyAxis(...), ...)`` (one entry per
    polymorphic dimension) or from the 1-D legacy kwargs
    ``in_axes``/``out_axes``/``policy``.
    """

    def __init__(
        self,
        compiler: "ForgeCompiler",
        fn: Callable,
        *,
        axes: Optional[Sequence[PolyAxis]] = None,
        in_axes: AxisSpec = 0,
        out_axes: AxisSpec = 0,
        policy: Union[str, BucketPolicy] = "pow2",
        pad_mode: str = "edge",
        async_compile: bool = False,
        service: Optional[CompileService] = None,
    ):
        self.compiler = compiler
        self.fn = fn
        #: async mode (DESIGN.md §Async compilation): a cold dispatch
        #: submits its exact key to the CompileService and pads into the
        #: nearest warm dominating bucket instead of blocking — it only
        #: ever blocks when no warm bucket dominates the concrete shape
        self.async_compile = bool(async_compile)
        self.service: Optional[CompileService] = (
            service
            if service is not None
            else (get_compile_service() if async_compile else None)
        )
        if axes is None:
            axes = (PolyAxis(in_axes=in_axes, out_axes=out_axes,
                             policy=policy),)
        self.axes: Tuple[PolyAxis, ...] = tuple(axes)
        if not self.axes:
            raise ValueError("BucketedModule needs at least one PolyAxis")
        # 1-D legacy views (first axis)
        self.in_axes = self.axes[0].in_axes
        self.out_axes = self.axes[0].out_axes
        self.policy = self.axes[0].policy
        self.pad_mode = pad_mode
        self.programs: Dict[ShapeKey, CompiledModule] = {}
        self.stats = BucketStats()
        #: per-bucket device-buffer pool (counters fold into ``stats``);
        #: the serve path parks each generation's KV cache here so the
        #: next admission to the bucket reuses the buffers in place
        self.pool = BufferPool(self.stats)
        self._out_axes_flat: Dict[
            ShapeKey, Tuple[Tuple[Optional[int], ...], ...]
        ] = {}
        self._lock = threading.Lock()
        #: per-key build locks: concurrent first dispatches to one cold
        #: bucket serialize instead of duplicating a seconds-scale compile
        self._build_locks: Dict[ShapeKey, threading.Lock] = {}

    # -- dispatch ---------------------------------------------------------

    def shape_key_for(self, *args: Any) -> Tuple[ShapeKey, Any]:
        """(ShapeKey, concrete extent(s)) of an argument tuple.

        The extent is an int for 1-D fronts (legacy) and a per-axis
        tuple for N-D fronts.
        """
        flat, _ = jax.tree_util.tree_flatten(args)
        key, ns = self._shape_key_flat(flat, args)
        return key, (ns[0] if len(ns) == 1 else ns)

    def _shape_key_flat(
        self, flat: List[Any], args: Tuple[Any, ...]
    ) -> Tuple[ShapeKey, Tuple[int, ...]]:
        ns: List[int] = []
        axis_keys: List[AxisKey] = []
        for pa in self.axes:
            a_flat = flatten_axes(pa.in_axes, args)
            n = infer_extent(flat, a_flat)
            ns.append(n)
            axis_keys.append(
                AxisKey(pa.policy.name, pa.policy.bucket(n), pa.label)
            )
        return ShapeKey(tuple(axis_keys)), tuple(ns)

    def program_for(self, *args: Any) -> Tuple[CompiledModule, ShapeKey, Any]:
        """Resolve the bucket program; compile Phases 1-4 on first miss."""
        key, n = self.shape_key_for(*args)
        return self._program_for_key(key, args), key, n

    def _program_for_key(
        self,
        key: ShapeKey,
        args: Tuple[Any, ...],
        *,
        background: bool = False,
    ) -> CompiledModule:
        with self._lock:
            mod = self.programs.get(key)
            if mod is None:
                build_lock = self._build_locks.setdefault(
                    key, threading.Lock()
                )
        if mod is not None:
            if not background:
                self.stats.note_lookup(hit=True)
            return mod
        # everything below is request-visible stall unless a service
        # worker is doing it: the split compile_wait_s is judged by
        t_wait = time.perf_counter()
        with build_lock:
            with self._lock:
                mod = self.programs.get(key)
            if mod is not None:  # a concurrent dispatch built it first
                if not background:
                    self.stats.note_lookup(hit=True)
                    self.stats.note_wait(time.perf_counter() - t_wait)
                return mod
            t0 = time.perf_counter()
            padded = pad_args(
                args,
                tuple(pa.in_axes for pa in self.axes),
                key.extents,
                mode=self.pad_mode,
            )
            mod = self.compiler.compile(
                self.fn, *padded, shape_key=key,
                poly_axes_nd=tuple(pa.in_axes for pa in self.axes),
            )
            with self._lock:
                self.programs[key] = mod
            self.stats.note_lookup(
                hit=False,
                compile_s=time.perf_counter() - t0,
                forge_phases_s=mod.result.forge_phases_ms / 1e3,
                xla_compile_s=mod.result.xla_ms / 1e3,
                background=background,
            )
            if not background:
                self.stats.note_wait(time.perf_counter() - t_wait)
        return mod

    # -- async compile service integration --------------------------------

    def _service_key(self, key: ShapeKey) -> str:
        # the module's identity joins the key: two fronts can share one
        # CompileService without colliding on equal ShapeKeys
        return f"bucketed@{id(self):#x}|{key}"

    def has_program(self, key: ShapeKey) -> bool:
        with self._lock:
            return key in self.programs

    def lookup_program(self, key: ShapeKey) -> Optional[CompiledModule]:
        """Table read without stats side effects (scheduler probes)."""
        with self._lock:
            return self.programs.get(key)

    def warm_keys(self) -> List[ShapeKey]:
        """Every ShapeKey with a compiled program (scheduler probes)."""
        with self._lock:
            return list(self.programs.keys())

    def key_for_extents(
        self, extents: Union[int, Sequence[int]]
    ) -> ShapeKey:
        """The ShapeKey of a given per-axis bucket-extent assignment."""
        if isinstance(extents, int):
            extents = (extents,)
        if len(extents) != len(self.axes):
            raise ValueError(
                f"expected {len(self.axes)} extents, got {len(extents)}"
            )
        return ShapeKey(
            tuple(
                AxisKey(pa.policy.name, int(e), pa.label)
                for pa, e in zip(self.axes, extents)
            )
        )

    def nearest_warm(
        self, ns: Union[int, Sequence[int]]
    ) -> Optional[ShapeKey]:
        """Smallest warm bucket that *dominates* the concrete extents.

        The fallback-domination rule (DESIGN.md): a warm bucket is a
        legal pad-up target iff every axis extent is >= the concrete
        extent — the dispatch then runs as an ordinary padded call of
        that bucket, bitwise equal to the warm program's own output on
        the same padded inputs.  Among legal buckets the one with the
        fewest total cells (ties: lexicographically smallest extents)
        wins, minimizing the fallback pad premium.
        """
        if isinstance(ns, int):
            ns = (ns,)
        ns = tuple(int(n) for n in ns)
        with self._lock:
            warm = list(self.programs.keys())
        best: Optional[ShapeKey] = None
        best_rank: Tuple[int, Tuple[int, ...]] = (0, ())
        for k in warm:
            ext = k.extents
            if len(ext) != len(ns):
                continue
            if any(e < n for e, n in zip(ext, ns)):
                continue
            rank = (int(np.prod(ext)), ext)
            if best is None or rank < best_rank:
                best, best_rank = k, rank
        return best

    def submit_key(
        self,
        key: ShapeKey,
        args: Optional[Tuple[Any, ...]] = None,
        args_fn: Optional[Callable[[], Tuple[Any, ...]]] = None,
        *,
        foreground: bool = True,
    ) -> Future:
        """Queue ``key``'s compile on the service; returns its future.

        ``args_fn`` defers example-arg construction (e.g. a bucket-sized
        KV cache) to the worker thread so submission itself stays cheap.
        An already-warm key returns a resolved future.
        """
        if self.service is None:
            raise RuntimeError("BucketedModule has no CompileService")
        with self._lock:
            mod = self.programs.get(key)
        if mod is not None:
            fut: Future = Future()
            fut.set_result(mod)
            return fut
        if args is None and args_fn is None:
            raise TypeError("submit_key needs args or args_fn")

        def build() -> CompiledModule:
            a = args if args is not None else args_fn()
            return self._program_for_key(key, a, background=True)

        return self.service.submit(
            self._service_key(key), build, foreground=foreground
        )

    def _resolve_dispatch(
        self, key: ShapeKey, ns: Tuple[int, ...], args: Tuple[Any, ...]
    ) -> Tuple[CompiledModule, ShapeKey]:
        """Pick the (program, bucket) a concrete call executes under.

        Sync mode: the exact bucket, compiled inline on a miss.  Async
        mode: the exact bucket when warm; otherwise submit it to the
        service and pad into ``nearest_warm`` — blocking on the future
        only when no warm bucket dominates (the very first program).
        """
        if not self.async_compile or self.service is None:
            return self._program_for_key(key, args), key
        with self._lock:
            mod = self.programs.get(key)
        if mod is not None:
            self.stats.note_lookup(hit=True)
            return mod, key
        fut = self.submit_key(key, args=args, foreground=True)
        warm = self.nearest_warm(ns)
        if warm is not None:
            mod = self.lookup_program(warm)
            if mod is not None:
                self.stats.note_fallback(
                    int(np.prod(warm.extents)) - int(np.prod(key.extents))
                )
                return mod, warm
        t0 = time.perf_counter()
        mod = fut.result()
        self.stats.note_wait(time.perf_counter() - t0)
        return mod, key

    def _plan_for(
        self, mod: CompiledModule, key: ShapeKey, ns: Tuple[int, ...]
    ) -> PadPlan:
        out_axes = self._out_axes_flat.get(key)
        if out_axes is None:
            # broadcast each axis's out spec over the (per-bucket
            # constant) output tree: a dummy instance carries the
            # structure; zip the per-axis views into per-leaf vectors
            n_out = mod.capture.out_tree.num_leaves
            dummy = jax.tree_util.tree_unflatten(
                mod.capture.out_tree, list(range(n_out))
            )
            per_axis = [flatten_axes(pa.out_axes, dummy) for pa in self.axes]
            out_axes = tuple(tuple(v) for v in zip(*per_axis))
            self._out_axes_flat[key] = out_axes
        return PadPlan(
            n_valid=ns,
            extent=key.extents,
            in_axes=mod.capture.poly_axes_flat(),
            out_axes=out_axes,
            mode=self.pad_mode,
        )

    def __call__(self, *args: Any) -> Any:
        # hot path: one pytree flatten feeds dispatch AND execution
        flat, tree = jax.tree_util.tree_flatten(args)
        key, ns = self._shape_key_flat(flat, args)
        # async mode may substitute a warm dominating bucket for a cold
        # exact key; the pad plan then pads up to *that* bucket's extents
        mod, use_key = self._resolve_dispatch(key, ns, args)
        flat = mod._filter_flat_inputs(flat, tree)
        plan = self._plan_for(mod, use_key, ns)
        outs = mod.executor.execute_padded(flat, plan=plan)
        self.stats.note_dispatch(use_key, ns, use_key.extents)
        return mod._unflatten_outputs(outs)

    # -- eviction ---------------------------------------------------------

    def evict_cold(self, max_programs: int) -> List[ShapeKey]:
        """Retire least-recently-dispatched programs beyond a budget.

        The program table never shrinks on its own — a ladder policy
        bounds it, but a server that saw a one-off traffic spike keeps
        the spike's bucket programs (and their pooled buffers) alive
        forever.  This trims the table to ``max_programs`` entries by
        the ``BucketStats.per_bucket_last_dispatch`` recency trail
        (never-dispatched programs evict first), releasing each evicted
        bucket's pooled device buffers.  Returns the evicted ShapeKeys;
        a later dispatch of an evicted bucket recompiles it (counted as
        a fresh ``compiles``) — callers trade table memory for that
        recompile risk.
        """
        if max_programs < 0:
            raise ValueError(f"max_programs must be >= 0, got {max_programs}")
        with self._lock:
            excess = len(self.programs) - max_programs
            if excess <= 0:
                return []
            last = self.stats.per_bucket_last_dispatch
            victims = sorted(
                self.programs, key=lambda k: last.get(str(k), 0)
            )[:excess]
            victim_mods = [self.programs[k] for k in victims]
            for k in victims:
                del self.programs[k]
                self._out_axes_flat.pop(k, None)
                self._build_locks.pop(k, None)
        for k, m in zip(victims, victim_mods):
            self.pool.drop(bucket_pool_key(k))
            self.stats.note_eviction(k)
            # eviction coherence: drop the retired program's compile-
            # cache memory entry too, so the LRU stops pinning a dead
            # executor.  The disk entry (if any) survives — a later
            # re-dispatch replays it instead of doing a full build.
            ck = m.result.cache_key
            if ck is not None and self.compiler.cache is not None:
                self.compiler.cache.drop(ck)
        return victims

    def refit_policy(
        self, new_policy: Union[str, BucketPolicy], axis: int = 0
    ) -> BucketPolicy:
        """Swap one polymorphic axis's bucket policy in place (re-fit).

        The replacement keeps the *old policy's name*: AxisKeys embed
        the policy name, so renaming would orphan every compiled
        program and pooled buffer set at extents both policies map to.
        With the name pinned, a re-fit that keeps a rung leaves that
        rung's program, compile-cache entry, and buffer pool directly
        addressable; dropped rungs' programs stay legal pad-up targets
        for ``nearest_warm`` (domination compares extents only) until
        ``evict_cold`` retires them.  Returns the installed policy.
        """
        new_policy = get_bucket_policy(new_policy)
        with self._lock:
            old_axis = self.axes[axis]
            # pin the name (frozen dataclass → object.__setattr__, the
            # same escape hatch their own __post_init__ uses)
            object.__setattr__(new_policy, "name", old_axis.policy.name)
            axes = list(self.axes)
            axes[axis] = PolyAxis(
                in_axes=old_axis.in_axes, out_axes=old_axis.out_axes,
                policy=new_policy, label=old_axis.label,
            )
            self.axes = tuple(axes)
            if axis == 0:  # keep the 1-D legacy view coherent
                self.policy = new_policy
        return new_policy

    # -- transparency -----------------------------------------------------

    @property
    def last_result(self) -> Optional[CompilationResult]:
        """The most recently compiled bucket's CompilationResult."""
        with self._lock:
            mods = list(self.programs.values())
        return mods[-1].result if mods else None

    def bucket_table(self) -> Dict[str, ExecutorStats]:
        """ShapeKey string -> that bucket program's executor stats."""
        with self._lock:
            return {str(k): m.stats for k, m in self.programs.items()}


class ForgeCompiler:
    """Four-phase compiler facade (paper Figure 1).

    Phase 4 is delegated to a pluggable :class:`~repro.core.backends.Backend`
    (``interpret`` | ``segment_jit`` | ``reference``) resolved from the
    ``backend=`` knob (argument wins over ``config.backend``), and the
    backend build is memoized in a content-addressed compile cache keyed
    by the lowered program's RGIR fingerprint.
    """

    def __init__(
        self,
        config: Optional[PipelineConfig] = None,
        *,
        reorder: bool = True,
        backend: Optional[str] = None,
        cache: Optional[CompileCache] = None,
    ):
        self.config = config or PipelineConfig()
        self.reorder = reorder
        self.backend_name = backend or self.config.backend
        get_backend(self.backend_name)  # fail fast on unknown names
        self.cache = cache if cache is not None else (
            get_compile_cache() if self.config.compile_cache else None
        )

    def compile(
        self,
        fn: Callable,
        *example_args: Any,
        shape_key: Optional[ShapeKey] = None,
        poly_axes: Optional[AxisSpec] = None,
        poly_axes_nd: Optional[Sequence[AxisSpec]] = None,
    ) -> CompiledModule:
        """Compile ``fn`` specialized to ``example_args``'s shapes.

        ``shape_key``/``poly_axes_nd`` are set by the bucketing front
        (:class:`BucketedModule`): the example args are then the canonical
        *bucket* shapes, the (possibly multi-axis) ShapeKey joins the
        compile-cache key, and the capture records which input dims
        carry each polymorphic axis.  ``poly_axes`` is the 1-D
        shorthand.
        """
        t_total = time.perf_counter()
        xla0 = xla_compile_seconds()

        # Phase 1 — capture
        with span("forge.capture"):
            cap = trace_to_graph(
                fn, *example_args, poly_axes=poly_axes,
                poly_axes_nd=poly_axes_nd,
            )
        g = cap.graph
        nodes_before = g.num_nodes()

        # Phase 2 — optimization passes
        t0 = time.perf_counter()
        with span("forge.optimize"):
            records = run_forge_passes(g, cfg=self.config)
        optimize_ms = (time.perf_counter() - t0) * 1e3

        # Phase 3 — lowering
        t0 = time.perf_counter()
        with span("forge.lower"):
            prog = lower_to_rgir(g)
        lower_ms = (time.perf_counter() - t0) * 1e3

        # Phase 4 — backend codegen (compile-cache hit: a dictionary read)
        t0 = time.perf_counter()
        with span("forge.backend"):
            executor, cache_key, cache_hit, disk_hit = self._build(
                prog, shape_key
            )
        backend_ms = (time.perf_counter() - t0) * 1e3

        cost = score_graph(g, self.config.precision)
        result = CompilationResult(
            nodes_before=nodes_before,
            nodes_after=g.num_nodes(),
            fused_ops=cost.n_fused,
            attention_fused=cost.n_attn_fused,
            pass_records=records,
            capture_ms=cap.capture_ms,
            optimize_ms=optimize_ms,
            lower_ms=lower_ms,
            backend_ms=backend_ms,
            xla_ms=(xla_compile_seconds() - xla0) * 1e3,
            total_ms=(time.perf_counter() - t_total) * 1e3,
            # on a hit the executor is shared: report its analysis stats
            # but not the run counters other modules accumulated on it
            executor_stats=(
                executor.stats.fresh_snapshot() if cache_hit
                else executor.stats
            ),
            cost=cost,
            tied_weights=len(cap.tied_map),
            config=self.config,
            backend=self.backend_name,
            cache_hit=cache_hit,
            cache_disk_hit=disk_hit,
            cache_key=cache_key,
            cache_hits=self.cache.stats.hits if self.cache else 0,
            cache_misses=self.cache.stats.misses if self.cache else 0,
            shape_key=str(shape_key) if shape_key is not None else None,
        )
        return CompiledModule(executor, cap, result, g)

    def _build(self, prog, shape_key: Optional[ShapeKey]):
        """Phase 4: the backend's executor for ``prog``, through the
        compile cache.  Returns (executor, cache_key, cache_hit,
        disk_hit)."""
        backend = get_backend(self.backend_name)
        cache_key: Optional[str] = None
        executor = None
        disk_hit = False
        if self.cache is not None:
            try:
                cache_key = make_cache_key(
                    self.backend_name,
                    self.reorder,
                    fingerprint_program(prog),
                    shape_key,
                )
            except UncacheableProgram:
                # tracer-valued constants (compile inside an enclosing
                # trace): no stable content address — bypass the cache
                cache_key = None
            if cache_key is not None:
                loader = None
                if self.cache.store is not None:
                    # persistent tier: rehydrate the executor from the
                    # stored analysis + exported segment programs
                    # against this freshly lowered same-fingerprint RGIR
                    came_from_disk = []

                    def loader(entry, _prog=prog, _mark=came_from_disk):
                        ex = backend.build_from_entry(
                            _prog, entry, reorder=self.reorder
                        )
                        if ex is not None:
                            _mark.append(True)
                        return ex

                    executor = self.cache.get(cache_key, loader)
                    disk_hit = bool(came_from_disk) and executor is not None
                else:
                    executor = self.cache.get(cache_key)
        cache_hit = executor is not None
        if executor is None:
            executor = backend.build(prog, reorder=self.reorder)
            if self.cache is not None and cache_key is not None:
                disk_entry = None
                if self.cache.store is not None:
                    try:
                        disk_entry = backend.export_entry(prog, executor)
                    except Exception:
                        disk_entry = None
                self.cache.put(cache_key, executor, disk_entry=disk_entry)
        return executor, cache_key, cache_hit, disk_hit

    def compile_bucketed(
        self,
        fn: Callable,
        *example_args: Any,
        axes: Optional[Sequence[PolyAxis]] = None,
        in_axes: AxisSpec = 0,
        out_axes: AxisSpec = 0,
        policy: Union[str, BucketPolicy] = "pow2",
        pad_mode: str = "edge",
        async_compile: bool = False,
        service: Optional[CompileService] = None,
    ) -> "BucketedModule":
        """Build a shape-generalized multi-program front over ``fn``.

        ``axes`` holds one :class:`PolyAxis` per polymorphic dimension
        (e.g. batch × sequence for whole-prompt prefill); the 1-D
        shorthand ``in_axes``/``out_axes``/``policy`` marks a single
        batch-polymorphic dimension.  Each axis's policy independently
        bounds the program grid.  When ``example_args`` are given their
        cell is compiled eagerly (warmup); otherwise the first call per
        cell pays the compile.
        """
        mod = BucketedModule(
            self, fn, axes=axes, in_axes=in_axes, out_axes=out_axes,
            policy=policy, pad_mode=pad_mode,
            async_compile=async_compile, service=service,
        )
        if example_args:
            mod.program_for(*example_args)
        return mod


def forge_compile(
    fn: Callable,
    *example_args: Any,
    config: Optional[PipelineConfig] = None,
    backend: Optional[str] = None,
    **config_kwargs: Any,
) -> CompiledModule:
    """One-shot convenience API: ``forge_compile(f, x, backend="segment_jit")``."""
    if config is None:
        config = PipelineConfig(**config_kwargs)
    return ForgeCompiler(config, backend=backend).compile(fn, *example_args)


def forge_compile_bucketed(
    fn: Callable,
    *example_args: Any,
    axes: Optional[Sequence[PolyAxis]] = None,
    in_axes: AxisSpec = 0,
    out_axes: AxisSpec = 0,
    policy: Union[str, BucketPolicy] = "pow2",
    pad_mode: str = "edge",
    async_compile: bool = False,
    service: Optional[CompileService] = None,
    config: Optional[PipelineConfig] = None,
    backend: Optional[str] = None,
    **config_kwargs: Any,
) -> BucketedModule:
    """Shape-generalized convenience API: one program per ShapeKey cell.

    ``forge_compile_bucketed(f, x, in_axes=0, policy="pow2")`` compiles
    ``x``'s bucket eagerly and lazily adds further buckets on demand;
    pass ``axes=(PolyAxis(...), ...)`` for multi-axis (e.g. batch ×
    sequence) bucketing.
    """
    if config is None:
        config = PipelineConfig(**config_kwargs)
    return ForgeCompiler(config, backend=backend).compile_bucketed(
        fn, *example_args, axes=axes, in_axes=in_axes, out_axes=out_axes,
        policy=policy, pad_mode=pad_mode,
        async_compile=async_compile, service=service,
    )
