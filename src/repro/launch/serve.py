"""Batched serving driver: whole-prompt prefill + decode loop over the
compiled steps, with 2-D shape-generalized bucketing and slot-level
continuous batching (per-row decode positions, mid-generation admission
into finished slots, pad-waste-aware packing).

The serve path is where the Forge pipeline earns its keep at runtime:
the decode step is compiled once per batch ShapeKey *bucket* (capture →
fusion → RGIR → scheduled executor) and replayed either as one XLA
program (``--mode jit``, the NNFactory compile-then-run analogue) or
through a Phase-4 backend executor (``--mode forge``).

``--mode forge`` is rebuild-free on both axes: a request group of batch
size B with prompt length P is admitted, padded up to
``(batch_policy.bucket(B), seq_policy.bucket(P))`` (edge-replicated —
provably inert, see DESIGN.md §Shape generalization), prefilled in ONE
whole-prompt forward pass on the grid cell's compiled ``prefill_step``
program (the KV cache written in one shot, causal within the chunk),
then decoded on the batch bucket's program with the padding rows sliced
off the emitted tokens.  After :meth:`BatchedServer.warmup` no (batch,
prompt-length) pair within the ladder grid ever re-runs Phases 1-4 —
compile cost (``compile_s``) and TTFT are reported separately from
steady-state decode throughput so bucket reuse is visible from the CLI.

Since the decode position became a per-row vector, the forge fronts
compile the *slot* signature — ``(params, cache, tok(B,1), pos(B,),
slot_mask(B,))`` — so the same compiled bucket programs serve both
group admission (``generate``: all rows share one position) and the
:class:`SlotScheduler` (``SlotScheduler.run``: ragged positions, finished
slots swapped for queued requests mid-generation, buckets packed
exactly).  See DESIGN.md §Continuous batching.

Usage (CPU-scale):
  PYTHONPATH=src python -m repro.launch.serve --arch forge-125m --smoke \
      --batch 4 --prompt-len 32 --gen 32
  PYTHONPATH=src python -m repro.launch.serve --arch forge-125m --smoke \
      --mode forge --sweep 1,4 --prompt-sweep 17,32,48,100 --gen 8
  PYTHONPATH=src python -m repro.launch.serve --arch forge-125m --smoke \
      --mode forge --continuous 24 --max-slots 8 --gen 12
"""
from __future__ import annotations

import argparse
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get_config
from ..core.paging import TRASH_PAGE, build_row_table, pages_for
from ..core.shapekey import LadderPolicy, propose_rungs
from ..models import get_model
from ..runtime import chaos
from ..runtime.chaos import RequestError, SystemError_
from ..runtime.trace import gc_spans, span
from .steps import (
    POISON_TOKEN,
    blend_cache_rows,
    gather_cache_rows,
    guarded_argmax,
    make_serve_step,
    supports_slot_decode,
)


#: JAX's persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR``
#: is unset: one fixed directory at the root of the checkout, so every
#: process run from it finds what an earlier one compiled
DEFAULT_JAX_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def setup_jax_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` decides where it is set (JAX reads it
    at import); otherwise :data:`DEFAULT_JAX_CACHE_DIR`.  Call it before
    the first compile: the cache binds its directory on first use, so a
    later move resets it.  Raises ``OSError`` when the directory cannot
    be created or written.  The Forge disk store (``--cache-dir``) is a
    separate tier and never moves this one.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_JAX_CACHE_DIR
    os.makedirs(path, exist_ok=True)
    if not os.access(path, os.W_OK):
        raise PermissionError(f"JAX compilation cache {path} is not writable")
    if jax.config.jax_compilation_cache_dir != path:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", path)
        compilation_cache.reset_cache()
    return path


class BatchedServer:
    """Bucketed batch server with greedy decoding.

    ``mode='forge'`` routes the decode step through the four-phase Forge
    pipeline behind a :class:`~repro.core.compiler.BucketedModule`: one
    compiled program per ShapeKey bucket (``bucket_policy``, pow2 ladder
    by default), dispatched by the concrete batch extent.  The KV cache
    and token stream live at the bucket extent for the whole generation,
    so each decode step is a plain program replay — no per-step padding,
    no module rebuilds on batch-size transitions.

    For slot-capable families the decode front compiles the vectorized
    slot signature (per-row ``pos`` + ``slot_mask``); ``generate`` runs
    it with a broadcast position and an all-true mask (group admission
    as a special case of slot decode), and :class:`SlotScheduler` drives
    the same programs with ragged positions — one program table serves
    both, so continuous batching adds zero compiles.

    Prefill runs through a second, 2-D front: one compiled
    ``prefill_step`` program per (batch-bucket × sequence-bucket) grid
    cell (``seq_bucket_policy``, a fixed ladder by default), consuming
    the whole edge-padded prompt block in one forward pass with a causal
    length mask — the KV cache is written in one shot and TTFT stops
    scaling with per-token dispatches.  Recurrent families (rg-lru,
    xLSTM) join the same grid through the chunked state scan: the whole
    prompt block folds into the recurrent state via an associative scan
    (per-row ``length`` bounds each row's scan, since state consumes
    every chunk token).  Only families where the algorithm couples
    tokens across the block fall back to the sequential decode-step
    loop automatically, as do prompts whose sequence bucket would not
    fit ``max_len``.  The prefill front takes
    a ``slot_mask`` too: the slot scheduler prefills a queued prompt
    into a finished slot's KV rows while every other slot's cache stays
    bitwise untouched (write-inert masking, DESIGN.md §Continuous
    batching).

    Steady-state replay avoids re-allocation on two levels (DESIGN.md
    §Donation, §Buffer pooling): accel segments donate dying live-in
    buffers to XLA (``donate_argnums`` through the backend path), and
    each generation's KV-cache pytree is parked in the BucketedModule's
    per-bucket :class:`~repro.core.compiler.BufferPool` on completion —
    the next admission to that bucket reuses the device buffers through
    a donating zero-fill instead of allocating a fresh cache.

    Remaining gap vs ``mode='jit'``: cache leaves are program *inputs*,
    which the donation analysis deliberately never donates (the executor
    does not own caller buffers), so each decode step still materializes
    a fresh cache pytree on device (~2x cache memory at large
    ``max_len``).  Pooling recycles at admission granularity; per-step
    in-place cache update needs caller-opt-in input donation.  With the
    paged store the model step already writes in place inside its layer
    loop (the stacked store rides in the loop's carry); what remains is
    one copy of the non-donated input store into that carry per call.
    """

    def __init__(self, cfg, params, max_len: int = 256, mode: str = "jit",
                 backend: str = "segment_jit", bucket_policy: str = "pow2",
                 seq_bucket_policy: str = "ladder:16,32,64,128,256",
                 prefill: str = "auto", paged: bool = False,
                 kv_page_size: int = 16, kv_pages: Optional[int] = None,
                 async_compile: bool = False, compile_workers: int = 2,
                 cache_dir: Optional[str] = None):
        setup_jax_compile_cache()
        self.cfg = cfg
        self.params = params
        self.model = get_model(cfg)
        self.max_len = max_len
        self.serve_step = make_serve_step(cfg)
        if mode == "jit":
            self.serve_step = jax.jit(self.serve_step, donate_argnums=(1,))
        self.mode = mode
        self.backend = backend
        self.bucket_policy = bucket_policy
        #: sequence-axis bucket policy for the 2-D prefill program grid
        self.seq_bucket_policy = seq_bucket_policy
        #: "auto" (batched when the family supports it and the prompt
        #: fits the ladder) | "batched" | "sequential" (force the legacy
        #: token-at-a-time loop — the TTFT baseline)
        self.prefill_policy = prefill
        #: whether the forge fronts carry the vectorized slot signature
        #: (per-row pos + slot_mask); families outside the slot contract
        #: compile the legacy scalar-position signature instead
        self.slot_capable = supports_slot_decode(cfg)
        #: recurrent families' prefill consumes every chunk token into
        #: state — their programs take a per-row ``length`` operand
        self.prefill_takes_length = self.model.prefill_takes_length
        #: the decode multi-program front (mode=forge); built once
        self.bucketed = None
        #: the 2-D (batch × sequence) whole-prompt prefill front; None
        #: for families without a batched prefill
        self.prefill_bucketed = None
        #: per-leaf cache batch axes (set with the fronts; the slot
        #: scheduler's bucket-resize row gather reads it)
        self.cache_axes = None
        #: how the most recent prefill ran: "batched" (KV chunk write) |
        #: "chunked" (recurrent state scan) | "sequential" (decode loop)
        self.last_prefill_mode = None
        #: most recently dispatched bucket program (CLI transparency)
        self.forge_module = None
        #: paged-KV serving (DESIGN.md §Paged KV cache): the per-slot
        #: contiguous cache rows are replaced by a shared page pool +
        #: per-slot page tables.  Scheduler-only — ``generate`` raises.
        self.paged = bool(paged)
        self.kv_page_size = int(kv_page_size)
        self.kv_pages = kv_pages
        self.page_pool = None
        self.prefix_tree = None
        #: server-resident page store (no batch axis): every entry of the
        #: model's ``init_paged_cache`` but the page table, each leaf
        #: (n_layers, num_pages, page_size, row) in token-major rows;
        #: every slot reads/writes it through its page-table row, and the
        #: server never looks inside it
        self.page_store = None
        self.max_pages_per_slot = 0
        if self.paged:
            from .steps import supports_paged_decode
            if mode != "forge":
                raise ValueError("paged KV serving needs mode='forge'")
            if not supports_paged_decode(cfg):
                raise ValueError(
                    f"family {cfg.family!r} has no paged decode path"
                )
            if max_len % self.kv_page_size:
                raise ValueError(
                    f"max_len={max_len} must be a multiple of "
                    f"kv_page_size={self.kv_page_size}"
                )
        #: async background compilation (DESIGN.md §Async compilation):
        #: a cold bucket compiles on the worker pool while dispatches
        #: pad into the nearest warm dominating bucket; a dispatch only
        #: blocks when no warm bucket can hold it (the first program)
        self.async_compile = bool(async_compile)
        self.compile_service = None
        if self.async_compile:
            from ..core import CompileService
            self.compile_service = CompileService(workers=compile_workers)
        #: persistent on-disk compile tier (--cache-dir): bucket
        #: programs (Phase 4a-c analysis + jax.export'ed segment
        #: executables) survive process restarts — a restart replays
        #: the whole warmed ladder with zero full builds
        self.cache_dir = cache_dir
        self.compile_cache = None
        if cache_dir is not None:
            from ..core import CompileCache, DiskCacheStore, get_compile_cache
            store = DiskCacheStore(cache_dir)
            self.compile_cache = CompileCache(store=store)
            # the per-block forge bodies (models/_forge.py, cfg.fuse ==
            # 'forge') compile through the process-global cache — give
            # it the same disk tier so a restart replays them too and
            # the whole process runs zero full builds
            g = get_compile_cache()
            if g.store is None:
                g.store = store
        self._front_lock = threading.Lock()
        #: donating zero-fill: recycles a pooled KV cache's device buffers
        #: in place instead of allocating a fresh bucket-sized pytree
        self._cache_reset = jax.jit(
            lambda c: jax.tree_util.tree_map(jnp.zeros_like, c),
            donate_argnums=(0,),
        )

    # -- bucketed front ---------------------------------------------------

    def _ensure_bucketed(self):
        """Build the BucketedModule fronts once (lazy, mode=forge only)."""
        with self._front_lock:
            if self.bucketed is not None:
                return
            if self.paged:
                self._build_paged_front()
                return
            from ..core import ForgeCompiler, PipelineConfig, PolyAxis
            from ..core.shapekey import infer_poly_axes
            from .steps import (
                make_batched_prefill_step,
                make_slot_prefill_step,
                make_slot_serve_step,
            )

            # per-leaf cache batch axes differ across model families
            # (transformer: axis 1 under the layer dim; recurrent states:
            # axis 0) — infer them by differencing two cache instantiations,
            # abstractly (eval_shape): only shapes are read, so no buffers
            # are allocated
            cache_axes = infer_poly_axes(
                lambda b: jax.eval_shape(
                    lambda: self.model.init_cache(self.cfg, b, self.max_len)
                )
            )
            self.cache_axes = cache_axes
            compiler = ForgeCompiler(PipelineConfig(backend=self.backend),
                                     cache=self.compile_cache)
            # the 2-D prefill front: batch × sequence, one program per
            # grid cell.  Only tokens/logits carry the sequence axis —
            # the KV cache is max_len-resident on both sides.
            prefill_step = None
            if self.prefill_policy != "sequential":
                prefill_step = (
                    make_slot_prefill_step(self.cfg) if self.slot_capable
                    else make_batched_prefill_step(self.cfg)
                )
            prefill_front = None
            if prefill_step is not None:
                # slot signature: (params, cache, tokens, pos, slot_mask)
                # legacy:         (params, cache, tokens, pos)
                # recurrent:      … + trailing per-row length (B,)
                b_in = ((None, cache_axes, 0, None, 0) if self.slot_capable
                        else (None, cache_axes, 0, None))
                s_in = ((None, None, 1, None, None) if self.slot_capable
                        else (None, None, 1, None))
                if self.prefill_takes_length:
                    b_in = b_in + (0,)
                    s_in = s_in + (None,)
                prefill_front = compiler.compile_bucketed(
                    prefill_step,
                    axes=(
                        PolyAxis(in_axes=b_in, out_axes=(0, cache_axes),
                                 policy=self.bucket_policy, label="B"),
                        PolyAxis(in_axes=s_in, out_axes=(1, None),
                                 policy=self.seq_bucket_policy, label="S"),
                    ),
                    async_compile=self.async_compile,
                    service=self.compile_service,
                )
            # decode front: one program per batch bucket.  Slot-capable
            # families compile (params, cache, token, pos(B,), mask(B,))
            # — group admission broadcasts into it, the slot scheduler
            # drives it ragged; the program table is shared.
            if self.slot_capable:
                step = make_slot_serve_step(self.cfg)
                in_axes = (None, cache_axes, 0, 0, 0)
            else:
                step = make_serve_step(self.cfg)
                in_axes = (None, cache_axes, 0, None)
            self.bucketed = compiler.compile_bucketed(
                step,
                in_axes=in_axes,
                out_axes=(0, cache_axes),
                policy=self.bucket_policy,
                async_compile=self.async_compile,
                service=self.compile_service,
            )
            self.prefill_bucketed = prefill_front

    def _build_paged_front(self):
        """Build the paged-KV fronts + pool state (called under the lock).

        Unlike the contiguous fronts, the KV store carries NO batch axis
        — ``in_axes`` marks it None on both sides, so every bucket
        program reads and returns the one server-resident page store.
        Only the page table / tokens / pos / mask are bucket-shaped,
        which is what makes swap-in and rung resizes O(table): the
        pages themselves never move.
        """
        from ..core import ForgeCompiler, PipelineConfig, PolyAxis
        from ..core.paging import PagePool, PrefixTree
        from .steps import (
            dealias_tree,
            make_paged_prefill_step,
            make_paged_serve_step,
            paged_store,
        )

        ps = self.kv_page_size
        self.max_pages_per_slot = self.max_len // ps
        # default pool: eight full-length slots' worth of pages, plus
        # the reserved trash page (id 0) that absorbs masked writes
        num_pages = int(self.kv_pages or 8 * self.max_pages_per_slot + 1)
        self.page_pool = PagePool(num_pages, ps)
        self.prefix_tree = PrefixTree(self.page_pool)
        full = self.model.init_paged_cache(
            self.cfg, 1, self.max_len, num_pages=num_pages, page_size=ps
        )
        self.page_store = dealias_tree(paged_store(full))
        self.cache_axes = None  # no batch-polymorphic cache rows exist
        compiler = ForgeCompiler(PipelineConfig(backend=self.backend),
                                 cache=self.compile_cache)
        prefill_front = None
        if self.prefill_policy != "sequential":
            pstep = make_paged_prefill_step(self.cfg)
            if pstep is not None:
                # (params, store, page_table(B,MP), tokens(B,S),
                #  pos(B,), mask(B,), last(B,)) -> ((B, vocab) logits
                # of each row's last real column, store) — per-row pos
                # lets prefix-hit rows anchor their chunk at the skip
                # offset in the same dispatch as cold rows
                prefill_front = compiler.compile_bucketed(
                    pstep,
                    axes=(
                        PolyAxis(in_axes=(None, None, 0, 0, 0, 0, 0),
                                 out_axes=(0, None),
                                 policy=self.bucket_policy, label="B"),
                        PolyAxis(in_axes=(None, None, None, 1, None, None, None),
                                 out_axes=(None, None),
                                 policy=self.seq_bucket_policy, label="S"),
                    ),
                    async_compile=self.async_compile,
                    service=self.compile_service,
                )
        self.bucketed = compiler.compile_bucketed(
            make_paged_serve_step(self.cfg),
            in_axes=(None, None, 0, 0, 0, 0),
            out_axes=(0, None),
            policy=self.bucket_policy,
            async_compile=self.async_compile,
            service=self.compile_service,
        )
        self.prefill_bucketed = prefill_front

    def _bucket_extent(self, B: int) -> int:
        """Decode bucket extent for a batch size — async-aware.

        Sync mode: the policy's exact bucket (its program compiles
        inline on the first dispatch).  Async mode: the exact bucket
        when its program is warm; otherwise the exact key goes to the
        compile service and the smallest warm bucket that *dominates*
        B serves the admission padded up — the call only blocks when
        no warm bucket can hold the batch (the very first program).
        """
        self._ensure_bucketed()
        exact = self.bucketed.policy.bucket(B)
        if not self.async_compile:
            return exact
        return self._async_extent(exact)

    def _async_extent(self, exact: int) -> int:
        """Warm-fallback extent selection for the decode front."""
        front = self.bucketed
        key = front.key_for_extents(exact)
        if front.lookup_program(key) is not None:
            return exact
        fut = front.submit_key(
            key,
            args_fn=(lambda e=exact: self._decode_example_args(e)),
            foreground=True,
        )
        warm = front.nearest_warm(exact)
        if warm is not None:
            # fallback premium: the extra padded rows vs the exact rung
            front.stats.note_fallback(warm.extents[0] - exact)
            return warm.extents[0]
        # nothing dominates: the very first program must block
        t0 = time.perf_counter()
        fut.result()
        front.stats.note_wait(time.perf_counter() - t0)
        return exact

    def _decode_example_args(self, extent: int):
        """Bucket-shaped example args for a background decode compile.

        Built in the service worker thread (``submit_key`` defers via
        ``args_fn``) so submission stays cheap; the throwaway cache is
        only traced/padded, never served.
        """
        if self.paged:
            MP = self.max_pages_per_slot
            return (self.params, self.page_store,
                    jnp.zeros((extent, MP), jnp.int32),
                    jnp.zeros((extent, 1), jnp.int32),
                    jnp.zeros((extent,), jnp.int32),
                    jnp.zeros((extent,), bool))
        cache = self._build_cache(extent)
        tok = jnp.zeros((extent, 1), jnp.int32)
        return (self.params, cache) + self._decode_args(extent, tok, 0)

    def _prefill_example_args(self, extent: int, s_ext: int):
        """Example args for a background (extent × s_ext) cell compile."""
        if self.paged:
            MP = self.max_pages_per_slot
            return (self.params, self.page_store,
                    jnp.zeros((extent, MP), jnp.int32),
                    jnp.zeros((extent, s_ext), jnp.int32),
                    jnp.zeros((extent,), jnp.int32),
                    jnp.zeros((extent,), bool),
                    jnp.zeros((extent,), jnp.int32))
        cache = self._build_cache(extent)
        tokens = jnp.zeros((extent, s_ext), jnp.int32)
        return (self.params, cache) + self._prefill_args(extent, tokens, 0)

    def _decode_args(self, extent: int, tok, pos, active: Optional[Any] = None):
        """Bucket-program decode argument tuple for the front signature.

        ``pos`` scalar broadcasts to a per-row vector and ``active``
        defaults to all-true for slot-capable fronts; legacy fronts get
        the scalar position through unchanged.
        """
        if not self.slot_capable:
            return (tok, jnp.asarray(pos, jnp.int32))
        pos = jnp.asarray(pos, jnp.int32)
        if pos.ndim == 0:
            pos = jnp.full((extent,), pos, jnp.int32)
        if active is None:
            active = jnp.ones((extent,), bool)
        else:
            active = jnp.asarray(active, bool)
        return (tok, pos, active)

    def _prefill_args(self, extent: int, tokens, pos,
                      active: Optional[Any] = None, lengths=None):
        """Argument tail for the prefill front (scalar pos + slot mask).

        Recurrent fronts append a per-row ``lengths`` operand (default:
        the full chunk width — every token is real) bounding each row's
        state scan.
        """
        pos = jnp.asarray(pos, jnp.int32)
        if not self.slot_capable:
            tail = (tokens, pos)
        else:
            if active is None:
                active = jnp.ones((extent,), bool)
            else:
                active = jnp.asarray(active, bool)
            tail = (tokens, pos, active)
        if self.prefill_takes_length:
            if lengths is None:
                lengths = jnp.full((extent,), tokens.shape[1], jnp.int32)
            else:
                lengths = jnp.asarray(lengths, jnp.int32)
            tail = tail + (lengths,)
        return tail

    def _build_cache(self, extent: int):
        from .steps import dealias_tree

        # donation-safe: identical zero-state leaves must not share buffers
        return dealias_tree(
            self.model.init_cache(self.cfg, extent, self.max_len)
        )

    def _acquire_cache(self, extent: int):
        """Bucket-extent KV cache: pooled in forge mode, fresh otherwise.

        The pool key is the bare batch extent — the same contract
        :func:`repro.core.compiler.bucket_pool_key` gives a 1-D
        ShapeKey, so ``BucketedModule.evict_cold`` releases what this
        method parks.
        """
        if self.bucketed is None:
            return self._build_cache(extent)
        return self.bucketed.pool.acquire(
            extent,
            lambda: self._build_cache(extent),
            reset=self._cache_reset,
        )

    def _release_cache(self, extent: int, cache) -> None:
        """Park a finished generation's cache for the next admission."""
        if self.bucketed is not None:
            self.bucketed.pool.release(extent, cache)

    def _bucket_args(self, prompts_b: np.ndarray):
        """Bucket-shaped (cache, first-token) for a padded prompt array."""
        cache = self._acquire_cache(prompts_b.shape[0])
        tok = jnp.asarray(prompts_b[:, :1], jnp.int32)
        return cache, tok

    def _seq_bucket_extent(self, P: int, extent: Optional[int] = None):
        """Sequence bucket for a prompt length, or None → sequential path.

        None when the family has no batched prefill, the policy rejects
        the length (ladder admission bound), or the bucket would not fit
        the cache (``max_len``).  Async mode (when the batch ``extent``
        is known) additionally requires a *warm* grid cell: a cold
        exact cell goes to the compile service and the smallest warm
        cell at the same batch extent with ``s' >= s`` serves the
        prompt edge-padded further right; with no such cell the prompt
        takes the sequential fill path — the decode program is warm by
        construction, so nothing stalls either way.
        """
        if self.prefill_bucketed is None:
            return None
        try:
            s = self.prefill_bucketed.axes[1].policy.bucket(P)
        except ValueError:
            return None
        if s > self.max_len:
            return None
        if not self.async_compile or extent is None:
            return s
        return self._async_cell_extent(extent, s)

    def _async_cell_extent(self, extent: int, s_ext: int) -> Optional[int]:
        """Warm-fallback sequence extent at a fixed batch extent."""
        front = self.prefill_bucketed
        key = front.key_for_extents((extent, s_ext))
        if front.lookup_program(key) is not None:
            return s_ext
        front.submit_key(
            key,
            args_fn=(lambda e=extent, s=s_ext:
                     self._prefill_example_args(e, s)),
            foreground=True,
        )
        # the batch extent is pinned by the decode bucket (the cache is
        # built at it), so only same-extent cells are legal pad targets
        best = None
        for k in front.warm_keys():
            es = k.extents
            if es[0] == extent and s_ext <= es[1] <= self.max_len:
                if best is None or es[1] < best:
                    best = es[1]
        if best is not None:
            front.stats.note_fallback(extent * (best - s_ext))
        return best

    def warmup(self, batch_sizes: Sequence[int],
               prompt_lens: Optional[Sequence[int]] = None) -> float:
        """Precompile the ladder grid covering ``batch_sizes`` (decode
        buckets) × ``prompt_lens`` (prefill grid cells).

        Returns the seconds spent compiling; afterwards serving any of
        these batch sizes — at any of these prompt lengths — never
        re-runs Phases 1-4.
        """
        if self.mode != "forge":
            return 0.0
        self._ensure_bucketed()
        if self.paged:
            return self._warmup_paged(batch_sizes, prompt_lens)
        t0 = time.perf_counter()
        if self.async_compile:
            self._submit_warmup(batch_sizes, prompt_lens)
        done = set()
        for B in batch_sizes:
            extent = self._bucket_extent(int(B))
            if extent in done:
                continue
            done.add(extent)
            prompts_b = np.zeros((extent, 1), np.int32)
            cache, tok = self._bucket_args(prompts_b)
            args = self._decode_args(extent, tok, 0)
            mod, key, _ = self.bucketed.program_for(self.params, cache, *args)
            # one throwaway step: warms the per-op eager-dispatch caches
            # the host segments hit, so the first *served* request per
            # bucket sees steady-state latency
            _, warm_cache = mod(self.params, cache, *args)
            # keep the counter invariant (executor total_calls sums to
            # BucketStats.calls) without skewing pad_waste: the throwaway
            # step's rows are all padding, none are served requests
            self.bucketed.stats.note_dispatch(key, 0, extent)
            # park the stepped cache: the first *served* admission per
            # bucket is then a pool hit (buffers recycled via zero-fill)
            self._release_cache(extent, warm_cache)
            self.forge_module = mod
        # prefill grid: one compile per (batch-bucket × seq-bucket) cell
        # actually reachable from the announced workload
        if prompt_lens and self.prefill_bucketed is not None:
            cells = set()
            for B in batch_sizes:
                extent = self._bucket_extent(int(B))
                for P in prompt_lens:
                    s_ext = self._seq_bucket_extent(int(P))
                    if s_ext is None or (extent, s_ext) in cells:
                        continue
                    cells.add((extent, s_ext))
                    tokens = jnp.zeros((extent, s_ext), jnp.int32)
                    cache = self._acquire_cache(extent)
                    pargs = self._prefill_args(extent, tokens, 0)
                    pmod, pkey, _ = self.prefill_bucketed.program_for(
                        self.params, cache, *pargs
                    )
                    _, warm_cache = pmod(self.params, cache, *pargs)
                    # all-padding throwaway, same invariant as decode
                    self.prefill_bucketed.stats.note_dispatch(
                        pkey, (0, 0), pkey.extents
                    )
                    self._release_cache(extent, warm_cache)
        return time.perf_counter() - t0

    def _submit_warmup(self, batch_sizes: Sequence[int],
                       prompt_lens: Optional[Sequence[int]]) -> None:
        """Queue every reachable grid cell on the compile service.

        Speculative priority — a foreground request discovering a cold
        bucket mid-warmup jumps the queue via promotion.  With W
        workers the warmup wall approaches sum(cells)/W instead of
        sum(cells); against a populated ``--cache-dir`` the workers
        replay disk entries, so warmup collapses to the deserialization
        cost with zero full builds.
        """
        front = self.bucketed
        done = set()
        for B in batch_sizes:
            extent = front.policy.bucket(int(B))
            if extent in done:
                continue
            done.add(extent)
            front.submit_key(
                front.key_for_extents(extent),
                args_fn=(lambda e=extent: self._decode_example_args(e)),
                foreground=False,
            )
        pf = self.prefill_bucketed
        if prompt_lens and pf is not None:
            cells = set()
            for B in batch_sizes:
                extent = front.policy.bucket(int(B))
                for P in prompt_lens:
                    try:
                        s_ext = pf.axes[1].policy.bucket(int(P))
                    except ValueError:
                        continue
                    if s_ext > self.max_len or (extent, s_ext) in cells:
                        continue
                    cells.add((extent, s_ext))
                    pf.submit_key(
                        pf.key_for_extents((extent, s_ext)),
                        args_fn=(lambda e=extent, s=s_ext:
                                 self._prefill_example_args(e, s)),
                        foreground=False,
                    )
        self.compile_service.wait_idle()

    def _warmup_paged(self, batch_sizes: Sequence[int],
                      prompt_lens: Optional[Sequence[int]]) -> float:
        """Paged-front warmup: all-false slot masks + trash-only page
        tables route every throwaway write to the trash page, so the
        warmed store stays all-zeros and the pool state is untouched."""
        t0 = time.perf_counter()
        if self.async_compile:
            self._submit_warmup(batch_sizes, prompt_lens)
        MP = self.max_pages_per_slot
        store = self.page_store
        done = set()
        for B in batch_sizes:
            extent = self._bucket_extent(int(B))
            if extent in done:
                continue
            done.add(extent)
            args = (jnp.zeros((extent, MP), jnp.int32),
                    jnp.zeros((extent, 1), jnp.int32),
                    jnp.zeros((extent,), jnp.int32),
                    jnp.zeros((extent,), bool))
            mod, key, _ = self.bucketed.program_for(self.params, store, *args)
            _, store = mod(self.params, store, *args)
            self.bucketed.stats.note_dispatch(key, 0, extent)
            self.forge_module = mod
        if prompt_lens and self.prefill_bucketed is not None:
            cells = set()
            for B in batch_sizes:
                extent = self._bucket_extent(int(B))
                for P in prompt_lens:
                    s_ext = self._seq_bucket_extent(int(P))
                    if s_ext is None or (extent, s_ext) in cells:
                        continue
                    cells.add((extent, s_ext))
                    pargs = (jnp.zeros((extent, MP), jnp.int32),
                             jnp.zeros((extent, s_ext), jnp.int32),
                             jnp.zeros((extent,), jnp.int32),
                             jnp.zeros((extent,), bool),
                             jnp.zeros((extent,), jnp.int32))
                    pmod, pkey, _ = self.prefill_bucketed.program_for(
                        self.params, store, *pargs
                    )
                    _, store = pmod(self.params, store, *pargs)
                    self.prefill_bucketed.stats.note_dispatch(
                        pkey, (0, 0), pkey.extents
                    )
        self.page_store = store
        return time.perf_counter() - t0

    # -- serving ----------------------------------------------------------

    def prefill(self, prompts: np.ndarray):
        """Prefill the KV cache for a prompt group.

        Batched (whole-prompt, one forward pass) when the 2-D front
        covers the group; sequential decode-step replay otherwise.
        Returns bucket-shaped state in forge mode: ``(cache, next_tok,
        pos, step_fn, key)`` where the first ``prompts.shape[0]`` rows
        are the real requests.
        """
        B, P = prompts.shape
        if self.cfg.family == "encdec":
            raise NotImplementedError("use examples/ for enc-dec serving")
        if self.paged:
            raise NotImplementedError(
                "paged KV serving is slot-scheduled: drive it through "
                "SlotScheduler.run (page allocation is per-slot)"
            )

        if self.mode == "forge":
            self._ensure_bucketed()
            # batch extent first: in async mode the sequence-cell probe
            # needs to know which batch rung the group will run on
            extent = self._bucket_extent(B)
            s_ext = self._seq_bucket_extent(P, extent=extent)
            if s_ext is not None:
                return self._prefill_batched(prompts, s_ext, extent)
            return self._prefill_sequential(prompts, extent)
        self.last_prefill_mode = "sequential"
        cache = self._build_cache(B)
        next_tok = None
        for i in range(P):
            tok_i = jnp.asarray(prompts[:, i:i + 1], jnp.int32)
            next_tok, cache = self.serve_step(
                self.params, cache, tok_i, jnp.asarray(i, jnp.int32)
            )
        return cache, next_tok, P, self.serve_step, None

    def _group_step(self, mod, extent: int):
        """Adapt a bucket program to the group-admission loop signature.

        ``generate`` advances all rows in lockstep from one scalar
        position; slot-capable programs receive it broadcast to a
        per-row vector with an all-true slot mask (group admission is
        the degenerate slot schedule where every slot shares one
        request lifetime).
        """
        if not self.slot_capable:
            return mod

        # hoisted: the mask is all-true for the whole generation — only
        # the position vector changes per step (one broadcast fill)
        ones = jnp.ones((extent,), bool)

        def step(params, cache, tok, pos):
            pos_vec = jnp.full((extent,), jnp.asarray(pos, jnp.int32))
            return mod(params, cache, tok, pos_vec, ones)

        return step

    def _prefill_batched(self, prompts: np.ndarray, s_ext: int,
                         extent: Optional[int] = None):
        """Whole-prompt prefill on the (batch × sequence) grid cell.

        The prompt block is edge-padded on both axes, the cell's
        compiled ``prefill_step`` writes the KV cache in one shot (the
        causal length mask keeps padded tail columns out of every real
        column's receptive field), and the first generated token is read
        from the last *real* prompt column's logits.
        """
        B, P = prompts.shape
        if extent is None:
            extent = self._bucket_extent(B)
        prompts_b = np.pad(prompts, ((0, extent - B), (0, s_ext - P)),
                           mode="edge")
        cache = self._acquire_cache(extent)
        tokens = jnp.asarray(prompts_b, jnp.int32)
        # recurrent fronts: every row's real prompt ends at P (padded
        # rows are edge replicas, so P is right for them too) — the
        # state scan must stop there, unlike the positional KV mask
        pargs = self._prefill_args(
            extent, tokens, 0, lengths=np.full((extent,), P, np.int32)
        )
        pmod, pkey, _ = self.prefill_bucketed.program_for(
            self.params, cache, *pargs
        )
        logits, cache = pmod(self.params, cache, *pargs)
        self.prefill_bucketed.stats.note_dispatch(pkey, (B, P), pkey.extents)
        # mask: the padded tail columns' logits never escape — the next
        # token comes from the last real column (the padded rows decode
        # edge-replica tokens and are sliced off at the end)
        tok = jnp.argmax(logits[:, P - 1, :], axis=-1).astype(jnp.int32)[:, None]
        mod, key, _ = self.bucketed.program_for(
            self.params, cache, *self._decode_args(extent, tok, P)
        )
        self.forge_module = mod
        self.last_prefill_mode = (
            "chunked" if self.model.stateful_decode else "batched"
        )
        return cache, tok, P, self._group_step(mod, extent), key

    def _prefill_sequential(self, prompts: np.ndarray,
                            extent: Optional[int] = None):
        """Token-at-a-time prefill through the decode bucket program
        (recurrent families, or prompts outside the sequence ladder)."""
        B, P = prompts.shape
        if extent is None:
            extent = self._bucket_extent(B)
        # admit the group: edge-pad the prompt rows up to the bucket
        prompts_b = np.pad(prompts, ((0, extent - B), (0, 0)), mode="edge")
        cache, tok = self._bucket_args(prompts_b)
        mod, key, _ = self.bucketed.program_for(
            self.params, cache, *self._decode_args(extent, tok, 0)
        )
        self.forge_module = mod
        step = self._group_step(mod, extent)
        next_tok = None
        for i in range(P):
            tok_i = jnp.asarray(prompts_b[:, i:i + 1], jnp.int32)
            next_tok, cache = step(
                self.params, cache, tok_i, jnp.asarray(i, jnp.int32)
            )
            self.bucketed.stats.note_dispatch(key, B, prompts_b.shape[0])
        self.last_prefill_mode = "sequential"
        return cache, next_tok, P, step, key

    def _compile_s_total(self) -> float:
        """Phase 1-4 seconds accumulated across BOTH serve fronts."""
        total = self.bucketed.stats.compile_s if self.bucketed else 0.0
        if self.prefill_bucketed is not None:
            total += self.prefill_bucketed.stats.compile_s
        return total

    def generate(self, prompts: np.ndarray, n_new: int) -> Dict[str, Any]:
        B = prompts.shape[0]
        compile_s0 = self._compile_s_total()
        t0 = time.perf_counter()
        cache, tok, pos0, step, key = self.prefill(prompts)
        jax.block_until_ready(tok)  # TTFT: the first token is real here
        t_prefill = time.perf_counter() - t0
        out: List[np.ndarray] = [np.asarray(tok)]
        lat: List[float] = []
        try:
            for i in range(n_new - 1):
                t1 = time.perf_counter()
                tok, cache = step(
                    self.params, cache, tok, jnp.asarray(pos0 + i, jnp.int32)
                )
                jax.block_until_ready(tok)
                lat.append(time.perf_counter() - t1)
                out.append(np.asarray(tok))
                if key is not None:
                    self.bucketed.stats.note_dispatch(key, B, tok.shape[0])
        finally:
            # park the bucket-sized cache even on an interrupted decode
            # (the donating zero-fill makes any parked state reusable),
            # so the post-warmup pool hit rate survives transient errors
            if key is not None:
                self._release_cache(key.extent, cache)
        # mask: slice the padded rows off the emitted token stream
        toks = np.concatenate(out, axis=1)[:B]
        lat_ms = np.asarray(lat) * 1e3
        compile_s = self._compile_s_total() - compile_s0
        return {
            "tokens": toks,
            "prefill_s": t_prefill,
            "ttft_s": t_prefill,  # time to first token (prefill wall)
            "prefill_mode": self.last_prefill_mode,
            "compile_s": compile_s,  # Phase 1-4 time inside this call
            "decode_ms_mean": float(lat_ms.mean()) if len(lat_ms) else 0.0,
            "decode_ms_p50": float(np.percentile(lat_ms, 50)) if len(lat_ms) else 0.0,
            "decode_ms_p99": float(np.percentile(lat_ms, 99)) if len(lat_ms) else 0.0,
            "tok_per_s": B * max(len(lat), 1) / max(sum(lat), 1e-9),
        }

    def run_workload(self, groups: Sequence[np.ndarray], n_new: int
                     ) -> List[Dict[str, Any]]:
        """Serve a FIFO stream of request groups, one group at a time.

        Group admission: each group is admitted whole, padded to its
        bucket, and decoded in lockstep until the LAST row reaches
        ``n_new`` tokens — short requests pad-decode until the longest
        finishes, and the bucket's padding rows decode garbage for the
        whole generation.  This is the throughput *baseline*;
        :class:`SlotScheduler` retires each slot
        independently and swaps queued requests into finished slots
        mid-generation, converting both kinds of pad-decode into real
        tokens.

        Error isolation: a group that fails — malformed prompt array, a
        contained-but-unrecovered dispatch fault — completes with a
        typed error outcome (``{"error", "error_type"}``) instead of
        killing the stream; the remaining groups are still served.
        """
        out: List[Dict[str, Any]] = []
        for g in groups:
            try:
                out.append(self.generate(g, n_new))
            except Exception as e:  # noqa: BLE001 — isolation boundary
                kind = ("RequestError" if isinstance(e, (RequestError,
                                                         ValueError,
                                                         TypeError))
                        else "SystemError")
                out.append({
                    "tokens": np.zeros((0, 0), np.int32),
                    "error": str(e),
                    "error_type": kind,
                })
                self.bucketed.stats.note_fault(request_failed=True)
        return out


# --------------------------------------------------------------------------
# slot-level continuous batching
# --------------------------------------------------------------------------


@dataclass
class Request:
    """One generation request (the slot scheduler's admission unit)."""

    rid: int
    prompt: np.ndarray  # (P,) int32
    max_new: int  # tokens to emit (first comes from the prompt's last logits)
    arrival: int = 0  # decode-step tick at which the request may be admitted
    # -- SLO fields (DESIGN.md §SLO-aware scheduling) ----------------------
    #: open-loop wall-clock arrival offset in seconds from run start;
    #: when every request sets it the scheduler clocks arrivals (and
    #: budgets) against the wall instead of the tick counter
    arrival_s: Optional[float] = None
    #: time-to-first-token budget: admission is EDF-ordered by
    #: ``arrival + ttft_budget_s``, and a request whose TTFT deadline
    #: has already passed while queued is shed with a typed
    #: RequestError instead of wasting capacity (None = no deadline)
    ttft_budget_s: Optional[float] = None
    #: end-to-end completion budget: a slot running past it becomes a
    #: preemption victim under queue pressure (None = no budget)
    latency_budget_s: Optional[float] = None
    #: higher wins: an arriving request may preempt (park) a running
    #: slot of strictly lower priority when no slot is free
    priority: int = 0


@dataclass
class _Slot:
    """Mutable per-slot serving state (one bucket row)."""

    req: Request
    pos: int = 0  # next cache write position == tokens consumed so far
    #: prompt tokens still to consume through masked decode replay; None
    #: once the prompt is in the cache (batched prefill or fill done)
    fill: Optional[np.ndarray] = None
    remaining: int = 0  # decode steps left after the first emitted token
    cur_tok: int = 0  # last emitted token (next decode input)
    tokens: List[int] = field(default_factory=list)
    admitted_tick: int = 0
    swapped_in: bool = False  # admitted into a slot another request vacated
    #: page-pool pages owned by this slot (paged mode; freed at retire —
    #: shared prefix pages survive through the prefix tree's own refs)
    pages: List[int] = field(default_factory=list)
    #: prompt tokens whose prefill was skipped via shared-prefix pages
    skip: int = 0
    #: the row emitted POISON_TOKEN (non-finite logits tripwire) — the
    #: request is quarantined with a typed error at the next boundary
    poisoned: bool = False
    # -- SLO bookkeeping ---------------------------------------------------
    #: wall clock at which the request arrived (TTFT/latency origin)
    arrival_wall: float = 0.0
    #: wall clock at the start of the admission that granted the slot
    #: (queue wait = admit_wall - arrival_wall)
    admit_wall: float = 0.0
    #: wall clock of the first emitted token (None until it exists)
    first_wall: Optional[float] = None
    #: times this slot was preempted (pages parked) and later resumed
    preempted: int = 0


class SlotScheduler:
    """Slot-level continuous batching over a :class:`BatchedServer`.

    Replaces group admission with per-slot lifetimes: a request queue,
    per-slot state (position, remaining budget, parked KV rows), and one
    decode dispatch per tick advancing every active slot at its OWN
    position (``pos: int32[B]`` + ``slot_mask: bool[B]`` through the
    bucket program).  When a slot finishes, the next queued request is
    swapped in mid-generation — its prompt prefilled into the finished
    slot's KV rows through the slot-masked prefill grid (one dispatch;
    every other slot's cache rows survive bitwise) or, for families
    without batched prefill, consumed token-by-token INSIDE the decode
    loop while the other slots keep generating.

    Admission is pad-waste-aware: queued requests are packed to fill the
    bucket exactly (13 active + 3 queued → B16), and the bucket is
    resized — active rows gathered into a smaller/larger bucket's cache
    via the pooled buffers — only when the active-slot count crosses a
    ladder rung.  All programs come from the server's warmed bucket
    grid, so steady-state scheduling runs zero Phase 1-4 compiles.
    """

    def __init__(self, server: BatchedServer, max_slots: int = 16, *,
                 max_dispatch_retries: int = 2,
                 degraded_cooldown: int = 8,
                 max_consec_failures: int = 6,
                 tick_deadline_s: Optional[float] = None,
                 slo: bool = True,
                 refit_interval: int = 0,
                 refit_max_rungs: int = 4,
                 refit_max_programs: Optional[int] = None):
        if server.mode != "forge":
            raise ValueError("SlotScheduler needs mode='forge' "
                             "(bucketed slot-signature fronts)")
        if not server.slot_capable:
            raise ValueError(
                f"family {server.cfg.family!r} has no slot-level decode"
            )
        server._ensure_bucketed()
        self.server = server
        #: paged-KV scheduling: page-table edits replace every KV copy
        #: (resize, swap-in), admission allocates pages + consults the
        #: prefix tree, retirement frees the slot's pages
        self.paged = bool(server.paged)
        self.max_slots = int(max_slots)
        # fail fast if the ladder cannot admit the slot cap
        self.top_extent = server.bucketed.policy.bucket(self.max_slots)
        #: one-row init_cache template for stateful-decode swap-ins
        #: (built lazily; KV-only families never need it)
        self._init_row = None
        # -- fault-tolerance knobs (DESIGN.md §Fault tolerance) ------------
        #: re-dispatches of one tick before the failure escalates
        self.max_dispatch_retries = int(max_dispatch_retries)
        #: ticks of degraded mode (shed admissions, warm rungs only)
        #: entered after a tick failure or a watchdog trip
        self.degraded_cooldown = int(degraded_cooldown)
        #: consecutive failed ticks before the run aborts — every live
        #: request then terminates with a typed SystemError outcome
        self.max_consec_failures = int(max_consec_failures)
        #: per-tick wall deadline; a tick running past it trips the
        #: watchdog and enters degraded mode (None = off)
        self.tick_deadline_s = tick_deadline_s
        #: degraded-mode flag read by _target_rung (pin to warm rungs)
        self._degraded = False
        # -- SLO-aware scheduling (DESIGN.md §SLO-aware scheduling) --------
        #: deadline-aware admission: EDF queue ordering, shed-on-hopeless,
        #: and page-parking preemption.  Inert on workloads that set no
        #: budgets/priorities (EDF with infinite deadlines is arrival
        #: order, nothing sheds, no slot is ever a victim), so the
        #: default stays backwards compatible; ``slo=False`` gives the
        #: throughput-only packer as an explicit baseline.
        self.slo = bool(slo)
        #: re-fit the decode bucket ladder from the BucketStats recency
        #: trail every this-many ticks (0 = off); new rungs are
        #: submitted speculatively when async compile is on, and cold
        #: rungs are retired through evict_cold
        self.refit_interval = int(refit_interval)
        self.refit_max_rungs = int(refit_max_rungs)
        #: program-table budget handed to evict_cold after a re-fit
        #: (default: one more than the proposed rung count)
        self.refit_max_programs = refit_max_programs
        self.metrics: Dict[str, Any] = {}
        self._reset_metrics()

    def _reset_metrics(self) -> None:
        self.metrics = {
            "decode_dispatches": 0,
            "occupied_row_steps": 0,
            "capacity_row_steps": 0,
            "prefill_dispatches": 0,
            "swaps": 0,
            "resizes": 0,
            "idle_ticks": 0,
            #: admissions bounced back to the queue because the page
            #: pool was exhausted even after LRU tree reclaim (paged)
            "deferrals": 0,
            #: ticks served on a warm rung while the exact rung
            #: compiled in the background (--async-compile)
            "warm_fallbacks": 0,
            # -- fault tolerance ------------------------------------------
            #: requests rejected at validation with a typed RequestError
            "requests_rejected": 0,
            #: requests that terminated with any typed error outcome
            "requests_failed": 0,
            #: slot rows quarantined by the non-finite logits tripwire
            "rows_quarantined": 0,
            #: tick dispatches re-run after a contained dispatch fault
            "dispatch_retries": 0,
            #: ticks whose body failed past the dispatch-retry budget
            "tick_failures": 0,
            #: ticks served in degraded mode (admissions shed, rung
            #: selection pinned to warm programs)
            "ticks_degraded": 0,
            #: admission prefills that failed and were contained (slots
            #: fell back to fill-path replay or were requeued)
            "admission_failures": 0,
            #: ticks that ran past tick_deadline_s (degraded mode entered)
            "watchdog_trips": 0,
            #: faults the installed FaultPlan fired during this run
            "faults_injected": 0,
            #: True when the run hit max_consec_failures and failed all
            #: remaining requests with typed SystemError outcomes
            "aborted": False,
            # -- SLO-aware scheduling -------------------------------------
            #: slots preempted (KV pages parked / rows pooled) to make
            #: room for higher-priority or tighter-deadline arrivals
            "preemptions": 0,
            #: parked slots swapped back in (page-table row write /
            #: masked row blend)
            "resumes": 0,
            #: queued requests shed with a typed RequestError because
            #: their TTFT deadline had already passed (hopeless)
            "shed": 0,
            #: ladder re-fits applied from the recency trail
            "refits": 0,
            #: bucket programs retired by evict_cold after a re-fit
            "refit_evictions": 0,
        }

    # -- warmup -----------------------------------------------------------

    def rungs(self) -> List[int]:
        """Every bucket extent the scheduler can resize through."""
        policy = self.server.bucketed.policy
        return sorted({policy.bucket(n) for n in range(1, self.max_slots + 1)})

    def warmup(self, prompt_lens: Optional[Sequence[int]] = None) -> float:
        """Precompile every reachable rung (and prefill grid cells)."""
        return self.server.warmup(self.rungs(), prompt_lens=prompt_lens)

    # -- adaptive ladder re-fit (PR 5 eviction half-item) -----------------

    def refit(self) -> Optional[tuple]:
        """Re-fit the decode bucket ladder to the observed batch sizes.

        Consumes the :class:`BucketStats` recency trail
        (``recent_extents``: the valid batch extent of each recent real
        dispatch) and proposes quantile rungs for that distribution,
        capped so the top rung still admits ``max_slots``.  The new
        :class:`LadderPolicy` is installed in place via
        ``BucketedModule.refit_policy`` (policy *name* pinned, so
        same-extent programs, pooled buffers, and cache entries stay
        addressable, and dropped rungs' programs remain legal
        ``nearest_warm`` pad-up targets).  With async compile on, each
        cold new rung is submitted speculatively so the ladder is warm
        before the scheduler crosses onto it; finally ``evict_cold``
        retires programs beyond ``refit_max_programs`` — the serving
        rung is the most recently dispatched, so it survives.  Returns
        the installed rungs, or None when the trail is empty or already
        fits.
        """
        srv = self.server
        front = srv.bucketed
        observed = [t[0] for t in list(front.stats.recent_extents)]
        if not observed:
            return None
        rungs = propose_rungs(observed, self.refit_max_rungs,
                              cap=self.max_slots)
        old = front.policy
        if isinstance(old, LadderPolicy) and tuple(old.rungs) == rungs:
            return None
        front.refit_policy(LadderPolicy(rungs=rungs))
        self.top_extent = front.policy.bucket(self.max_slots)
        self.metrics["refits"] += 1
        if srv.async_compile and srv.compile_service is not None:
            # speculative: warm the new rungs off the request path so
            # the next boundary crossing finds a program waiting
            for r in rungs:
                k = front.key_for_extents(r)
                if front.lookup_program(k) is None:
                    front.submit_key(
                        k,
                        args_fn=(lambda e=r: srv._decode_example_args(e)),
                        foreground=False,
                    )
        budget = (self.refit_max_programs
                  if self.refit_max_programs is not None
                  else len(rungs) + 1)
        evicted = front.evict_cold(budget)
        self.metrics["refit_evictions"] += len(evicted)
        return rungs

    # -- bucket resize ----------------------------------------------------

    def _target_rung(self, exact: int) -> int:
        """Rung selection at a scheduling boundary — async-aware.

        Sync mode: the exact rung (``resolve_program`` compiles inline
        at the resize boundary, stalling the tick).  Async mode: a cold
        exact rung compiles in the background while this tick proceeds
        on the smallest warm rung that dominates it; once the exact
        program lands a later boundary re-selects it through the warm
        path (the ordinary resize machinery does the switch).  When no
        warm rung dominates (growth past the warm top) the scheduler
        serves what fits in the *largest* warm rung — excess requests
        stay queued until the background compile lands — and only the
        very first rung, with nothing warm at all, blocks.
        """
        srv = self.server
        front = srv.bucketed
        if self._degraded:
            # degraded mode pins to warm rungs: no cold compile — inline
            # OR background — may start while the loop is recovering
            if front.lookup_program(front.key_for_extents(exact)) is not None:
                return exact
            warm = [k.extents[0] for k in front.warm_keys()]
            dominating = [w for w in warm if w >= exact]
            if dominating:
                return min(dominating)
            if warm:
                return max(warm)
            # nothing warm at all: no choice but the normal path
        if not srv.async_compile:
            return exact
        if front.lookup_program(front.key_for_extents(exact)) is not None:
            return exact
        fut = front.submit_key(
            front.key_for_extents(exact),
            args_fn=(lambda e=exact: srv._decode_example_args(e)),
            foreground=True,
        )
        warm = [k.extents[0] for k in front.warm_keys()]
        dominating = [w for w in warm if w >= exact]
        if dominating:
            target = min(dominating)
            front.stats.note_fallback(target - exact)
        elif warm:
            # capacity-capped: no pad premium, the rung is *smaller*
            target = max(warm)
            front.stats.note_fallback(0)
        else:
            t0 = time.perf_counter()
            # reap-aware wait: a dead or hung compile worker resolves
            # (or requeues) the future instead of deadlocking the tick
            srv.compile_service.result(fut)
            front.stats.note_wait(time.perf_counter() - t0)
            return exact
        self.metrics["warm_fallbacks"] += 1
        return target

    def _gather_rows(self, old_cache, new_cache, src_rows: List[int]):
        """Move the active slots' cache rows into the new bucket's cache.

        Row ``src_rows[j]`` of every batch-polymorphic leaf lands in row
        ``j``; batch-free leaves (none in current families) keep the new
        cache's zeros.  Runs once per rung crossing — eager jnp ops, no
        compiled program involved.
        """
        from ..core.shapekey import flatten_axes

        flat_old, tree = jax.tree_util.tree_flatten(old_cache)
        flat_new, _ = jax.tree_util.tree_flatten(new_cache)
        axes = flatten_axes(self.server.cache_axes, old_cache)
        src = jnp.asarray(src_rows, jnp.int32)
        n = len(src_rows)
        moved = []
        for o, nw, ax in zip(flat_old, flat_new, axes):
            if ax is None:
                moved.append(nw)
                continue
            rows = jnp.take(o, src, axis=ax)
            sl = [slice(None)] * nw.ndim
            sl[ax] = slice(0, n)
            moved.append(nw.at[tuple(sl)].set(rows))
        return jax.tree_util.tree_unflatten(tree, moved)

    def _reset_rows(self, cache, rows: List[int], extent: int):
        """Re-initialize the admitted rows of a stateful-decode cache.

        KV rows are reusable as-is (the per-row position mask hides
        stale entries past the new request's position), but recurrent
        states fold every past token in: without this reset a swapped-in
        request would continue the PREVIOUS occupant's h/conv/cell
        state.  Blends the one-row ``init_cache`` template into the
        admitted rows only — every other slot's state survives bitwise.
        """
        from ..core.shapekey import flatten_axes

        srv = self.server
        if self._init_row is None:
            self._init_row = srv.model.init_cache(srv.cfg, 1, srv.max_len)
        mask = np.zeros((extent,), bool)
        mask[rows] = True
        flat, tree = jax.tree_util.tree_flatten(cache)
        flat_init, _ = jax.tree_util.tree_flatten(self._init_row)
        axes = flatten_axes(srv.cache_axes, cache)
        out = []
        for leaf, ini, ax in zip(flat, flat_init, axes):
            if ax is None:
                out.append(leaf)
                continue
            shape = [1] * leaf.ndim
            shape[ax] = extent
            m = jnp.asarray(mask).reshape(shape)
            out.append(jnp.where(m, ini, leaf))  # ini broadcasts (1 @ ax)
        return jax.tree_util.tree_unflatten(tree, out)

    # -- request validation ------------------------------------------------

    def _validate(self, r: Request) -> Optional[str]:
        """Admission-time validation; a non-None return rejects the
        request with a typed RequestError outcome instead of killing the
        whole workload."""
        srv = self.server
        try:
            plen = len(r.prompt)
        except TypeError:
            return "prompt must be an array of token ids"
        if plen < 1:
            return "prompt must be non-empty"
        if r.max_new < 1:
            return "max_new must be >= 1"
        if plen + r.max_new > srv.max_len:
            return (f"prompt {plen} + budget {r.max_new} exceeds "
                    f"max_len={srv.max_len}")
        if self.paged:
            need = pages_for(plen + r.max_new, srv.page_pool.page_size)
            if need > srv.page_pool.capacity:
                return (f"needs {need} KV pages, pool capacity is "
                        f"{srv.page_pool.capacity}")
        if r.ttft_budget_s is not None and r.ttft_budget_s <= 0:
            return "ttft_budget_s must be > 0"
        if r.latency_budget_s is not None and r.latency_budget_s <= 0:
            return "latency_budget_s must be > 0"
        return None

    # -- the scheduling loop ----------------------------------------------

    def run(self, requests: Sequence[Request]) -> Dict[str, Any]:
        """:meth:`_run`, with a ``py.gc`` span around each full garbage
        collection while it serves."""
        with gc_spans():
            return self._run(requests)

    def _run(self, requests: Sequence[Request]) -> Dict[str, Any]:
        """Serve ``requests`` to completion; returns results + metrics.

        The clock is the decode-dispatch counter (``tick``):
        ``Request.arrival`` is measured in ticks, and a tick with no
        runnable slot fast-forwards to the next arrival.  When every
        request sets ``arrival_s`` the run is *open-loop*: arrivals are
        clocked against the wall (seconds since run start), which is
        what TTFT/latency budgets are measured against.
        """
        srv = self.server
        params = srv.params
        stats = srv.bucketed.stats
        self._reset_metrics()
        compiles0 = stats.compiles + (
            srv.prefill_bucketed.stats.compiles if srv.prefill_bucketed else 0
        )

        results: Dict[int, Dict[str, Any]] = {}
        plan = chaos.current_plan()
        faults0 = plan.faults_injected if plan is not None else 0

        def fail_request(req: Request, why: str,
                         kind: str = "RequestError") -> None:
            """Terminate an un-admitted request with a typed outcome."""
            results[req.rid] = {
                "tokens": np.zeros((0,), np.int32),
                "admitted_tick": -1,
                "finished_tick": -1,
                "swapped_in": False,
                "error": why,
                "error_type": kind,
            }
            stats.note_fault(request_failed=True)
            self.metrics["requests_failed"] += 1

        # per-request validation: an invalid request completes with a
        # typed RequestError outcome; the rest of the workload is served
        valid: List[Request] = []
        for r in requests:
            why = self._validate(r)
            if why is not None:
                fail_request(r, why)
                self.metrics["requests_rejected"] += 1
            else:
                valid.append(r)
        requests = valid

        paged = self.paged
        pool = srv.page_pool if paged else None
        MP = srv.max_pages_per_slot if paged else 0
        #: host-side page table (extent, MP); device copy refreshed at
        #: resize/admission boundaries — retired rows go stale on device,
        #: which is inert (their mask is False, writes route to trash)
        pt_host = np.full((0, MP), TRASH_PAGE, np.int32)
        pt_dev = None
        #: open-loop wall-clock arrivals iff every request carries one
        wall_mode = bool(requests) and all(
            r.arrival_s is not None for r in requests
        )
        if wall_mode:
            pendreq = deque(sorted(requests,
                                   key=lambda r: (r.arrival_s, r.rid)))
        else:
            pendreq = deque(sorted(requests,
                                   key=lambda r: (r.arrival, r.rid)))
        queue: deque = deque()
        #: preempted slots awaiting resume, keyed by rid; their KV lives
        #: in the page pool's parked registry (paged) or the bucket
        #: BufferPool under ("parked", rid) (contiguous)
        parked: Dict[int, _Slot] = {}
        #: wall clock of each request's arrival (TTFT/latency origin)
        arr_wall: Dict[int, float] = {}
        slots: List[Optional[_Slot]] = []
        extent = 0
        cache = srv.page_store if paged else None
        #: the store's expert counter at the start (an MoE config's, see
        #: models/transformer.py EXPERT_LOAD): the run reports its growth
        load0 = cache.get("expert_load") if isinstance(cache, dict) else None
        mod = key = None
        cur_tok = np.zeros((0, 1), np.int32)
        cur_pos = np.zeros((0,), np.int32)
        tick = 0
        #: device-resident (tok, pos, mask) for the steady-state fast
        #: path; None whenever host state changed since the last dispatch
        dev_args = None
        #: token columns not yet copied to host (steady-state ticks defer
        #: the D2H sync; harvested at the next boundary — see _harvest)
        pending: List[Any] = []
        t0 = time.perf_counter()

        def active_count() -> int:
            return sum(s is not None for s in slots)

        # -- SLO helpers (EDF ordering, deadlines, preemption) ------------

        def req_arrival_wall(req: Request) -> float:
            """Wall clock at which ``req`` arrived: its scheduled
            open-loop offset in wall mode, else the moment the tick
            clock surfaced it (stamped at the pendreq→queue pop)."""
            if req.rid in arr_wall:
                return arr_wall[req.rid]
            if wall_mode:
                return t0 + (req.arrival_s or 0.0)
            return t0

        def ttft_deadline(req: Request) -> float:
            if req.ttft_budget_s is None:
                return float("inf")
            return req_arrival_wall(req) + req.ttft_budget_s

        def edf_key(req: Request):
            """Earliest-deadline-first with priority tiebreak; with no
            budgets/priorities set this degenerates to arrival order,
            so SLO mode is inert on legacy workloads."""
            arrival = (req.arrival_s or 0.0) if wall_mode else req.arrival
            return (ttft_deadline(req), -req.priority, arrival, req.rid)

        def resolve_program():
            nonlocal mod, key
            if paged:
                args = (jnp.asarray(pt_host), jnp.asarray(cur_tok),
                        jnp.asarray(cur_pos), jnp.zeros((extent,), bool))
            else:
                args = srv._decode_args(extent, jnp.asarray(cur_tok),
                                        jnp.asarray(cur_pos))
            mod, key, _ = srv.bucketed.program_for(params, cache, *args)
            srv.forge_module = mod

        def retire(i: int, s: _Slot, error: Optional[str] = None,
                   error_type: str = "RequestError") -> None:
            now = time.perf_counter()
            entry = {
                "tokens": np.asarray(s.tokens, np.int32),
                "admitted_tick": s.admitted_tick,
                "finished_tick": tick,
                "swapped_in": s.swapped_in,
                "preempted": s.preempted,
                "priority": s.req.priority,
                "ttft_s": (s.first_wall - s.arrival_wall
                           if s.first_wall is not None else None),
                "queue_wait_s": s.admit_wall - s.arrival_wall,
                "latency_s": now - s.arrival_wall,
            }
            if error is not None:
                entry["error"] = error
                entry["error_type"] = error_type
                stats.note_fault(request_failed=True)
                self.metrics["requests_failed"] += 1
            results[s.req.rid] = entry
            slots[i] = None
            if paged and s.pages:
                # the slot's refs drop; pages shared through the prefix
                # tree stay live on the tree's own refs
                pool.free(s.pages)
                s.pages = []
                pt_host[i, :] = TRASH_PAGE

        def quarantine(i: int, s: _Slot) -> None:
            """Non-finite logits tripwire fired for this row: complete
            the request with a typed error; its emitted tokens stop at
            the last finite one.  Every other slot's cache rows and
            token stream are untouched (slot_gate write-inertness)."""
            self.metrics["rows_quarantined"] += 1
            retire(i, s, error="non-finite logits in decode row "
                               "(quarantined)")

        def harvest() -> None:
            """:func:`collect` inside a ``serve.harvest`` span."""
            if pending:
                with span("serve.harvest"):
                    collect()

        def collect() -> None:
            """Copy the deferred token columns to host, in tick order.

            The active set cannot have changed while ticks were pending
            (any change is a boundary that harvests first), so every
            pending column distributes to the same rows.  A row that
            emitted POISON_TOKEN (non-finite logits) stops accumulating
            at the poison point and is quarantined; the other rows'
            tokens are unaffected.
            """
            nonlocal dev_args
            if not pending:
                return
            rows = [i for i, s in enumerate(slots) if s is not None]
            for out in pending:
                arr = np.asarray(out)
                for i in rows:
                    s = slots[i]
                    if s.poisoned:
                        continue  # post-poison columns are garbage
                    t = int(arr[i, 0])
                    if t == POISON_TOKEN:
                        s.poisoned = True
                        continue
                    s.cur_tok = t
                    s.tokens.append(s.cur_tok)
                    if s.first_wall is None:
                        s.first_wall = time.perf_counter()
            pending.clear()
            for i in rows:
                s = slots[i]
                if s is not None and s.poisoned:
                    quarantine(i, s)
                    dev_args = None  # active set shrank: rebuild mask

        def park_slot(i: int, s: _Slot) -> None:
            """Preempt one mid-decode slot by parking its KV.

            Paged path: the slot row is dropped and its page-table row
            trashed, but the page chain keeps its refcounts and moves
            into the pool's parked registry — O(table row), no KV bytes
            move.  Contiguous path: the slot's cache rows are gathered
            into a 1-row tree and parked in the bucket BufferPool under
            ``("parked", rid)``.  The fault hook fires BEFORE any state
            moves, so an injected preempt fault is contained as an
            ordinary tick failure with accounting intact.  Host decode
            state (pos, cur_tok, tokens) rides along in the _Slot —
            resume needs only the KV back under a row.
            """
            nonlocal cache, dev_args, pt_dev
            chaos.maybe_fault(chaos.SITE_PREEMPT)
            rid = s.req.rid
            if paged:
                pool.park(rid, s.pages)
                pt_host[i, :] = TRASH_PAGE
                pt_dev = jnp.asarray(pt_host)
            else:
                srv.bucketed.pool.release(
                    ("parked", rid),
                    gather_cache_rows(cache, srv.cache_axes, [i]),
                )
            s.preempted += 1
            parked[rid] = s
            slots[i] = None
            dev_args = None
            self.metrics["preemptions"] += 1

        def resume_slot(i: int, s: _Slot) -> None:
            """Swap a parked slot back in: page-table row write (paged)
            or masked row blend (contiguous), then restore the host
            decode state.  No prefill dispatch — the KV is exactly what
            the slot parked, and decode is row/extent-invariant, so the
            resumed request's tokens are bitwise-equal to an
            unpreempted run."""
            nonlocal cache, dev_args, pt_dev
            rid = s.req.rid
            parked.pop(rid)
            if paged:
                s.pages = pool.unpark(rid)
                pt_host[i] = build_row_table(s.pages, MP)
                pt_dev = jnp.asarray(pt_host)
            else:
                def _missing():
                    raise SystemError_(
                        f"parked rows for rid {rid} missing from pool"
                    )

                row = srv.bucketed.pool.acquire(("parked", rid), _missing)
                srv.bucketed.pool.drop(("parked", rid))  # empty key
                cache = blend_cache_rows(cache, srv.cache_axes, row, [i])
            slots[i] = s
            cur_tok[i, 0] = s.cur_tok
            cur_pos[i] = s.pos
            dev_args = None
            self.metrics["resumes"] += 1

        def abort_run(err: BaseException) -> None:
            """Containment exhausted: every live request terminates with
            a typed SystemError outcome — the loop returns, never
            crashes, and slot/page accounting is left clean."""
            why = (f"serving loop aborted after "
                   f"{self.max_consec_failures} consecutive tick "
                   f"failures: {err}")
            for i, s in enumerate(slots):
                if s is not None:
                    retire(i, s, error=why, error_type="SystemError")
            # drain parked slots: release their KV (pages / pooled rows)
            # and terminate them with the same typed outcome, keeping
            # the partial tokens they generated before preemption
            for rid, s in list(parked.items()):
                if paged:
                    pool.unpark(rid)
                    if s.pages:
                        pool.free(s.pages)
                        s.pages = []
                else:
                    srv.bucketed.pool.drop(("parked", rid))
                results[rid] = {
                    "tokens": np.asarray(s.tokens, np.int32),
                    "admitted_tick": s.admitted_tick,
                    "finished_tick": tick,
                    "swapped_in": s.swapped_in,
                    "preempted": s.preempted,
                    "priority": s.req.priority,
                    "ttft_s": (s.first_wall - s.arrival_wall
                               if s.first_wall is not None else None),
                    "queue_wait_s": s.admit_wall - s.arrival_wall,
                    "latency_s": time.perf_counter() - s.arrival_wall,
                    "error": why,
                    "error_type": "SystemError",
                }
                stats.note_fault(request_failed=True)
                self.metrics["requests_failed"] += 1
            parked.clear()
            for req in list(queue) + list(pendreq):
                fail_request(req, why, kind="SystemError")
            queue.clear()
            pendreq.clear()

        def tick_once() -> Optional[str]:
            """One scheduler tick: arrivals, admission/resize, one decode
            dispatch + bookkeeping.  Returns a loop directive
            ('continue' | 'break' | 'deadline') or None."""
            nonlocal slots, cur_tok, cur_pos, cache, extent, mod, key
            nonlocal dev_args, pt_dev, pt_host, tick
            now = time.perf_counter()
            if wall_mode:
                while pendreq and t0 + (pendreq[0].arrival_s or 0.0) <= now:
                    req = pendreq.popleft()
                    arr_wall[req.rid] = t0 + (req.arrival_s or 0.0)
                    queue.append(req)
            else:
                while pendreq and pendreq[0].arrival <= tick:
                    req = pendreq.popleft()
                    arr_wall.setdefault(req.rid, now)
                    queue.append(req)

            # ---- SLO admission: shed-on-hopeless + EDF ordering ---------
            if self.slo and queue:
                kept: List[Request] = []
                for req in queue:
                    if (req.ttft_budget_s is not None
                            and now > ttft_deadline(req)):
                        # hopeless: its TTFT deadline passed while it
                        # queued — admitting it now wastes capacity the
                        # still-meetable requests need
                        fail_request(
                            req,
                            f"shed: TTFT deadline exceeded while queued "
                            f"(budget {req.ttft_budget_s:.3f}s)",
                        )
                        self.metrics["shed"] += 1
                    else:
                        kept.append(req)
                kept.sort(key=edf_key)
                queue.clear()
                queue.extend(kept)

            # ---- preemption: park over-budget / low-priority slots ------
            # Only under queue pressure (EDF overflow past the free
            # slots), never in degraded mode (parking is state motion the
            # recovering loop should not attempt).  A victim must be
            # mid-decode (not prefilling), and either strictly lower
            # priority than the incoming request or past its own latency
            # budget.  Parking is O(page-table row) on the paged path.
            if self.slo and not self._degraded and queue:
                overflow = list(queue)[
                    max(self.max_slots - active_count() - len(parked), 0):
                ]
                harvested = False
                for req in overflow:
                    cands = [
                        (s.req.priority, -s.remaining, i)
                        for i, s in enumerate(slots)
                        if s is not None and s.fill is None
                        and not s.poisoned
                        and (s.req.priority < req.priority
                             or (s.req.latency_budget_s is not None
                                 and now > s.arrival_wall
                                 + s.req.latency_budget_s))
                    ]
                    if not cands:
                        continue  # nothing preemptible for this request
                    _, _, vi = min(cands)
                    if not harvested:
                        # sync pending device token columns before any
                        # slot state moves (same boundary rule as resize)
                        harvest()
                        harvested = True
                    victim = slots[vi]
                    if victim is None or victim.poisoned:
                        continue  # harvest quarantined it
                    park_slot(vi, victim)

            # ---- pad-waste-aware admission + rung resize ----------------
            active = active_count()
            want = min(active + len(queue) + len(parked), self.max_slots)
            t_tick = time.perf_counter()
            # degraded mode sheds admissions (queued requests wait out
            # the cooldown) unless nothing at all is active — then an
            # admission is the only way to make progress
            if want > 0 and not (self._degraded and active > 0):
                # the bucket policy is read through the front on every
                # boundary (not captured once) so a mid-run ladder
                # re-fit takes effect at the next rung selection
                target = self._target_rung(srv.bucketed.policy.bucket(want))
                if target != extent or ((queue or parked)
                                        and any(s is None for s in slots)):
                    # resize/admission is a boundary: sync the pending
                    # device-resident token columns before slot rows move
                    # or dev_args is rebuilt from host state (a deferred
                    # request retrying admission reaches here from a
                    # steady-state tick with no other boundary — without
                    # the harvest the rebuilt tok_dev would feed a stale
                    # cur_tok back in)
                    harvest()
                if target != extent:
                    with span("serve.resize"):
                        keep = [(i, s) for i, s in enumerate(slots)
                                if s is not None]
                        if paged:
                            # O(table) resize: surviving rows' page-table
                            # entries move; the KV pages themselves do not
                            new_pt = np.full((target, MP), TRASH_PAGE,
                                             np.int32)
                            for dst, (i, _) in enumerate(keep):
                                new_pt[dst] = pt_host[i]
                            pt_host = new_pt
                            if extent > 0:
                                self.metrics["resizes"] += 1
                        else:
                            new_cache = srv._acquire_cache(target)
                            if keep and cache is not None:
                                new_cache = self._gather_rows(
                                    cache, new_cache, [i for i, _ in keep]
                                )
                            if cache is not None:
                                srv._release_cache(extent, cache)
                                self.metrics["resizes"] += 1
                            cache = new_cache
                        new_tok = np.zeros((target, 1), np.int32)
                        new_pos = np.zeros((target,), np.int32)
                        new_slots: List[Optional[_Slot]] = [None] * target
                        for dst, (i, s) in enumerate(keep):
                            new_slots[dst] = s
                            new_tok[dst] = cur_tok[i]
                            new_pos[dst] = cur_pos[i]
                        slots, cur_tok, cur_pos = new_slots, new_tok, new_pos
                        extent = target
                        dev_args = None
                        if paged:
                            pt_dev = jnp.asarray(pt_host)
                        # on a resolve failure (injected build fault, poisoned
                        # key) mod stays None and the dispatch path retries
                        # the resolve next tick — never dispatches stale
                        mod = None
                        resolve_program()
                # pack queued requests AND parked resumes into every
                # free slot (13+3 → B16).  Resumes and fresh admissions
                # compete in one EDF order (a parked slot keeps its
                # original arrival/deadline); without SLO mode parked is
                # always empty and this is the original FIFO pack.
                mid_generation = active > 0
                admitted: List[int] = []
                cand = [("resume", s.req) for s in parked.values()]
                cand += [("new", r) for r in queue]
                if self.slo and parked:
                    cand.sort(key=lambda kr: edf_key(kr[1]))
                cand = deque(cand)
                for i in range(extent):
                    if not cand:
                        break
                    if slots[i] is not None:
                        continue
                    kind, req = cand.popleft()
                    if kind == "resume":
                        resume_slot(i, parked[req.rid])
                        continue
                    # a swap-in: admission while other slots are mid-
                    # generation (the continuous-batching case the
                    # lockstep server could not serve)
                    slots[i] = _Slot(
                        req=req, admitted_tick=tick,
                        swapped_in=mid_generation,
                        fill=np.asarray(req.prompt, np.int32),
                        arrival_wall=req_arrival_wall(req),
                    )
                    if mid_generation:
                        self.metrics["swaps"] += 1
                    admitted.append(i)
                # unpacked fresh requests go back to the queue in order
                # (unpacked resumes simply stay parked)
                queue.clear()
                queue.extend(r for kind, r in cand if kind == "new")
                if admitted:
                    t_admit = time.perf_counter()
                    for i in admitted:
                        slots[i].admit_wall = t_admit
                    # ";": the profiler splits span arguments at ","
                    rids = ";".join(str(slots[i].req.rid) for i in admitted)
                    with span("serve.admit", rids=rids):
                        if paged:
                            cache = self._admit_paged(admitted, slots, cache,
                                                      extent, cur_tok, cur_pos,
                                                      pt_host, queue)
                            pt_dev = jnp.asarray(pt_host)
                        else:
                            cache = self._admit(admitted, slots, cache, extent,
                                                cur_tok, cur_pos)
                        dev_args = None
                        # degenerate 1-token budgets finish at admission
                        # (a paged deferral leaves slots[i] None — skip it);
                        # a poisoned first token quarantines the row instead
                        for i in admitted:
                            s = slots[i]
                            if s is None:
                                continue
                            if s.poisoned:
                                quarantine(i, s)
                            elif s.fill is None and s.remaining <= 0:
                                retire(i, s)

            if not any(s is not None for s in slots):
                if pendreq:
                    # nothing runnable until the next arrival
                    self.metrics["idle_ticks"] += 1
                    if wall_mode:
                        # open-loop clock: sleep (briefly) toward the
                        # next scheduled arrival instead of spinning
                        wait = (t0 + (pendreq[0].arrival_s or 0.0)
                                - time.perf_counter())
                        if wait > 0:
                            with span("serve.wait_arrival"):
                                time.sleep(min(wait, 0.025))
                        tick += 1
                    else:
                        tick = max(tick + 1, pendreq[0].arrival)
                    return "continue"
                if queue or parked:
                    # degraded shed with nothing active still admits, so
                    # reaching here means admission itself kept failing
                    # (pool exhaustion faults, prefill faults): count it
                    # so repeated stalls escalate instead of spinning
                    tick += 1
                    return "stalled"
                return "break"

            # ---- one decode dispatch advances every active slot ---------
            if dev_args is None:
                mask_np = np.array([s is not None for s in slots])
                for i, s in enumerate(slots):
                    if s is None:
                        continue
                    cur_pos[i] = s.pos
                    cur_tok[i, 0] = (s.fill[s.pos] if s.fill is not None
                                     else s.cur_tok)
                tok_dev = jnp.asarray(cur_tok)
                pos_dev = jnp.asarray(cur_pos)
                mask_dev = jnp.asarray(mask_np)
            else:
                # steady state (same active set, no prompts being
                # consumed): the previous dispatch's output IS this
                # dispatch's input — feed the device arrays straight
                # back, no host round-trip
                tok_dev, pos_dev, mask_dev = dev_args
            if mod is None:
                # a failed resolve last tick (injected build fault,
                # poisoned key) left no program — retry the resolve here
                # before dispatching
                resolve_program()
            # bounded retry: cache leaves are program *inputs* (never
            # donated) and the executor releases its pooled scratch in a
            # finally, so re-dispatching the same tick after a transient
            # failure is state-safe
            n_act = sum(s is not None for s in slots)
            attempt = 0
            while True:
                try:
                    if paged:
                        with span("serve.dispatch", extent=extent,
                                  n_active=n_act):
                            out_tok, cache = mod(params, cache, pt_dev,
                                                 tok_dev, pos_dev, mask_dev)
                        # pool invariant holds after every tick: every
                        # page is either referenced or on the free list,
                        # never both
                        with span("serve.pool_check"):
                            pool.check()
                    else:
                        with span("serve.dispatch", extent=extent,
                                  n_active=n_act):
                            out_tok, cache = mod(params, cache, tok_dev,
                                                 pos_dev, mask_dev)
                    break
                except Exception:
                    attempt += 1
                    self.metrics["dispatch_retries"] += 1
                    stats.note_fault(retries=1)
                    if attempt > self.max_dispatch_retries:
                        raise
            if chaos.should_fault(chaos.SITE_LOGITS_NAN):
                # fault model: one active row's logits went non-finite on
                # device; guarded_argmax would then emit POISON_TOKEN for
                # exactly that row, so inject at its observable boundary.
                # Host round-trip on the tiny (extent, 1) token block —
                # device-side edits would compile a fresh program for the
                # victim's index, which only fault runs would ever pay
                victim = next(i for i, s in enumerate(slots)
                              if s is not None)
                poked = np.asarray(out_tok).copy()
                poked[victim, 0] = POISON_TOKEN
                out_tok = jnp.asarray(poked)
            stats.note_dispatch(key, n_act, extent)
            self.metrics["decode_dispatches"] += 1
            self.metrics["occupied_row_steps"] += n_act
            self.metrics["capacity_row_steps"] += extent
            tick += 1
            if wall_mode:
                arrival_due = bool(pendreq) and (
                    t0 + (pendreq[0].arrival_s or 0.0) <= time.perf_counter()
                )
            else:
                arrival_due = bool(pendreq) and pendreq[0].arrival <= tick
            with span("serve.harvest"):
                if any(s is not None and s.fill is not None for s in slots):
                    # prompt-consuming rows need this tick's tokens NOW (a
                    # fill transition switches a row's input source); fills
                    # always start at a boundary, so nothing should be
                    # pending — the harvest is a defensive no-op
                    collect()
                    out_np = np.asarray(out_tok)
                    changed = False
                    for i, s in enumerate(slots):
                        if s is None:
                            continue
                        s.pos += 1
                        if s.fill is not None:
                            if s.pos == len(s.fill):
                                # prompt consumed: this dispatch emitted the
                                # request's first real token (its next input
                                # is the program output, like a decode row)
                                s.fill = None
                                t_emit = int(out_np[i, 0])
                                if t_emit == POISON_TOKEN:
                                    quarantine(i, s)
                                    changed = True
                                    continue
                                s.cur_tok = t_emit
                                s.tokens.append(s.cur_tok)
                                if s.first_wall is None:
                                    s.first_wall = time.perf_counter()
                                s.remaining = s.req.max_new - 1
                            else:
                                # mid-prompt rows feed host prompt tokens
                                changed = True
                        else:
                            t_emit = int(out_np[i, 0])
                            if t_emit == POISON_TOKEN:
                                quarantine(i, s)
                                changed = True
                                continue
                            s.cur_tok = t_emit
                            s.tokens.append(s.cur_tok)
                            if s.first_wall is None:
                                s.first_wall = time.perf_counter()
                            s.remaining -= 1
                        if s.fill is None and s.remaining <= 0:
                            retire(i, s)
                            changed = True  # active set shrank: rebuild mask
                    dev_args = (None if changed or arrival_due
                                else (out_tok, pos_dev + 1, mask_dev))
                else:
                    # pure decode tick: budgets are host-side counters, so
                    # retirement needs no token values — defer the D2H sync
                    # and keep the loop device-resident until a boundary
                    # (a retire, or an arrival that may admit)
                    pending.append(out_tok)
                    boundary = arrival_due
                    for s in slots:
                        if s is None:
                            continue
                        s.pos += 1
                        s.remaining -= 1
                        if s.remaining <= 0:
                            boundary = True
                    if boundary:
                        collect()
                        for i, s in enumerate(slots):
                            if s is not None and s.remaining <= 0:
                                retire(i, s)
                        dev_args = None
                    else:
                        dev_args = (out_tok, pos_dev + 1, mask_dev)
            dt = time.perf_counter() - t_tick
            if (self.tick_deadline_s is not None
                    and dt > self.tick_deadline_s):
                return "deadline"
            return None

        # ---- driver: every tick runs inside containment ----------------
        # a tick that throws degrades the loop (cooldown sheds admissions
        # and pins warm rungs) instead of killing the workload; only
        # max_consec_failures consecutive failures abort, and even then
        # every live/queued request gets a typed SystemError outcome
        consec_failures = 0
        degraded_until = 0
        next_refit = self.refit_interval
        while (pendreq or queue or parked
               or any(s is not None for s in slots)):
            self._degraded = tick < degraded_until
            if (self.refit_interval and tick >= next_refit
                    and not self._degraded):
                next_refit = tick + self.refit_interval
                try:
                    self.refit()
                except Exception:
                    # re-fit is advisory: a failed proposal/compile must
                    # never take the serving loop down with it
                    pass
            if self._degraded:
                stats.note_fault(tick_degraded=True)
                self.metrics["ticks_degraded"] += 1
            try:
                with span("serve.tick"):
                    directive = tick_once()
            except Exception as e:
                consec_failures += 1
                self.metrics["tick_failures"] += 1
                # salvage what the tick managed before it threw: pending
                # columns from dispatches that DID complete are valid
                try:
                    harvest()
                except Exception:
                    pending.clear()
                dev_args = None
                degraded_until = max(degraded_until,
                                     tick + self.degraded_cooldown)
                tick += 1
                if consec_failures > self.max_consec_failures:
                    self.metrics["aborted"] = True
                    abort_run(e)
                    break
                continue
            if directive == "stalled":
                # admission made no progress with nothing active —
                # escalates like a failure so the loop cannot spin
                consec_failures += 1
                self.metrics["tick_failures"] += 1
                if consec_failures > self.max_consec_failures:
                    self.metrics["aborted"] = True
                    abort_run(RuntimeError(
                        "admission made no progress"))
                    break
                continue
            consec_failures = 0
            if directive == "deadline":
                # tick finished but blew its deadline: enter degraded
                # mode so the next ticks stay on warm rungs
                self.metrics["watchdog_trips"] += 1
                degraded_until = max(degraded_until,
                                     tick + self.degraded_cooldown)
            elif directive == "break":
                break

        self._degraded = False
        wall = time.perf_counter() - t0
        if plan is not None:
            injected = plan.faults_injected - faults0
            self.metrics["faults_injected"] = injected
            if injected:
                stats.note_fault(injected=injected)
        if paged:
            # the store is server-resident: the next run (and the prefix
            # tree's cached pages) continue from it
            srv.page_store = cache
        elif cache is not None:
            srv._release_cache(extent, cache)
        compiles = stats.compiles + (
            srv.prefill_bucketed.stats.compiles if srv.prefill_bucketed
            else 0
        ) - compiles0
        m = self.metrics
        cap = max(m["capacity_row_steps"], 1)
        real_tokens = sum(len(r["tokens"]) for r in results.values())
        out = {
            "results": results,
            "wall_s": wall,
            "tok_per_s": real_tokens / max(wall, 1e-9),
            "real_tokens": real_tokens,
            "occupancy": m["occupied_row_steps"] / cap,
            "pad_decode_fraction": 1.0 - m["occupied_row_steps"] / cap,
            "compiles": compiles,  # 0 after warmup covering the rungs
            **m,
        }
        # SLO tails over per-request outcomes (wall-clock TTFT/latency)
        ttfts = [r["ttft_s"] for r in results.values()
                 if r.get("ttft_s") is not None]
        lats = [r["latency_s"] for r in results.values()
                if r.get("latency_s") is not None and "error" not in r]
        out["ttft_p50_s"] = float(np.percentile(ttfts, 50)) if ttfts else 0.0
        out["ttft_p99_s"] = float(np.percentile(ttfts, 99)) if ttfts else 0.0
        out["latency_p99_s"] = float(np.percentile(lats, 99)) if lats else 0.0
        out["shed_rate"] = (m["shed"] / len(requests) if requests else 0.0)
        if paged:
            ps_ = pool.stats
            leaf_bytes = sum(
                int(np.prod(v.shape)) * v.dtype.itemsize
                for v in jax.tree_util.tree_leaves(cache)
            )
            page_bytes = leaf_bytes // pool.num_pages
            out.update(
                kv_pages_in_use=pool.pages_in_use,
                kv_pages_capacity=pool.capacity,
                kv_peak_pages_in_use=ps_.peak_pages_in_use,
                kv_page_bytes=page_bytes,
                #: high-water mark of KV bytes actually referenced — the
                #: number a contiguous cache pins at extent * max_len
                kv_bytes_resident_peak=ps_.peak_pages_in_use * page_bytes,
                prefix_hits=ps_.prefix_hits,
                prefix_misses=ps_.prefix_misses,
                prefix_hit_rate=ps_.prefix_hit_rate,
                prefill_skip_rate=ps_.prefill_skip_rate,
                tokens_reused=ps_.tokens_reused,
                pages_allocated=ps_.pages_allocated,
                pages_reused=ps_.pages_reused,
                pages_reclaimed=ps_.pages_reclaimed,
            )
            if load0 is not None:
                #: rows (decode, prefill); columns (picks that reached a
                #: held expert, held experts, held experts picked), summed
                #: over MoE layers and dispatches: read once, after the run
                out["expert_load"] = (np.asarray(cache["expert_load"])
                                      - np.asarray(load0)).tolist()
        return out

    def _admit(self, admitted: List[int], slots: List[Optional[_Slot]],
               cache, extent: int, cur_tok: np.ndarray,
               cur_pos: np.ndarray):
        """Prefill newly admitted slots through the slot-masked grid.

        One ``prefill_step`` dispatch writes every admitted prompt into
        its slot's cache rows at position 0 while the other slots' rows
        stay bitwise untouched; the first generated token is read from
        each row's last real prompt column.  Recurrent families take
        the same path through the chunked state scan (a per-row
        ``length`` bounds each row's scan; swapped-in rows are reset to
        init state first).  When the grid does not cover the longest
        admitted prompt (ladder overflow, ``--prefill sequential``),
        the slots keep their ``fill`` buffers and consume the prompt
        inside the decode loop instead — the other slots keep
        generating in the same dispatches.
        """
        srv = self.server
        if srv.model.stateful_decode:
            # recurrent state is not positional: swapped-in rows must
            # restart from the init state, not the previous occupant's
            cache = self._reset_rows(cache, admitted, extent)
        Ps = [len(slots[i].req.prompt) for i in admitted]
        s_ext = srv._seq_bucket_extent(max(Ps), extent=extent)
        if s_ext is None:
            # no grid cell covers the prompt (ladder overflow, forced
            # sequential prefill): the slots keep their fill buffers and
            # consume the prompt inside the decode loop instead
            return cache
        tokens = np.zeros((extent, s_ext), np.int32)
        mask = np.zeros((extent,), bool)
        for i, P in zip(admitted, Ps):
            tokens[i, :P] = slots[i].req.prompt
            tokens[i, P:] = slots[i].req.prompt[-1]  # edge pad
            mask[i] = True
        jtokens = jnp.asarray(tokens)
        # per-row real prompt ends (recurrent fronts only): masked-out
        # rows get a trivial length of 1 — their state is slot-gated
        # back to the old rows anyway
        lengths = np.ones((extent,), np.int32)
        for i, P in zip(admitted, Ps):
            lengths[i] = P
        pargs = srv._prefill_args(extent, jtokens, 0, mask, lengths)
        try:
            with span("serve.prefill"):
                pmod, pkey, _ = srv.prefill_bucketed.program_for(
                    srv.params, cache, *pargs
                )
                logits, cache = pmod(srv.params, cache, *pargs)
        except Exception:
            # contained prefill failure (injected build/dispatch fault):
            # the contiguous cache owns its rows outright, so the slots
            # simply keep their fill buffers and replay the prompt
            # through the decode loop — the same fallback as a grid
            # miss; every other slot's rows were never touched
            self.metrics["admission_failures"] += 1
            return cache
        srv.prefill_bucketed.stats.note_dispatch(
            pkey, (len(admitted), max(Ps)), pkey.extents
        )
        self.metrics["prefill_dispatches"] += 1
        # device-side gather: only the admitted rows' last-real-column
        # argmax crosses to host, not the whole (extent, S, vocab)
        # logits block.  The gather is padded to a fixed (extent,) shape
        # so its jitted program depends only on the bucket cell — an
        # admission wave of any size (including post-requeue retries)
        # reuses the same compiled gather
        rows_p = np.zeros((extent,), np.int32)
        cols_p = np.zeros((extent,), np.int32)
        rows_p[: len(admitted)] = admitted
        cols_p[: len(admitted)] = [P - 1 for P in Ps]
        firsts = np.asarray(
            guarded_argmax(logits[jnp.asarray(rows_p), jnp.asarray(cols_p)])
        ).astype(np.int32)[: len(admitted)]
        for i, P, first in zip(admitted, Ps, firsts):
            s = slots[i]
            s.fill = None
            s.pos = P
            cur_pos[i] = P
            if int(first) == POISON_TOKEN:
                # non-finite prefill logits for this row: flag it — the
                # admission boundary quarantines flagged slots
                s.poisoned = True
                continue
            s.cur_tok = int(first)
            s.tokens.append(s.cur_tok)
            if s.first_wall is None:
                s.first_wall = time.perf_counter()
            s.remaining = s.req.max_new - 1
            cur_tok[i, 0] = s.cur_tok
        return cache

    def _admit_paged(self, admitted: List[int],
                     slots: List[Optional[_Slot]], store, extent: int,
                     cur_tok: np.ndarray, cur_pos: np.ndarray,
                     pt_host: np.ndarray, queue: deque):
        """Admit into the page pool: prefix match, alloc, masked prefill.

        Per admitted slot: match the prompt's leading full-page blocks
        in the prefix tree (matched pages are forked — refcount bump, no
        prefill, no copy), allocate fresh pages for the rest of the
        prompt + generation budget, and write the slot's page-table row.
        Pool exhaustion first reclaims LRU tree-only pages; if the pool
        is still short the request is bounced back to the queue (its
        pages are held by mid-generation slots — they free at retire).

        The prefill dispatch is per-row anchored: a prefix-hit row's
        chunk starts at its skip offset, so hit and cold rows share one
        dispatch and the sequence bucket covers only the longest
        *suffix*.  After prefill each prompt's full pages are inserted
        into the tree so later admissions can share them.
        """
        srv = self.server
        pool = srv.page_pool
        tree = srv.prefix_tree
        ps = pool.page_size
        MP = srv.max_pages_per_slot
        Ps = [len(slots[i].req.prompt) for i in admitted]
        # prefix reuse is only sound on the grid path: matched pages
        # skip prefill, but a fill-path (decode-replay) admission must
        # write every position itself
        grid_ok = srv._seq_bucket_extent(max(Ps), extent=extent) is not None

        live: List[int] = []
        deferred: List[Request] = []
        for i in list(admitted):
            s = slots[i]
            prompt = np.asarray(s.req.prompt, np.int32)
            P = len(prompt)
            total = pages_for(P + s.req.max_new, ps)
            shared: List[int] = []
            skip = 0
            if grid_ok:
                # the last real prompt token must prefill — its logits
                # emit the first token — so the match is capped one
                # token short of the prompt
                shared, skip = tree.match(
                    prompt, max_tokens=((P - 1) // ps) * ps
                )
            try:
                if shared:
                    pool.fork(shared)  # the slot's own refs on the chain
                try:
                    fresh = pool.alloc(total - len(shared))
                except MemoryError:
                    tree.reclaim(total - len(shared) - pool.pages_free)
                    fresh = pool.alloc(total - len(shared))
            except MemoryError:
                # exhausted even after reclaim: the missing pages are
                # held by mid-generation slots — requeue and vacate
                if shared:
                    pool.free(shared)
                slots[i] = None
                deferred.append(s.req)
                self.metrics["deferrals"] += 1
                if s.swapped_in:
                    self.metrics["swaps"] -= 1
                continue
            s.pages = list(shared) + list(fresh)
            s.skip = skip
            pt_host[i] = build_row_table(s.pages, MP)
            live.append(i)
        if deferred:
            queue.extendleft(reversed(deferred))
        if not live or not grid_ok:
            # fill-path admission: the decode loop writes the prompt's
            # pages token-by-token through the table (skip == 0)
            return store
        Ls = [len(slots[i].req.prompt) - slots[i].skip for i in live]
        # suffixes never exceed the full prompts, so the cell that
        # admitted max(Ps) covers max(Ls) too
        s_ext = srv._seq_bucket_extent(max(Ls), extent=extent)
        tokens = np.zeros((extent, s_ext), np.int32)
        mask = np.zeros((extent,), bool)
        pos_np = np.zeros((extent,), np.int32)
        last = np.zeros((extent,), np.int32)
        for i, L in zip(live, Ls):
            s = slots[i]
            suffix = np.asarray(s.req.prompt[s.skip:], np.int32)
            tokens[i, :L] = suffix
            tokens[i, L:] = suffix[-1]  # edge pad
            mask[i] = True
            pos_np[i] = s.skip
            last[i] = L - 1
        pargs = (jnp.asarray(pt_host), jnp.asarray(tokens),
                 jnp.asarray(pos_np), jnp.asarray(mask), jnp.asarray(last))
        try:
            with span("serve.prefill"):
                pmod, pkey, _ = srv.prefill_bucketed.program_for(
                    srv.params, store, *pargs
                )
                logits, store = pmod(srv.params, store, *pargs)
        except Exception:
            # a failed paged prefill must NOT fall back to fill-path
            # replay: prefix-hit rows hold forked (shared) pages, and a
            # token-by-token replay from position 0 would write into
            # pages other slots and the prefix tree still read.  Undo
            # the admission instead — drop the rows' page refs, vacate
            # the slots, requeue the requests for a later tick.
            self.metrics["admission_failures"] += 1
            for i in live:
                s = slots[i]
                if s.pages:
                    pool.free(s.pages)
                    s.pages = []
                pt_host[i] = TRASH_PAGE
                slots[i] = None
                if s.swapped_in:
                    self.metrics["swaps"] -= 1
                queue.append(s.req)
            return store
        srv.prefill_bucketed.stats.note_dispatch(
            pkey, (len(live), max(Ls)), pkey.extents
        )
        self.metrics["prefill_dispatches"] += 1
        pool.stats.tokens_prefilled += sum(Ls)
        # the step gave each row's last-real-suffix-column logits: only
        # the argmax of every row crosses to host
        firsts = np.asarray(guarded_argmax(logits)).astype(np.int32)[live]
        for i, first in zip(live, firsts):
            s = slots[i]
            P = len(s.req.prompt)
            s.fill = None
            s.pos = P
            cur_pos[i] = P
            if int(first) == POISON_TOKEN:
                # non-finite prefill logits: flag for quarantine at the
                # admission boundary, and do NOT register the row's
                # pages in the prefix tree — their KV came out of the
                # same suspect dispatch
                s.poisoned = True
                continue
            s.cur_tok = int(first)
            s.tokens.append(s.cur_tok)
            if s.first_wall is None:
                s.first_wall = time.perf_counter()
            s.remaining = s.req.max_new - 1
            cur_tok[i, 0] = s.cur_tok
            # register the prompt's full pages for later admissions;
            # decode writes start at P — strictly past every registered
            # page — so cached pages are never mutated afterwards
            nfull = P // ps
            if nfull:
                tree.insert(s.req.prompt[:nfull * ps], s.pages[:nfull])
        return store

    def report(self) -> str:
        m = self.metrics
        cap = max(m["capacity_row_steps"], 1)
        return (
            f"slots: dispatches={m['decode_dispatches']} "
            f"occupancy={m['occupied_row_steps'] / cap:.1%} "
            f"pad_decode={1 - m['occupied_row_steps'] / cap:.1%} "
            f"swaps={m['swaps']} resizes={m['resizes']} "
            f"prefills={m['prefill_dispatches']}"
            + (f" preempts={m['preemptions']} resumes={m['resumes']} "
               f"shed={m['shed']}" if m["preemptions"] or m["shed"] else "")
            + (f" deferrals={m['deferrals']}" if self.paged else "")
            + (f" warm_fallbacks={m['warm_fallbacks']}"
               if self.server.async_compile else "")
        )


def _compile_epilogue(server: BatchedServer, args) -> int:
    """CLI transparency for the async/persistent compile tiers, plus
    the restart-replay gate (``--assert-no-builds``)."""
    rc = 0
    if server.compile_cache is not None:
        from repro.core import get_compile_cache

        cs = server.compile_cache.stats
        ds = server.compile_cache.store.stats
        # bucket-front builds + the per-block forge bodies that compile
        # through the process-global cache (same disk tier, attached in
        # BatchedServer.__init__) — together: every full Phase 1-4 run
        builds = cs.misses + get_compile_cache().stats.misses
        print(f"[serve] disk cache: builds={builds} "
              f"disk_hits={cs.disk_hits + get_compile_cache().stats.disk_hits} "
              f"mem_hits={cs.hits} writes={ds.writes} "
              f"corrupt={ds.corrupt} bytes_written={ds.bytes_written}")
        if args.assert_no_builds and builds > 0:
            print(f"[serve] ASSERT FAILED: {builds} full builds ran "
                  f"against --cache-dir={args.cache_dir} (expected a "
                  f"pure disk replay)")
            rc = 1
    if server.compile_service is not None:
        ss = server.compile_service.stats.snapshot()
        extra = ""
        if server.bucketed is not None:
            bs = server.bucketed.stats
            extra = (f" wait_s={bs.compile_wait_s:.2f} "
                     f"bg_s={bs.compile_background_s:.2f} "
                     f"fallbacks={bs.fallback_calls}"
                     f"(+{bs.fallback_cells_padded} cells)")
        print(f"[serve] compile service: submitted={ss['submitted']} "
              f"completed={ss['completed']} dedup={ss['dedup_hits']} "
              f"promoted={ss['promoted']} failed={ss['failed']} "
              f"busy_s={ss['busy_s']:.2f}" + extra)
        server.compile_service.shutdown()
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="forge-125m",
                    choices=ARCH_IDS + ["forge-125m"])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--mode", choices=["jit", "interpret", "forge"],
                    default="jit")
    ap.add_argument("--backend", default="segment_jit",
                    help="Phase-4 backend for --mode forge "
                         "(interpret | segment_jit | reference)")
    ap.add_argument("--bucket-policy", default="pow2",
                    help="batch-axis bucket policy for --mode forge "
                         "(exact | pow2 | ladder:<r1,r2,...>)")
    ap.add_argument("--seq-bucket-policy", default="ladder:16,32,64,128,256",
                    help="sequence-axis bucket policy for the 2-D "
                         "whole-prompt prefill grid (--mode forge)")
    ap.add_argument("--prefill", default="auto",
                    choices=["auto", "batched", "sequential"],
                    help="prefill strategy: auto = whole-prompt batched "
                         "when the family supports it, sequential = "
                         "token-at-a-time baseline")
    ap.add_argument("--sweep", default=None,
                    help="comma-separated batch sizes to serve as a "
                         "workload sweep (mode=forge), e.g. 1,2,3,5,8,13")
    ap.add_argument("--prompt-sweep", default=None,
                    help="comma-separated prompt lengths to cross with "
                         "--sweep (mode=forge), e.g. 17,32,48,100")
    ap.add_argument("--continuous", type=int, default=0, metavar="N",
                    help="serve N mixed-length requests through the "
                         "slot scheduler instead of the sweep "
                         "(mode=forge)")
    ap.add_argument("--max-slots", type=int, default=8,
                    help="slot-scheduler bucket cap (--continuous)")
    ap.add_argument("--paged", action="store_true",
                    help="serve the KV cache from a shared page pool "
                         "with prefix reuse (--mode forge --continuous); "
                         "contiguous per-slot rows remain the default")
    ap.add_argument("--kv-page-size", type=int, default=16,
                    help="tokens per KV page (--paged; must divide "
                         "--max-len)")
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="page-pool size incl. the reserved trash page "
                         "(--paged; 0 = eight full-length slots' worth)")
    ap.add_argument("--kv-kernel", default="ref",
                    choices=["ref", "pallas", "interpret"],
                    help="paged attend implementation (--paged): ref = "
                         "page gather + unfused sdpa (bitwise vs the "
                         "contiguous cache), pallas = the paged-"
                         "attention decode kernel compiled for the TPU, "
                         "interpret = that kernel in the Pallas "
                         "interpreter")
    ap.add_argument("--async-compile", action="store_true",
                    help="compile cold buckets on a background worker "
                         "pool; dispatches pad into the nearest warm "
                         "dominating bucket instead of blocking "
                         "(--mode forge)")
    ap.add_argument("--compile-workers", type=int, default=2,
                    help="background compile worker threads "
                         "(--async-compile)")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent on-disk Forge compile store: bucket "
                         "programs (Phase 4a-c analysis + serialized "
                         "segment executables) replay across process "
                         "restarts (--mode forge).  JAX's own "
                         "compilation cache is placed by "
                         "JAX_COMPILATION_CACHE_DIR, else .jax_cache/ at "
                         "the checkout root")
    ap.add_argument("--assert-no-builds", action="store_true",
                    help="exit nonzero if any full Phase 1-4 build ran "
                         "(compile-cache miss count > 0) — the CI "
                         "restart-replay gate against a populated "
                         "--cache-dir")
    ap.add_argument("--chaos", default=None, metavar="SITE=RATE[,..]",
                    help="arm a seeded fault plan before serving, e.g. "
                         "'compile.build=0.2,page.alloc=0.1' or 'all=0.05' "
                         "(sites: " + ", ".join(chaos.ALL_SITES) + "); "
                         "the loop must finish with typed outcomes, "
                         "never crash")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the --chaos fault plan (per-site "
                         "streams; same seed = same fault schedule)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.paged and not args.continuous:
        ap.error("--paged serves through the slot scheduler; "
                 "add --continuous N")
    if args.paged and args.mode != "forge":
        ap.error("--paged needs --mode forge")
    if (args.async_compile or args.cache_dir) and args.mode != "forge":
        ap.error("--async-compile / --cache-dir need --mode forge "
                 "(they act on the bucketed fronts)")
    if args.assert_no_builds and not args.cache_dir:
        ap.error("--assert-no-builds needs --cache-dir (it gates the "
                 "restart-replay path)")

    sweep = ([int(x) for x in args.sweep.split(",")] if args.sweep
             else [args.batch])
    prompt_sweep = ([int(x) for x in args.prompt_sweep.split(",")]
                    if args.prompt_sweep else [args.prompt_len])

    if args.mode == "forge":
        from repro.core import get_backend
        from repro.core.shapekey import get_bucket_policy

        try:  # fail fast, before paying model init
            get_backend(args.backend)
            policy = get_bucket_policy(args.bucket_policy)
            get_bucket_policy(args.seq_bucket_policy)
            for B in sweep:  # admission bounds (e.g. ladder overflow)
                policy.bucket(B)
        except ValueError as e:
            ap.error(str(e))

    setup_jax_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.family == "encdec":
        raise SystemExit("use examples/ for enc-dec serving")
    if args.paged:
        cfg = cfg.with_(kv_kernel=args.kv_kernel)
    model = get_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    # one program: no float32 draw of a stacked weight sits beside the
    # bf16 parameters, as it would op by op
    params = jax.jit(model.init, static_argnums=1)(key, cfg)
    rng = np.random.default_rng(args.seed)

    server = BatchedServer(cfg, params, max_len=args.max_len, mode=args.mode,
                           backend=args.backend,
                           bucket_policy=args.bucket_policy,
                           seq_bucket_policy=args.seq_bucket_policy,
                           prefill=args.prefill, paged=args.paged,
                           kv_page_size=args.kv_page_size,
                           kv_pages=args.kv_pages or None,
                           async_compile=args.async_compile,
                           compile_workers=args.compile_workers,
                           cache_dir=args.cache_dir)

    plan = None
    if args.chaos:
        if not args.continuous:
            ap.error("--chaos needs --continuous N (fault containment "
                     "lives in the slot-scheduler loop)")
        try:
            plan = chaos.plan_from_spec(args.chaos, seed=args.chaos_seed)
        except ValueError as e:
            ap.error(str(e))

    if args.continuous:
        if args.mode != "forge":
            ap.error("--continuous needs --mode forge")
        lens = sorted({max(2, p // (2 ** k)) for p in prompt_sweep
                       for k in range(2)})
        reqs = [
            Request(
                rid=i,
                prompt=rng.integers(0, cfg.vocab,
                                    (int(rng.choice(lens)),)).astype(np.int32),
                max_new=int(rng.integers(2, args.gen + 1)),
                arrival=int(i // args.max_slots),
            )
            for i in range(args.continuous)
        ]
        sched = SlotScheduler(server, max_slots=args.max_slots)
        warmup_s = sched.warmup(lens)
        # armed only for the serving loop: setup/warmup is not a
        # containment domain, the scheduler tick is
        if plan is not None:
            chaos.install_plan(plan)
        try:
            res = sched.run(reqs)
        finally:
            if plan is not None:
                chaos.install_plan(None)
        print(f"[serve] {cfg.name} continuous n={args.continuous} "
              f"tok/s={res['tok_per_s']:.0f} "
              f"occupancy={res['occupancy']:.1%} "
              f"pad_decode={res['pad_decode_fraction']:.1%} "
              f"swaps={res['swaps']} resizes={res['resizes']} "
              f"compiles_post_warmup={res['compiles']} "
              f"(warmup={warmup_s:.2f}s)")
        print(f"[serve] {sched.report()}")
        if plan is not None:
            errs = sum(1 for r in res["results"].values() if "error" in r)
            ok = len(res["results"]) - errs
            print(f"[serve] chaos: faults_injected={plan.faults_injected} "
                  f"requests_ok={ok} requests_failed={errs} "
                  f"degraded_ticks={res['ticks_degraded']} "
                  f"aborted={res['aborted']}")
        if args.paged:
            print(f"[serve] pages: in_use={res['kv_pages_in_use']}/"
                  f"{res['kv_pages_capacity']} "
                  f"peak={res['kv_peak_pages_in_use']} "
                  f"(page={args.kv_page_size}tok) "
                  f"prefix hit_rate={res['prefix_hit_rate']:.1%} "
                  f"skip_rate={res['prefill_skip_rate']:.1%} "
                  f"tokens_reused={res['tokens_reused']} "
                  f"reclaimed={res['pages_reclaimed']}")
            from repro.core.metrics import bucket_report
            print(f"[serve] decode "
                  f"{bucket_report(server.bucketed.stats, server.page_pool)}")
        return _compile_epilogue(server, args)

    warmup_s = server.warmup(sweep, prompt_lens=prompt_sweep)

    for B in sweep:
        for P in prompt_sweep:
            prompts = rng.integers(0, cfg.vocab, (B, P))
            res = server.generate(prompts.astype(np.int32), args.gen)
            # TTFT (prefill wall) reported separately from steady-state
            # decode throughput — the 2-D grid's win is in the former
            print(f"[serve] {cfg.name} batch={B} prompt={P} "
                  f"ttft={res['ttft_s'] * 1e3:.1f}ms "
                  f"(prefill={res['prefill_mode'] or args.mode}) "
                  f"compile={res['compile_s']:.2f}s "
                  f"decode mean={res['decode_ms_mean']:.1f}ms "
                  f"p50={res['decode_ms_p50']:.1f} "
                  f"p99={res['decode_ms_p99']:.1f} "
                  f"({res['tok_per_s']:.0f} tok/s steady-state)")
            assert res["tokens"].shape == (B, args.gen)

    if server.bucketed is not None:
        from repro.core import get_compile_cache
        from repro.core.metrics import bucket_report

        bs = server.bucketed.stats
        cs = get_compile_cache().stats
        # compile_s (warmup) reported separately from steady-state tok/s:
        # after warmup every row above decoded with zero Phase 1-4 reruns
        print(f"[serve] compile_s={server._compile_s_total():.2f} "
              f"(warmup wall={warmup_s:.2f}s) decode {bucket_report(bs)}")
        if server.prefill_bucketed is not None:
            print(f"[serve] prefill grid "
                  f"{bucket_report(server.prefill_bucketed.stats)}")
        r = server.forge_module.result
        s = r.executor_stats
        rs = server.forge_module.stats  # live run counters (donation/pool)
        print(f"[serve] forge backend={r.backend} bucket={r.shape_key} "
              f"cache_hit={r.cache_hit} "
              f"segments={s.n_segments} (compiled={s.n_compiled_segments}) "
              f"delta={s.delta_before}->{s.delta_after} "
              f"donating={rs.n_donating_segments}seg/"
              f"{rs.n_donated_args}args "
              f"file_pool={rs.file_pool_hits}h/{rs.file_pool_misses}m "
              f"cache hit_rate={cs.hit_rate:.1%} "
              f"({cs.hits}h/{cs.misses}m)")
    return _compile_epilogue(server, args)


if __name__ == "__main__":
    raise SystemExit(main())
