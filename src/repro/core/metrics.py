"""The paper's three evaluation metrics (§5) + the fidelity protocol (§6.5).

* **per-pass profiling** — τ(p_k); produced by the pipeline itself
  (``CompilationResult.pass_table``), re-exported here for benchmarks.
* **FGR** (Eq. 22) — CostModel(α=0) / CostModel(α=1): a cost-model-
  internal diagnostic of fusion impact.  NOT a latency ratio (paper's
  caveat retained).
* **CEI** (Eq. 23/24) — (L_baseline / L_forge) / T_compile_seconds:
  latency-speedup delivered per second of compile time.
* **fidelity** — max-abs logit difference and KL divergence between
  pre- and post-compilation outputs (paper Table 6 protocol).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .capture import trace_to_graph
from .compiler import CompilationResult, ForgeCompiler
from .cost_model import score_graph
from .passes import PipelineConfig, run_forge_passes


# --------------------------------------------------------------------------
# FGR
# --------------------------------------------------------------------------


def fusion_gain_ratio(
    fn: Callable,
    *example_args: Any,
    config: Optional[PipelineConfig] = None,
) -> Dict[str, float]:
    """FGR = Score(α=0) / Score(α=1)  (paper Eq. 22)."""
    base = config or PipelineConfig()

    def _score(alpha: float) -> float:
        cfg = PipelineConfig(
            alpha=alpha,
            layout=base.layout,
            precision=base.precision,
            max_rounds=base.max_rounds,
            impl=base.impl,
            swiglu_fusion=base.swiglu_fusion,
            enable=dict(base.enable),
        )
        cap = trace_to_graph(fn, *example_args)
        run_forge_passes(cap.graph, cfg=cfg)
        return score_graph(cap.graph, cfg.precision).score

    s0 = _score(0.0)
    s1 = _score(1.0)
    return {"score_alpha0": s0, "score_alpha1": s1, "fgr": s0 / max(s1, 1e-12)}


# --------------------------------------------------------------------------
# CEI
# --------------------------------------------------------------------------


def compilation_efficiency_index(
    latency_baseline_ms: float,
    latency_forge_ms: float,
    compile_time_ms: float,
) -> float:
    """CEI_B = (L_B / L_forge) / T_compile^(s)  (paper Eq. 23)."""
    speedup = latency_baseline_ms / max(latency_forge_ms, 1e-12)
    return speedup / max(compile_time_ms / 1e3, 1e-12)


# --------------------------------------------------------------------------
# Numerical fidelity (paper §6.5 protocol, Table 6)
# --------------------------------------------------------------------------


@dataclass
class FidelityReport:
    max_abs_diff: float
    kl_divergence: float
    n_elements: int

    def ok(self, max_abs: float = 2.1e-5, max_kl: float = 8.4e-9) -> bool:
        """Check against the paper's reported bounds (Table 6)."""
        return self.max_abs_diff <= max_abs and self.kl_divergence <= max_kl


def _kl(p_logits: jnp.ndarray, q_logits: jnp.ndarray) -> float:
    """Mean KL(P‖Q) over the last axis of logits."""
    p = jax.nn.log_softmax(p_logits.astype(jnp.float32), axis=-1)
    q = jax.nn.log_softmax(q_logits.astype(jnp.float32), axis=-1)
    kl = jnp.sum(jnp.exp(p) * (p - q), axis=-1)
    return float(jnp.mean(kl))


def fidelity(
    pre_outputs: Any,
    post_outputs: Any,
    *,
    logits_are_last_axis: bool = True,
) -> FidelityReport:
    """Compare pre- vs post-compilation outputs (logit-level, Table 6)."""
    pre_flat = jax.tree_util.tree_leaves(pre_outputs)
    post_flat = jax.tree_util.tree_leaves(post_outputs)
    assert len(pre_flat) == len(post_flat), "output arity mismatch"
    max_abs = 0.0
    kl = 0.0
    n = 0
    for a, b in zip(pre_flat, post_flat):
        a = jnp.asarray(a, dtype=jnp.float32)
        b = jnp.asarray(b, dtype=jnp.float32)
        max_abs = max(max_abs, float(jnp.max(jnp.abs(a - b))))
        if logits_are_last_axis and a.ndim >= 1 and a.shape[-1] > 1:
            kl = max(kl, _kl(a, b))
        n += int(np.prod(a.shape or (1,)))
    return FidelityReport(max_abs_diff=max_abs, kl_divergence=kl, n_elements=n)


def check_compilation_fidelity(
    fn: Callable,
    *concrete_args: Any,
    config: Optional[PipelineConfig] = None,
) -> FidelityReport:
    """End-to-end protocol: run ``fn`` raw vs Forge-compiled, compare."""
    pre = fn(*concrete_args)
    mod = ForgeCompiler(config or PipelineConfig()).compile(fn, *concrete_args)
    post = mod(*concrete_args)
    return fidelity(pre, post)


def check_bucketed_fidelity(
    fn: Callable,
    *concrete_args: Any,
    in_axes: Any = 0,
    out_axes: Any = 0,
    policy: Any = "pow2",
    axes: Optional[Sequence[Any]] = None,
    config: Optional[PipelineConfig] = None,
    backend: Optional[str] = None,
) -> FidelityReport:
    """Bucketed pad-and-mask execution vs exact-shape compilation.

    Compiles ``fn`` twice — once specialized to the concrete shapes, once
    through the ShapeKey bucketing front (``axes=(PolyAxis, ...)`` for
    multi-axis fronts, the 1-D kwargs otherwise) — and compares outputs.
    Any divergence means the padded rows/columns were *not* inert (some
    op coupled rows along a polymorphic axis) or the output mask sliced
    the wrong axis.  Private caches keep the two compiles from sharing
    executors.
    """
    from .cache import CompileCache

    cfg = config or PipelineConfig()
    exact = ForgeCompiler(cfg, backend=backend, cache=CompileCache()).compile(
        fn, *concrete_args
    )
    bucketed = ForgeCompiler(
        cfg, backend=backend, cache=CompileCache()
    ).compile_bucketed(
        fn, axes=axes, in_axes=in_axes, out_axes=out_axes, policy=policy
    )
    return fidelity(exact(*concrete_args), bucketed(*concrete_args))


def check_prefill_fidelity(
    cfg: Any,
    params: Any,
    prompts: Any,
    *,
    max_len: int = 64,
) -> FidelityReport:
    """Whole-prompt batched prefill vs sequential decode-step replay.

    Runs the model's ``prefill_step`` once on the (B, P) prompt block
    and ``decode_step`` P times on the same prompts, then compares the
    per-position logits AND the resulting KV caches — the acceptance
    bound for the 2-D serve front is 1e-5 max-abs (any divergence means
    the chunk-causal length mask let a future token leak into a past
    position, or the cache write strided wrong).
    """
    import numpy as np

    from ..models import get_model

    model = get_model(cfg)
    if model.prefill_step is None:
        raise ValueError(f"family {cfg.family!r} has no batched prefill")
    prompts = np.asarray(prompts)
    B, P = prompts.shape

    cache_seq = model.init_cache(cfg, B, max_len)
    logits_seq = []
    for i in range(P):
        lg, cache_seq = model.decode_step(
            params, cache_seq, jnp.asarray(prompts[:, i:i + 1], jnp.int32),
            jnp.asarray(i, jnp.int32), cfg,
        )
        logits_seq.append(lg[:, -1, :])

    cache_b = model.init_cache(cfg, B, max_len)
    logits_b, cache_b = model.prefill_step(
        params, cache_b, jnp.asarray(prompts, jnp.int32),
        jnp.asarray(0, jnp.int32), cfg,
    )
    return fidelity(
        (jnp.stack(logits_seq, axis=1), cache_seq),
        (logits_b, cache_b),
    )


def check_ragged_decode_fidelity(
    cfg: Any,
    params: Any,
    prompts: Sequence[Any],
    *,
    n_new: int = 3,
    max_len: int = 32,
) -> FidelityReport:
    """Vectorized per-row-position decode vs per-row sequential decode.

    ``prompts`` is a list of 1-D token arrays of DIFFERENT lengths.  The
    reference decodes each row solo (batch 1, scalar positions); the
    candidate runs all rows in ONE batch through slot-masked ragged
    decode — each prompt consumed through masked decode steps (rows
    whose prompt is exhausted are frozen by ``slot_mask``), then
    ``n_new`` greedy steps with a per-row position vector.  Any
    divergence means a per-row RoPE/KV-write/mask strayed from its
    row's position, or a masked slot leaked state — the acceptance
    bound for slot-level continuous batching is 1e-5 max-abs.
    """
    import numpy as np

    from ..models import get_model

    model = get_model(cfg)
    B = len(prompts)
    prompts = [np.asarray(p, np.int32) for p in prompts]
    plens = [len(p) for p in prompts]

    def greedy(lg):
        return jnp.argmax(lg[:, -1, :], axis=-1).astype(jnp.int32)[:, None]

    solo_logits = []  # per row: (n_new, vocab)
    for r in range(B):
        cache = model.init_cache(cfg, 1, max_len)
        lg = None
        for i in range(plens[r]):
            lg, cache = model.decode_step(
                params, cache, jnp.asarray(prompts[r][i:i + 1][None]),
                jnp.asarray(i, jnp.int32), cfg,
            )
        tok = greedy(lg)
        outs = []
        for j in range(n_new):
            lg, cache = model.decode_step(
                params, cache, tok, jnp.asarray(plens[r] + j, jnp.int32),
                cfg,
            )
            outs.append(lg[0, -1, :])
            tok = greedy(lg)
        solo_logits.append(jnp.stack(outs))

    cache = model.init_cache(cfg, B, max_len)
    tok_col = np.zeros((B, 1), np.int32)
    first = np.zeros((B, 1), np.int32)
    for i in range(max(plens)):
        active = np.asarray([i < p for p in plens])
        for r in range(B):
            tok_col[r, 0] = prompts[r][min(i, plens[r] - 1)]
        lg, cache = model.decode_step(
            params, cache, jnp.asarray(tok_col),
            jnp.asarray(np.full((B,), i, np.int32)), cfg,
            slot_mask=jnp.asarray(active),
        )
        t = np.asarray(greedy(lg))
        for r in range(B):
            if plens[r] == i + 1:
                first[r] = t[r]
    tok = jnp.asarray(first)
    pos = np.asarray(plens, np.int32)
    ragged = []
    for j in range(n_new):
        lg, cache = model.decode_step(
            params, cache, tok, jnp.asarray(pos + j), cfg,
            slot_mask=jnp.ones((B,), bool),
        )
        ragged.append(lg[:, -1, :])
        tok = greedy(lg)
    return fidelity(
        jnp.stack(solo_logits),  # (B, n_new, vocab)
        jnp.stack(ragged, axis=1),
    )


def bucket_report(stats: Any, page_pool: Any = None) -> str:
    """One-line summary of a BucketedModule's BucketStats, with the page
    pool's counters (``PagePool`` and its ``PageStats``) when given."""
    per = ", ".join(
        f"{k}:{v}" for k, v in sorted(stats.per_bucket_calls.items())
    )
    pool = ""
    if stats.pool_hits or stats.pool_misses:
        pool = (
            f" pool={stats.pool_hits}h/{stats.pool_misses}m "
            f"(hit_rate={stats.pool_hit_rate:.1%}, "
            f"reused={stats.pool_bytes_reused / 1e6:.1f}MB)"
        )
    evic = f" evictions={stats.evictions}" if stats.evictions else ""
    # async-compile split: request-visible stall vs worker-absorbed time
    async_note = ""
    if getattr(stats, "compile_background_s", 0.0) or getattr(
        stats, "fallback_calls", 0
    ):
        async_note = (
            f" wait_s={stats.compile_wait_s:.2f}"
            f" bg_s={stats.compile_background_s:.2f}"
            f" fallbacks={stats.fallback_calls}"
            f" (+{stats.fallback_cells_padded} padded cells)"
        )
    pages = ""
    if page_pool is not None:
        ps = page_pool.stats
        pages = (
            f" kv_pages={page_pool.pages_in_use}/{page_pool.capacity}"
            f" (peak={ps.peak_pages_in_use},"
            f" prefix_hits={ps.prefix_hits},"
            f" tokens_reused={ps.tokens_reused})"
        )
    faults = ""
    if (getattr(stats, "faults_injected", 0)
            or getattr(stats, "requests_failed", 0)
            or getattr(stats, "ticks_degraded", 0)
            or getattr(stats, "dispatch_retries", 0)):
        faults = (
            f" faults={stats.faults_injected}"
            f" req_failed={stats.requests_failed}"
            f" degraded_ticks={stats.ticks_degraded}"
            f" retries={stats.dispatch_retries}"
        )
    return (
        f"buckets: compiles={stats.compiles} hits={stats.bucket_hits} "
        f"(hit_rate={stats.hit_rate:.1%}) calls={stats.calls} "
        f"pad_waste={stats.pad_waste:.1%} compile_s={stats.compile_s:.2f}"
        f" (forge={stats.forge_phases_s:.2f} xla={stats.xla_compile_s:.2f})"
        f"{async_note}{evic}{pool}{pages}{faults} [{per}]"
    )


def check_backend_fidelity(
    fn: Callable,
    *concrete_args: Any,
    backends: Sequence[str] = ("interpret", "segment_jit"),
    config: Optional[PipelineConfig] = None,
) -> Dict[str, FidelityReport]:
    """Compare every Phase-4 backend against the ``reference`` oracle.

    The reference backend executes the same lowered program with no
    scheduling and no buffer sharing, so any divergence here isolates a
    Phase-4 (backend-layer) bug from a Phase-1..3 one.
    """
    cfg = config or PipelineConfig()
    oracle = ForgeCompiler(cfg, backend="reference").compile(fn, *concrete_args)
    ref_out = oracle(*concrete_args)
    reports: Dict[str, FidelityReport] = {}
    for name in backends:
        mod = ForgeCompiler(cfg, backend=name).compile(fn, *concrete_args)
        reports[name] = fidelity(ref_out, mod(*concrete_args))
    return reports
