"""Zero-copy Phase-4 execution (ISSUE 3): donation safety, precompiled
dispatch plans over the pooled flat buffer file, per-bucket buffer
pooling, and per-constant fingerprint memoization.

The donation property tests are seed-parametrized random RGIR programs
(same convention as test_scheduler_props): a donated live-in must never
be read after its segment, never be caller-owned (program input or
constant), and must have a live-out of identical aval for XLA to alias
its buffer onto — and donated-path outputs must match the unscheduled,
unallocated ``reference`` oracle.
"""
import gc
import logging
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BufferPool,
    CompileCache,
    ForgeCompiler,
    PipelineConfig,
)
from repro.core.backends import SegmentExecutor
from repro.core.bufalloc import segment_donations
from repro.core.capture import trace_to_graph
from repro.core.executor import analyze_program
from repro.core.lowering import lower_to_rgir
from repro.core.passes import run_forge_passes
from repro.core.shapekey import BucketStats


def random_dag_program(seed: int, n_ops: int = 12):
    """Lower a random primitive DAG mixing host and accel ops.

    Matmul-heavy relative to test_scheduler_props' generator so device
    transitions (and therefore dying live-ins crossing segment
    boundaries) are frequent — the donation analysis' target shape.
    """
    rng = np.random.default_rng(seed)

    def f(x):
        vals = [x]
        for _ in range(n_ops):
            a = vals[int(rng.integers(0, len(vals)))]
            b = vals[int(rng.integers(0, len(vals)))]
            op = int(rng.integers(0, 4))
            if op == 0:
                vals.append(a + b)  # host
            elif op == 1:
                vals.append(a * 0.5 + jnp.tanh(b))  # host
            else:
                vals.append(a @ b)  # accel (dot_general)
        return vals[-1]

    return lower_to_rgir(trace_to_graph(f, np.ones((4, 4), np.float32)).graph)


SEEDS = list(range(20))


def _segment_executor(prog, **kw):
    return SegmentExecutor(analyze_program(prog), warmup=False, **kw)


def _block_prog(block_fn, block_args):
    g = trace_to_graph(block_fn, *block_args).graph
    run_forge_passes(g)
    return lower_to_rgir(g)


class TestDonationSafety:
    @pytest.mark.parametrize("seed", SEEDS)
    def test_donated_regs_die_inside_their_segment(self, seed):
        """A donated live-in is never read by any later instruction."""
        ex = _segment_executor(random_dag_program(seed))
        for seg in ex.segments:
            for pos in seg.donate_argnums:
                r = seg.live_in[pos]
                s, e = ex.live.intervals[r]
                assert s >= 0, "caller-owned register donated"
                assert seg.start <= e < seg.stop, "donated reg outlives segment"
                assert r in seg.free_after
                assert r not in ex.live.pinned
                for op in ex.prog.ops[seg.stop:]:
                    assert r not in op.input_regs, (
                        f"r{r} donated in seg{seg.index} but read later"
                    )

    @pytest.mark.parametrize("seed", SEEDS)
    def test_inputs_and_constants_never_donated(self, seed):
        ex = _segment_executor(random_dag_program(seed))
        caller_owned = set(ex.prog.input_regs) | set(ex.prog.constants)
        for seg in ex.segments:
            donated = {seg.live_in[p] for p in seg.donate_argnums}
            assert not (donated & caller_owned)

    @pytest.mark.parametrize("seed", SEEDS)
    def test_donated_avals_match_a_live_out(self, seed):
        """Every donated buffer must be usable: one live-out of identical
        shape/dtype per donated arg (multiset-matched, no double use)."""
        ex = _segment_executor(random_dag_program(seed))
        ra = ex.prog.reg_avals
        for seg in ex.segments:
            outs = [
                (tuple(ra[r].shape), str(ra[r].dtype)) for r in seg.live_out
            ]
            for pos in seg.donate_argnums:
                r = seg.live_in[pos]
                key = (tuple(ra[r].shape), str(ra[r].dtype))
                assert key in outs
                outs.remove(key)

    def test_block_graph_donates(self, block_fn, block_args):
        """The fused transformer block must exercise the donated path."""
        ex = _segment_executor(_block_prog(block_fn, block_args))
        assert ex.stats.n_donating_segments >= 1
        assert ex.stats.n_donated_args >= 1

    def test_donation_analysis_unit(self):
        """Direct check of the candidate conditions on a crafted segment."""
        from repro.core.liveness import LivenessInfo
        from repro.core._jax_internal import ShapedArray

        aval = ShapedArray((4, 4), np.dtype(np.float32))
        live = LivenessInfo(
            intervals={0: (-1, 5), 1: (2, 5), 2: (1, 9), 3: (6, 11)},
            dead_after={},
            pinned=set(),
        )
        avals = {r: aval for r in (0, 1, 2, 3)}
        # segment [4, 8): r0 (input) and r1 die inside; r2 lives past it
        donate = segment_donations(
            live, avals, live_in=(0, 1, 2), live_out=(3,),
            free_after=(0, 1),
        )
        assert donate == (1,)  # r1 only: r0 is caller-owned, r2 survives


class TestDonationFidelity:
    @pytest.mark.parametrize("seed", SEEDS[:10])
    def test_matches_reference_oracle(self, seed):
        from repro.core.backends import get_backend

        prog = random_dag_program(seed)
        x = np.random.default_rng(seed).standard_normal((4, 4)).astype(
            np.float32
        ) * 0.1
        ref_out = get_backend("reference").build(prog).execute(x)
        seg_ex = SegmentExecutor(analyze_program(prog))
        for _ in range(2):  # repeat: pooled file reuse must stay correct
            out = seg_ex.execute(x)
            diff = max(
                float(np.max(np.abs(np.asarray(a, np.float32)
                                    - np.asarray(b, np.float32))))
                for a, b in zip(ref_out, out)
            )
            assert diff <= 1e-5

    def test_donated_vs_nondonated_identical(self, block_fn, block_args):
        prog = _block_prog(block_fn, block_args)
        a = SegmentExecutor(analyze_program(prog), donate=True)
        b = SegmentExecutor(analyze_program(prog), donate=False)
        flat = [np.asarray(x) for x in block_args]
        out_a = a.execute(*flat)
        out_b = b.execute(*flat)
        for va, vb in zip(out_a, out_b):
            np.testing.assert_allclose(
                np.asarray(va, np.float32), np.asarray(vb, np.float32),
                atol=1e-5, rtol=0,
            )


class TestDispatchPlans:
    def test_zero_buffer_file_allocs_steady_state(self, block_fn, block_args):
        """After the first call every call reuses the pooled buffer file."""
        mod = ForgeCompiler(
            PipelineConfig(backend="segment_jit"), cache=CompileCache()
        ).compile(block_fn, *block_args)
        for _ in range(4):
            mod(*block_args)
        assert mod.stats.file_pool_misses == 1
        assert mod.stats.file_pool_hits == 3

    def test_interpret_backend_pools_too(self, block_fn, block_args):
        mod = ForgeCompiler(
            PipelineConfig(backend="interpret"), cache=CompileCache()
        ).compile(block_fn, *block_args)
        for _ in range(3):
            mod(*block_args)
        assert mod.stats.file_pool_misses == 1
        assert mod.stats.file_pool_hits == 2

    def test_pooled_replay_is_deterministic(self, block_fn, block_args):
        mod = ForgeCompiler(
            PipelineConfig(backend="segment_jit"), cache=CompileCache()
        ).compile(block_fn, *block_args)
        first = np.asarray(mod(*block_args), np.float32)
        for _ in range(3):
            np.testing.assert_array_equal(
                first, np.asarray(mod(*block_args), np.float32)
            )

    def test_constants_survive_pooled_reuse(self):
        """Regression: a constant read after another reg's free must still
        be present on the second (pooled-file) call."""

        def f(x):
            c = jnp.asarray(np.arange(16, dtype=np.float32).reshape(4, 4))
            y = x @ c  # c read on the accel side
            return y + c  # ... and on the host side after frees

        x = np.ones((4, 4), np.float32)
        mod = ForgeCompiler(
            PipelineConfig(backend="segment_jit"), cache=CompileCache()
        ).compile(f, x)
        a = np.asarray(mod(x))
        b = np.asarray(mod(x))
        np.testing.assert_array_equal(a, b)

    def test_concurrent_execute_thread_safe(self, block_fn, block_args):
        """Overlapping calls must not share one buffer file."""
        mod = ForgeCompiler(
            PipelineConfig(backend="segment_jit"), cache=CompileCache()
        ).compile(block_fn, *block_args)
        expect = np.asarray(mod(*block_args), np.float32)
        errs = []

        def worker():
            try:
                for _ in range(3):
                    got = np.asarray(mod(*block_args), np.float32)
                    np.testing.assert_array_equal(got, expect)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs

    def test_static_peak_matches_dynamic_semantics(self, block_fn, block_args):
        """The precomputed peak is per-call-stable and bounded by the file."""
        mod = ForgeCompiler(
            PipelineConfig(backend="segment_jit"), cache=CompileCache()
        ).compile(block_fn, *block_args)
        mod(*block_args)
        p1 = mod.stats.last_peak_live_buffers
        mod(*block_args)
        assert mod.stats.last_peak_live_buffers == p1
        assert 0 < p1 <= mod.stats.n_buffers

    def test_fresh_snapshot_zeroes_pool_counters(self, block_fn, block_args):
        mod = ForgeCompiler(
            PipelineConfig(backend="segment_jit"), cache=CompileCache()
        ).compile(block_fn, *block_args)
        mod(*block_args)
        snap = mod.stats.fresh_snapshot()
        assert snap.file_pool_hits == snap.file_pool_misses == 0
        assert snap.total_donated_args == 0
        assert snap.n_donated_args == mod.stats.n_donated_args


class TestWarmupDedup:
    def test_warmup_zeros_shared_by_aval(self, block_fn, block_args,
                                         monkeypatch, caplog):
        """AOT warmup compiles every accel segment from its avals alone:
        it builds no zero array for any live-in (weights included), and
        the first real call then compiles nothing."""
        import repro.core.backends.segment_jit as sj

        calls = []
        real_zeros = np.zeros

        def counting_zeros(*a, **kw):
            calls.append(a)
            return real_zeros(*a, **kw)

        monkeypatch.setattr(np, "zeros", counting_zeros)
        prog = _block_prog(block_fn, block_args)
        ex = SegmentExecutor(analyze_program(prog), warmup=True)
        monkeypatch.setattr(np, "zeros", real_zeros)
        live_in_avals = {
            (tuple(prog.reg_avals[r].shape), np.dtype(prog.reg_avals[r].dtype))
            for seg in ex.segments if seg.compiled
            for r in seg.live_in
        }
        assert live_in_avals, "the block must have compiled segments"
        built = [a for a in calls if a and tuple(np.shape(a[0])) in
                 {shape for shape, _ in live_in_avals}]
        assert built == [], "warmup built arrays for segment live-ins"
        assert not hasattr(sj, "np"), "segment_jit needs no host arrays"

        # host segments replay op by op and compile their eager ops on
        # first use; the accel segment programs must already be built
        with jax.log_compiles(True), caplog.at_level(
            logging.WARNING, logger="jax._src.interpreters.pxla"
        ):
            ex.execute(*block_args)
        late = [r.getMessage() for r in caplog.records
                if "jit(seg_fn)" in r.getMessage()]
        assert late == [], "a warmed segment compiled on first call"


class TestBufferPool:
    def test_hit_miss_and_bytes(self):
        stats = BucketStats()
        pool = BufferPool(stats)
        build = lambda: {"k": np.zeros((8, 8), np.float32)}  # noqa: E731
        t1 = pool.acquire("B8", build)
        assert stats.pool_misses == 1 and stats.pool_hits == 0
        pool.release("B8", t1)
        t2 = pool.acquire("B8", build)
        assert t2 is t1  # reused, not rebuilt
        assert stats.pool_hits == 1
        assert stats.pool_bytes_reused == 8 * 8 * 4
        assert stats.pool_hit_rate == 0.5

    def test_reset_applied_on_hit(self):
        pool = BufferPool(BucketStats())
        tree = {"k": np.full((4,), 7.0, np.float32)}
        pool.release("x", tree)
        got = pool.acquire(
            "x", build=lambda: pytest.fail("should not rebuild"),
            reset=lambda t: {"k": np.zeros_like(t["k"])},
        )
        np.testing.assert_array_equal(got["k"], 0.0)

    def test_failing_reset_falls_back_to_build(self):
        stats = BucketStats()
        pool = BufferPool(stats)
        pool.release("x", {"k": np.zeros(4)})

        def bad_reset(t):
            raise RuntimeError("aliased buffers")

        fresh = {"k": np.ones(4)}
        got = pool.acquire("x", build=lambda: fresh, reset=bad_reset)
        assert got is fresh
        assert stats.pool_misses == 1 and stats.pool_hits == 0

    def test_release_capped(self):
        pool = BufferPool(BucketStats(), max_per_key=2)
        for _ in range(5):
            pool.release("k", {"a": np.zeros(1)})
        assert pool.pooled("k") == 2

    def test_keys_are_independent(self):
        pool = BufferPool(BucketStats())
        pool.release(2, "two")
        pool.release(4, "four")
        assert pool.acquire(4, build=lambda: "fresh") == "four"
        assert pool.acquire(2, build=lambda: "fresh") == "two"
        assert pool.acquire(2, build=lambda: "fresh") == "fresh"


class TestFingerprintMemo:
    def test_large_constant_hashed_once(self):
        from repro.core import cache as C

        big = np.random.default_rng(0).standard_normal((64, 64)).astype(
            np.float32
        )

        def digest_of(v):
            import hashlib

            h = hashlib.sha256()
            C._hash_value(h, v)
            return h.hexdigest()

        h0 = C.fp_memo_stats.hits
        d1 = digest_of(big)
        d2 = digest_of(big)
        assert d1 == d2
        assert C.fp_memo_stats.hits == h0 + 1  # second hash was a memo hit

    def test_different_content_different_digest(self):
        import hashlib

        from repro.core import cache as C

        a = np.zeros((64, 64), np.float32)
        b = np.zeros((64, 64), np.float32)
        b[0, 0] = 1.0
        ha, hb = hashlib.sha256(), hashlib.sha256()
        C._hash_value(ha, a)
        C._hash_value(hb, b)
        assert ha.hexdigest() != hb.hexdigest()

    def test_memo_entry_dropped_on_collection(self):
        import hashlib

        from repro.core import cache as C

        v = np.ones((64, 64), np.float32)
        C._hash_value(hashlib.sha256(), v)
        key = id(v)
        assert key in C._FP_MEMO
        del v
        gc.collect()
        assert key not in C._FP_MEMO

    def test_program_fingerprint_stable_under_memo(self, block_fn,
                                                   block_args):
        from repro.core import fingerprint_program

        prog = _block_prog(block_fn, block_args)
        assert fingerprint_program(prog) == fingerprint_program(prog)
