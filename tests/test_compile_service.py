"""Async background compilation + persistent on-disk compile cache
(ISSUE 7 acceptance criteria).

Covers the CompileService worker pool (dedup, priority, promotion,
failure retry), the BucketedModule async dispatch path (thundering
herd compiles once; warm-bucket fallback is bitwise-equal to the warm
program's own padded output; the exact program takes over once the
background build lands), the DiskCacheStore persistent tier
(roundtrip, checksum corruption detection, salt invalidation), the
eviction-coherence hook, and the serve-level restart-replay flow.
"""
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CompileCache,
    CompileService,
    DiskCacheStore,
    ForgeCompiler,
    PipelineConfig,
    forge_compile_bucketed,
    get_compile_cache,
)


@pytest.fixture(autouse=True)
def _isolate_global_cache():
    """Serve's --cache-dir attaches a disk store to the process-global
    cache; snapshot/restore it so this module never leaks tmp-dir
    stores (or entries) into the rest of the suite."""
    g = get_compile_cache()
    store0 = g.store
    yield
    g.store = store0


def _fn(x):
    return jnp.cumsum(x, axis=-1) * 2.0 + 1.0


def _x(b, seed=0):
    return np.random.default_rng(seed).normal(size=(b, 4)).astype(np.float32)


# --------------------------------------------------------------------------
# CompileService unit behavior (no compiler involved)
# --------------------------------------------------------------------------


class TestCompileService:
    def test_dedup_builds_once(self):
        svc = CompileService(workers=2)
        built = []
        gate = threading.Event()

        def build():
            gate.wait(5.0)
            built.append(1)
            return "value"

        futs = [svc.submit("k", build) for _ in range(8)]
        gate.set()
        assert all(f.result(10.0) == "value" for f in futs)
        assert len(built) == 1
        assert svc.stats.submitted == 1
        assert svc.stats.dedup_hits == 7
        svc.shutdown()

    def test_foreground_preempts_speculative(self):
        svc = CompileService(workers=1)
        order = []
        gate = threading.Event()
        svc.submit("blocker", lambda: gate.wait(5.0))
        time.sleep(0.05)  # let the worker claim the blocker
        svc.submit("spec-a", lambda: order.append("spec-a"),
                   foreground=False)
        svc.submit("spec-b", lambda: order.append("spec-b"),
                   foreground=False)
        fg = svc.submit("fg", lambda: order.append("fg"))
        gate.set()
        fg.result(10.0)
        svc.wait_idle(10.0)
        assert order[0] == "fg"  # jumped the speculative queue
        svc.shutdown()

    def test_promotion_shares_future(self):
        svc = CompileService(workers=1)
        gate = threading.Event()
        svc.submit("blocker", lambda: gate.wait(5.0))
        time.sleep(0.05)
        spec = svc.submit("k", lambda: 42, foreground=False)
        fg = svc.submit("k", lambda: 43)  # promote, not a second build
        assert fg is spec
        gate.set()
        assert fg.result(10.0) == 42
        assert svc.stats.promoted == 1
        assert svc.stats.submitted == 2  # blocker + k
        svc.shutdown()

    def test_failed_build_allows_retry(self):
        # legacy semantics: no retry, no quarantine — the key is simply
        # forgotten on failure so a resubmit builds again
        svc = CompileService(workers=1, max_retries=0,
                             poison_failures=False)

        def boom():
            raise RuntimeError("transient")

        with pytest.raises(RuntimeError):
            svc.submit("k", boom).result(10.0)
        assert svc.submit("k", lambda: "ok").result(10.0) == "ok"
        assert svc.stats.failed == 1
        assert svc.stats.retries == 0
        assert svc.stats.completed >= 1
        svc.shutdown()

    def test_transient_failure_retried_with_backoff(self):
        svc = CompileService(workers=1, max_retries=2,
                             retry_backoff_s=0.005)
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise RuntimeError("transient")
            return "recovered"

        assert svc.submit("k", flaky).result(10.0) == "recovered"
        assert len(calls) == 3
        assert svc.stats.retries == 2
        assert svc.stats.failed == 0
        assert svc.stats.completed == 1
        svc.shutdown()

    def test_deterministic_failure_poisons_key(self):
        svc = CompileService(workers=1, max_retries=1,
                             retry_backoff_s=0.002)
        calls = []

        def boom():
            calls.append(1)
            raise RuntimeError("deterministic")

        with pytest.raises(RuntimeError, match="deterministic"):
            svc.submit("k", boom).result(10.0)
        assert len(calls) == 2  # first attempt + 1 retry
        assert svc.stats.failed == 1
        assert svc.poisoned_keys() == ["k"]
        # resubmits fail fast from the quarantine — no rebuild hot-loop
        with pytest.raises(RuntimeError, match="deterministic"):
            svc.submit("k", boom).result(10.0)
        assert len(calls) == 2
        assert svc.stats.poison_hits == 1
        # clearing the quarantine lets a fixed build through
        assert svc.clear_poisoned("k") == 1
        assert svc.submit("k", lambda: "fixed").result(10.0) == "fixed"
        svc.shutdown()

    def test_dead_worker_respawned_and_job_rescued(self):
        from repro.runtime import chaos

        svc = CompileService(workers=1, max_retries=0)
        prev = chaos.install_plan(
            chaos.FaultPlan(seed=3).arm(chaos.SITE_COMPILE_WORKER,
                                        times=(0,))
        )
        try:
            # the worker thread dies AFTER claiming this job; without
            # the reaper the future would be stranded forever
            fut = svc.submit("k", lambda: "survived")
            assert svc.result(fut, timeout=10.0) == "survived"
            assert svc.stats.worker_restarts >= 1
            assert svc.stats.requeued == 1
        finally:
            chaos.install_plan(prev)
            svc.shutdown()

    def test_hung_build_abandoned(self):
        svc = CompileService(workers=1, max_retries=0,
                             hang_timeout_s=0.05)
        gate = threading.Event()
        fut = svc.submit("hung", lambda: gate.wait(10.0))
        from repro.runtime.chaos import SystemError_
        with pytest.raises(SystemError_, match="hang timeout"):
            svc.result(fut, timeout=10.0)
        assert svc.stats.hangs_abandoned == 1
        assert svc.stats.worker_restarts >= 1
        # the replacement worker keeps serving new jobs
        assert svc.submit("next", lambda: "ok").result(10.0) == "ok"
        gate.set()
        svc.shutdown()

    def test_shutdown_cancels_queued(self):
        svc = CompileService(workers=1)
        gate = threading.Event()
        svc.submit("blocker", lambda: gate.wait(5.0))
        time.sleep(0.05)
        queued = svc.submit("never", lambda: 1)
        gate.set()
        svc.shutdown(wait=True)
        assert queued.cancelled() or queued.done()


# --------------------------------------------------------------------------
# BucketedModule async dispatch
# --------------------------------------------------------------------------


class TestAsyncDispatch:
    def test_thundering_herd_compiles_once(self):
        """Eight threads hitting the same cold bucket (nothing warm to
        fall back to) all block on ONE build — compiles == 1."""
        svc = CompileService(workers=2)
        mod = forge_compile_bucketed(
            _fn, in_axes=0, policy="pow2",
            async_compile=True, service=svc,
        )
        x = _x(5)
        outs, errs = [None] * 8, []

        def call(i):
            try:
                outs[i] = np.asarray(mod(x))
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
        assert not errs
        assert mod.stats.compiles == 1
        assert svc.stats.submitted == 1
        for o in outs[1:]:
            np.testing.assert_array_equal(outs[0], o)
        svc.shutdown()

    def test_fallback_bitwise_then_exact_switch(self):
        """Acceptance: a cold-bucket dispatch with a warm dominating
        bucket never blocks — it pads up and is bitwise-equal to the
        warm program's own output on the same padded inputs; once the
        background build lands, the exact program takes over and is
        token-exact vs a cold blocking (sync) run."""
        svc = CompileService(workers=1)
        # example args warm the B8 bucket eagerly (sync, like warmup)
        mod = forge_compile_bucketed(
            _fn, np.ones((8, 4), np.float32), in_axes=0, policy="pow2",
            async_compile=True, service=svc,
        )
        assert mod.has_program(mod.key_for_extents(8))
        wait0 = mod.stats.compile_wait_s  # eager warmup stall (sync)
        x = _x(3)
        y_fb = np.asarray(mod(x))  # exact B4 is cold -> warm B8 fallback
        assert mod.stats.fallback_calls == 1
        assert mod.stats.fallback_cells_padded == 8 - 4
        assert mod.stats.compile_wait_s == wait0  # never blocked
        # bitwise vs the warm program's solo output on the padded batch
        xp = np.pad(x, ((0, 5), (0, 0)), mode="edge")
        y_warm = np.asarray(mod(xp))
        np.testing.assert_array_equal(y_fb, y_warm[:3])
        # the background build lands -> the exact bucket takes over
        assert svc.wait_idle(30.0)
        assert mod.has_program(mod.key_for_extents(4))
        y_exact = np.asarray(mod(x))
        assert mod.stats.fallback_calls == 1  # no new fallback
        assert mod.stats.compile_background_s > 0.0
        # token-exact vs a cold sync module that blocked on B4
        sync = forge_compile_bucketed(_fn, in_axes=0, policy="pow2")
        np.testing.assert_array_equal(y_exact, np.asarray(sync(x)))
        svc.shutdown()

    def test_first_dispatch_blocks_without_warm(self):
        """With nothing warm the very first dispatch must block (and
        the stall is accounted as request-visible wait)."""
        svc = CompileService(workers=1)
        mod = forge_compile_bucketed(
            _fn, in_axes=0, policy="pow2",
            async_compile=True, service=svc,
        )
        y = np.asarray(mod(_x(3)))
        assert mod.stats.compiles == 1
        assert mod.stats.compile_wait_s > 0.0
        assert mod.stats.fallback_calls == 0
        sync = forge_compile_bucketed(_fn, in_axes=0, policy="pow2")
        np.testing.assert_array_equal(y, np.asarray(sync(_x(3))))
        svc.shutdown()


# --------------------------------------------------------------------------
# persistent disk tier
# --------------------------------------------------------------------------


def _compile_once(cache, backend="segment_jit"):
    comp = ForgeCompiler(PipelineConfig(backend=backend), cache=cache)
    return comp.compile(_fn, np.ones((4, 4), np.float32))


class TestDiskCache:
    def test_restart_replays_with_zero_builds(self, tmp_path):
        store = DiskCacheStore(str(tmp_path))
        c1 = CompileCache(store=store)
        m1 = _compile_once(c1)
        assert c1.stats.misses == 1
        assert store.stats.writes == 1
        assert len(store) == 1
        # simulated restart: fresh memory cache over the same directory
        c2 = CompileCache(store=DiskCacheStore(str(tmp_path)))
        m2 = _compile_once(c2)
        assert c2.stats.misses == 0
        assert c2.stats.disk_hits == 1
        assert m2.result.cache_disk_hit
        x = _x(4)
        np.testing.assert_array_equal(np.asarray(m1(x)), np.asarray(m2(x)))

    def test_interpret_backend_roundtrip(self, tmp_path):
        c1 = CompileCache(store=DiskCacheStore(str(tmp_path)))
        m1 = _compile_once(c1, backend="interpret")
        c2 = CompileCache(store=DiskCacheStore(str(tmp_path)))
        m2 = _compile_once(c2, backend="interpret")
        assert c2.stats.disk_hits == 1 and c2.stats.misses == 0
        x = _x(4)
        np.testing.assert_array_equal(np.asarray(m1(x)), np.asarray(m2(x)))

    def _entry_files(self, root):
        return [os.path.join(r, f) for r, _d, fs in os.walk(root)
                for f in fs if f.endswith(".forgec")]

    def test_corrupt_entry_detected_and_recompiled(self, tmp_path):
        c1 = CompileCache(store=DiskCacheStore(str(tmp_path)))
        _compile_once(c1)
        files = self._entry_files(tmp_path)
        assert files
        for p in files:  # truncate: checksum must catch it
            blob = open(p, "rb").read()
            open(p, "wb").write(blob[: len(blob) // 2])
        store2 = DiskCacheStore(str(tmp_path))
        c2 = CompileCache(store=store2)
        m2 = _compile_once(c2)
        assert store2.stats.corrupt == 1
        assert c2.stats.misses == 1  # recompiled, not crashed
        assert store2.stats.writes == 1  # entry healed on disk
        x = _x(4)
        sync = _compile_once(CompileCache())
        np.testing.assert_array_equal(np.asarray(m2(x)),
                                      np.asarray(sync(x)))

    def test_garbage_entry_detected(self, tmp_path):
        c1 = CompileCache(store=DiskCacheStore(str(tmp_path)))
        _compile_once(c1)
        for p in self._entry_files(tmp_path):
            open(p, "wb").write(os.urandom(256))
        store2 = DiskCacheStore(str(tmp_path))
        c2 = CompileCache(store=store2)
        _compile_once(c2)
        assert store2.stats.corrupt == 1
        assert c2.stats.misses == 1
        # the corrupt file was unlinked and rewritten
        assert len(store2) == 1

    def test_salt_invalidates_by_address(self, tmp_path):
        a = DiskCacheStore(str(tmp_path), salt="jax=1")
        assert a.store_entry("k", {"v": 1})
        b = DiskCacheStore(str(tmp_path), salt="jax=2")
        assert b.load_entry("k") is None  # different address, clean miss
        assert b.stats.misses == 1
        assert a.load_entry("k") == {"v": 1}

    def test_foreign_file_key_mismatch(self, tmp_path):
        """A store re-rooted onto foreign files (or a path collision)
        must miss, not deserialize the wrong program."""
        s = DiskCacheStore(str(tmp_path))
        s.store_entry("k1", {"v": 1})
        import shutil

        p2 = s.path_for("k2")
        os.makedirs(os.path.dirname(p2), exist_ok=True)
        shutil.copy(s.path_for("k1"), p2)
        assert s.load_entry("k2") is None
        assert s.stats.corrupt == 1
        assert not os.path.exists(p2)  # poisoned file unlinked


# --------------------------------------------------------------------------
# eviction coherence
# --------------------------------------------------------------------------


class TestEvictionCoherence:
    def test_evict_cold_drops_cache_entry(self, tmp_path):
        store = DiskCacheStore(str(tmp_path))
        cache = CompileCache(store=store)
        comp = ForgeCompiler(PipelineConfig(backend="segment_jit"),
                             cache=cache)
        mod = comp.compile_bucketed(_fn, in_axes=0, policy="pow2")
        for b in (2, 4, 8):
            mod(_x(b))
        assert len(cache) == 3
        n_disk = len(store)
        victims = mod.evict_cold(1)
        assert len(victims) == 2
        # coherence: the memory tier dropped the retired programs...
        assert cache.stats.coherence_drops == 2
        assert len(cache) == 1
        # ...but the disk tier keeps them (it IS the cold tier)
        assert len(store) == n_disk
        # a re-dispatch of an evicted bucket replays from disk
        y = np.asarray(mod(_x(2)))
        assert cache.stats.disk_hits == 1
        sync = forge_compile_bucketed(_fn, in_axes=0, policy="pow2")
        np.testing.assert_array_equal(y, np.asarray(sync(_x(2))))

    def test_evict_without_store_only_counts(self):
        cache = CompileCache()
        comp = ForgeCompiler(PipelineConfig(backend="segment_jit"),
                             cache=cache)
        mod = comp.compile_bucketed(_fn, in_axes=0, policy="pow2")
        mod(_x(2))
        mod(_x(4))
        mod.evict_cold(1)
        assert cache.stats.coherence_drops == 1
        assert len(cache) == 1


# --------------------------------------------------------------------------
# serve-level acceptance
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_setup():
    from repro.configs import get_config
    from repro.models import get_model

    cfg = get_config("forge-125m", smoke=True)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


class TestServeAsync:
    def test_warm_fallback_never_blocks_and_switches(self, smoke_setup):
        """Acceptance: with --async-compile a dispatch discovering a
        cold bucket never blocks when a dominating warm bucket exists;
        the fallback generation is token-exact vs the warm-padded sync
        server, and the post-switch generation is token-exact vs a
        cold blocking run."""
        from repro.launch.serve import BatchedServer

        cfg, params = smoke_setup
        rng = np.random.default_rng(0)
        prompts = rng.integers(0, cfg.vocab, (3, 8)).astype(np.int32)
        srv = BatchedServer(cfg, params, max_len=64, mode="forge",
                            async_compile=True)
        try:
            srv.warmup([8], prompt_lens=[8])  # ONLY the B8 rung is warm
            bs = srv.bucketed.stats
            r1 = srv.generate(prompts, 4)  # exact rung B4 is cold
            assert bs.fallback_calls >= 1
            assert bs.compile_wait_s == 0.0  # the tick never stalled
            sync = BatchedServer(cfg, params, max_len=64, mode="forge")
            sync.warmup([8], prompt_lens=[8])
            np.testing.assert_array_equal(
                r1["tokens"], sync.generate(prompts, 4)["tokens"]
            )
            # background build lands -> exact bucket takes over
            assert srv.compile_service.wait_idle(60.0)
            assert srv.bucketed.has_program(
                srv.bucketed.key_for_extents(4)
            )
            r2 = srv.generate(prompts, 4)
            cold = BatchedServer(cfg, params, max_len=64, mode="forge")
            np.testing.assert_array_equal(
                r2["tokens"], cold.generate(prompts, 4)["tokens"]
            )
        finally:
            srv.compile_service.shutdown()

    def test_scheduler_async_token_parity(self, smoke_setup):
        """SlotScheduler without warmup: cold rungs discovered mid-
        serve fall back to warm rungs (warm_fallbacks > 0) and the
        emitted tokens match the sync scheduler exactly."""
        from repro.launch.serve import BatchedServer, Request, SlotScheduler

        cfg, params = smoke_setup

        def reqs():
            rng = np.random.default_rng(1)
            return [
                Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab, (6,)).astype(
                            np.int32),
                        max_new=4, arrival=i // 4)
                for i in range(10)
            ]

        srv = BatchedServer(cfg, params, max_len=64, mode="forge",
                            async_compile=True)
        try:
            sched = SlotScheduler(srv, max_slots=8)
            res = sched.run(reqs())
            assert res["warm_fallbacks"] > 0
            srv2 = BatchedServer(cfg, params, max_len=64, mode="forge")
            res2 = SlotScheduler(srv2, max_slots=8).run(reqs())
            a = {r: v["tokens"].tolist() for r, v in res["results"].items()}
            b = {r: v["tokens"].tolist() for r, v in res2["results"].items()}
            assert a == b
        finally:
            srv.compile_service.shutdown()

    def test_restart_replay_zero_builds(self, smoke_setup, tmp_path):
        """Acceptance: a server restart against a populated --cache-dir
        replays the warmed ladder from disk with zero full builds."""
        from repro.launch.serve import BatchedServer

        cfg, params = smoke_setup
        import repro.models._forge as forge_glue

        g = get_compile_cache()
        # earlier tests memoized the inner per-block bodies; reset so
        # run 1 actually compiles (and persists) the whole ladder
        forge_glue.clear_cache()
        g.clear()
        srv1 = BatchedServer(cfg, params, max_len=64, mode="forge",
                             cache_dir=str(tmp_path))
        srv1.warmup([2], prompt_lens=[8])
        assert srv1.compile_cache.stats.misses > 0
        assert srv1.compile_cache.store.stats.writes > 0
        # simulated restart: fresh per-server cache, fresh global-cache
        # state, fresh per-block body memo — only the directory survives
        forge_glue.clear_cache()
        g.clear()
        g.store = None
        srv2 = BatchedServer(cfg, params, max_len=64, mode="forge",
                             cache_dir=str(tmp_path))
        srv2.warmup([2], prompt_lens=[8])
        assert srv2.compile_cache.stats.misses == 0
        assert srv2.compile_cache.stats.disk_hits > 0
        assert g.stats.misses == 0  # inner forge bodies replayed too
        rng = np.random.default_rng(0)
        prompts = rng.integers(0, cfg.vocab, (2, 8)).astype(np.int32)
        t1 = srv1.generate(prompts, 4)["tokens"]
        t2 = srv2.generate(prompts, 4)["tokens"]
        np.testing.assert_array_equal(t1, t2)


class TestJaxCachePlacement:
    """JAX's persistent compilation cache sits where
    ``JAX_COMPILATION_CACHE_DIR`` says, else at one fixed directory in
    the checkout; the Forge ``cache_dir`` store never moves it."""

    @pytest.fixture(autouse=True)
    def _restore_jax_cache_dir(self):
        from jax.experimental.compilation_cache import compilation_cache

        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()

    def test_env_var_decides(self, smoke_setup, tmp_path, monkeypatch):
        from repro.launch.serve import BatchedServer

        cfg, params = smoke_setup
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
        BatchedServer(cfg, params, max_len=64, mode="forge")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "xla")
        assert os.path.isdir(tmp_path / "xla")

    def test_unset_uses_fixed_checkout_path(self, smoke_setup, monkeypatch):
        from repro.launch import serve

        cfg, params = smoke_setup
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        serve.BatchedServer(cfg, params, max_len=64, mode="forge")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert serve.DEFAULT_JAX_CACHE_DIR == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == serve.DEFAULT_JAX_CACHE_DIR

    def test_forge_cache_dir_leaves_jax_cache_alone(
        self, smoke_setup, tmp_path, monkeypatch
    ):
        from repro.launch.serve import BatchedServer

        cfg, params = smoke_setup
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
        BatchedServer(cfg, params, max_len=64, mode="forge",
                      cache_dir=str(tmp_path / "forge"))
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "xla")
        assert not os.path.exists(tmp_path / "forge" / "xla")
