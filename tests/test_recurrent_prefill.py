"""Chunked state-scan prefill for the recurrent families (ISSUE 10):
the associative-scan reformulation of RG-LRU and mLSTM, the Pallas
chunked-scan kernel vs its oracle, chunk-boundary carry chaining, and
chunked ≡ sequential fidelity on the serve paths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.metrics import check_prefill_fidelity
from repro.kernels import ops
from repro.kernels.ref import rg_lru_chunk_ref, rg_lru_ref
from repro.kernels.rg_lru import rg_lru_chunked
from repro.launch.serve import BatchedServer, Request, SlotScheduler
from repro.launch.steps import (
    make_batched_prefill_step,
    make_slot_prefill_step,
    supports_batched_prefill,
)
from repro.models import get_model
from repro.models.xlstm import mlstm_chunk_combine

from _hyp import HAVE_HYPOTHESIS, given, settings, st  # optional dep


def _f32(cfg):
    return cfg.with_(dtype="float32")


@pytest.fixture(scope="module")
def rglru_setup():
    cfg = _f32(get_config("recurrentgemma-2b", smoke=True))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    return cfg, model, params


@pytest.fixture(scope="module")
def xlstm_setup():
    cfg = _f32(get_config("xlstm-350m", smoke=True))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    return cfg, model, params


def _prompts(batch, n, seed=0, vocab=512):
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, (batch, n)).astype(np.int32)


def _xa(shape, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape).astype(np.float32)
    a = rng.uniform(0.3, 0.999, shape).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(a)


def _tree_allclose(got, want, rtol=1e-5, atol=1e-5, path=""):
    if isinstance(want, dict):
        assert set(got) == set(want), path
        for k in want:
            _tree_allclose(got[k], want[k], rtol, atol, f"{path}/{k}")
    elif isinstance(want, (list, tuple)):
        assert len(got) == len(want), path
        for i, (g, w) in enumerate(zip(got, want)):
            _tree_allclose(g, w, rtol, atol, f"{path}[{i}]")
    else:
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=rtol, atol=atol, err_msg=path,
        )


def _tree_bitwise_rows(got, want, rows, path=""):
    """Selected batch rows must survive bitwise (NaN == NaN)."""
    if isinstance(want, dict):
        for k in want:
            _tree_bitwise_rows(got[k], want[k], rows, f"{path}/{k}")
    elif isinstance(want, (list, tuple)):
        for i, (g, w) in enumerate(zip(got, want)):
            _tree_bitwise_rows(g, w, rows, f"{path}[{i}]")
    else:
        for r in rows:
            assert np.array_equal(
                np.asarray(got)[r], np.asarray(want)[r], equal_nan=True
            ), f"{path} row {r} not bitwise-inert"


# --------------------------------------------------------------------------
# Pallas chunked-scan kernel vs the associative_scan oracle
# --------------------------------------------------------------------------


class TestChunkedKernelVsOracle:
    @pytest.mark.parametrize("shape", [(2, 16, 8), (1, 7, 5), (3, 24, 16),
                                       (2, 33, 12)])
    def test_interpret_matches_oracle(self, shape):
        """Acceptance: the Pallas chunked kernel (interpret=True on the
        CPU container) reproduces the pure-associative_scan oracle —
        both the per-step sequence and the h[:, -1] carry output."""
        x, a = _xa(shape, seed=shape[1])
        h0 = jnp.asarray(
            np.random.default_rng(99).standard_normal(
                (shape[0], shape[2])).astype(np.float32))
        h_ref, last_ref = rg_lru_chunk_ref(x, a, h0)
        h, last = rg_lru_chunked(x, a, h0, interpret=True)
        np.testing.assert_allclose(h, h_ref, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(last, last_ref, rtol=1e-5, atol=1e-5)

    def test_block_t_not_dividing_t(self):
        """The carry fold across Pallas T-blocks must be exact even when
        block_t does not divide T (the kernel shrinks the block)."""
        x, a = _xa((2, 13, 8), seed=7)
        h_ref, last_ref = rg_lru_chunk_ref(x, a)
        h, last = rg_lru_chunked(x, a, block_t=8, interpret=True)
        np.testing.assert_allclose(h, h_ref, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(last, last_ref, rtol=1e-5, atol=1e-5)

    def test_ops_dispatch(self):
        """ops.rg_lru_scan routes xla → oracle, interpret → kernel, and
        both return the (h, h_last) pair."""
        x, a = _xa((1, 9, 4), seed=3)
        h_x, last_x = ops.rg_lru_scan(x, a, impl="xla")
        h_i, last_i = ops.rg_lru_scan(x, a, impl="interpret")
        np.testing.assert_allclose(h_i, h_x, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(last_i, last_x, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(last_x, h_x[:, -1], rtol=0, atol=0)

    def test_grad_matches_reference(self):
        """custom_vjp: grads through BOTH outputs match the oracle's."""
        x, a = _xa((2, 11, 6), seed=5)
        h0 = jnp.asarray(np.random.default_rng(6).standard_normal(
            (2, 6)).astype(np.float32))

        def loss_k(x, a, h0):
            h, last = rg_lru_chunked(x, a, h0, interpret=True)
            return jnp.sum(h * h) + jnp.sum(last)

        def loss_r(x, a, h0):
            h, last = rg_lru_chunk_ref(x, a, h0)
            return jnp.sum(h * h) + jnp.sum(last)

        gk = jax.grad(loss_k, argnums=(0, 1, 2))(x, a, h0)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(x, a, h0)
        for g1, g2 in zip(gk, gr):
            np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------
# chunk-boundary carry: chaining chunks == one unchunked scan
# --------------------------------------------------------------------------


class TestChunkBoundaryCarry:
    @pytest.mark.parametrize("chunk", [4, 5, 16])  # 4 | 16; 5 ∤ 16; whole
    def test_chained_chunks_match_unchunked(self, chunk):
        """Folding h_last into the next chunk's h0 reproduces the
        single-scan result for chunk sizes dividing and not dividing S."""
        x, a = _xa((2, 16, 8), seed=chunk)
        want = rg_lru_ref(x, a)
        h0 = None
        got = []
        for s in range(0, 16, chunk):
            h, h0 = ops.rg_lru_scan(
                x[:, s:s + chunk], a[:, s:s + chunk], h0, impl="interpret"
            )
            got.append(h)
        np.testing.assert_allclose(
            jnp.concatenate(got, axis=1), want, rtol=1e-5, atol=1e-5
        )

    def test_mlstm_chunk_scan_chained(self):
        """mlstm_chunk_scan carried across a chunk boundary equals the
        token-by-token recurrent decode."""
        from repro.models.xlstm import mlstm_chunk_scan, mlstm_recurrent_step

        B, H, S, D = 2, 3, 11, 4
        rng = np.random.default_rng(11)
        q, k, v = (jnp.asarray(rng.standard_normal(
            (B, H, S, D)).astype(np.float32)) for _ in range(3))
        i_pre = jnp.asarray(rng.standard_normal((B, H, S)).astype(np.float32))
        f_pre = jnp.asarray(
            rng.standard_normal((B, H, S)).astype(np.float32) + 3.0)
        state = {
            "C": jnp.zeros((B, H, D, D), jnp.float32),
            "n": jnp.zeros((B, H, D), jnp.float32),
            "m": jnp.zeros((B, H), jnp.float32) - 1e30,
        }
        # sequential reference
        st = state
        hs = []
        for t in range(S):
            h, st = mlstm_recurrent_step(
                q[:, :, t], k[:, :, t], v[:, :, t],
                i_pre[:, :, t], f_pre[:, :, t], st,
            )
            hs.append(h)
        want = jnp.stack(hs, axis=2)
        # chunked: 11 = 4 + 7 (boundary not at a power of two)
        st2 = state
        got = []
        for s, e in ((0, 4), (4, 11)):
            L = jnp.full((B,), e - s, jnp.int32)
            h, st2 = mlstm_chunk_scan(
                q[:, :, s:e], k[:, :, s:e], v[:, :, s:e],
                i_pre[:, :, s:e], f_pre[:, :, s:e], st2, L,
            )
            got.append(h)
        np.testing.assert_allclose(
            jnp.concatenate(got, axis=2), want, rtol=1e-5, atol=1e-5
        )
        _tree_allclose(st2, st)


# --------------------------------------------------------------------------
# associativity property (hypothesis when installed; a fixed-seed sweep
# keeps the invariant asserted — with no skip — when it is absent)
# --------------------------------------------------------------------------


def _check_rg_lru_assoc(seed):
    """(a1,x1)∘(a2,x2) = (a1·a2, a2·x1+x2) must associate — the
    precondition for lax.associative_scan to be a valid evaluation
    order for the affine recurrence."""
    rng = np.random.default_rng(seed)
    a = rng.uniform(0.2, 0.999, (3, 5)).astype(np.float64)
    x = rng.standard_normal((3, 5)).astype(np.float64)

    def comb(e1, e2):
        return e1[0] * e2[0], e2[0] * e1[1] + e2[1]

    e = [(a[i], x[i]) for i in range(3)]
    lhs = comb(comb(e[0], e[1]), e[2])
    rhs = comb(e[0], comb(e[1], e[2]))
    np.testing.assert_allclose(lhs[0], rhs[0], rtol=1e-12)
    np.testing.assert_allclose(lhs[1], rhs[1], rtol=1e-12, atol=1e-12)


def _check_mlstm_assoc(seed):
    """The stabilized (F, M, Ĉ, n̂) combine must associate — max/+
    distribute, so grouping cannot change the folded cell."""
    rng = np.random.default_rng(seed)

    def elem(i):  # noqa: ARG001 — rng advances per element
        F = jnp.asarray(-np.abs(rng.standard_normal((2,))))
        M = jnp.asarray(rng.standard_normal((2,)) * 3)
        C = jnp.asarray(rng.standard_normal((2, 3, 3)))
        n = jnp.asarray(rng.standard_normal((2, 3)))
        return F, M, C, n

    e0, e1, e2 = elem(0), elem(1), elem(2)
    lhs = mlstm_chunk_combine(mlstm_chunk_combine(e0, e1), e2)
    rhs = mlstm_chunk_combine(e0, mlstm_chunk_combine(e1, e2))
    for g1, g2 in zip(lhs, rhs):
        np.testing.assert_allclose(
            np.asarray(g1), np.asarray(g2), rtol=1e-5, atol=1e-6
        )


class TestCombineAssociativity:
    def test_rg_lru_combine_associative(self):
        for seed in range(20):
            _check_rg_lru_assoc(seed)

    def test_mlstm_combine_associative(self):
        for seed in range(20):
            _check_mlstm_assoc(seed)

    if HAVE_HYPOTHESIS:
        @given(st.integers(min_value=0, max_value=10_000))
        @settings(max_examples=25, deadline=None)
        def test_rg_lru_combine_associative_prop(self, seed):
            _check_rg_lru_assoc(seed)

        @given(st.integers(min_value=0, max_value=10_000))
        @settings(max_examples=25, deadline=None)
        def test_mlstm_combine_associative_prop(self, seed):
            _check_mlstm_assoc(seed)


# --------------------------------------------------------------------------
# model-level: chunked prefill ≡ sequential decode (both families)
# --------------------------------------------------------------------------


def _sequential_reference(model, cfg, params, prompts, max_len=32, pos0=0):
    B, P = prompts.shape
    cache = model.init_cache(cfg, B, max_len)
    logits = []
    for t in range(P):
        lg, cache = model.decode_step(
            params, cache, jnp.asarray(prompts[:, t:t + 1], jnp.int32),
            jnp.full((B,), pos0 + t, jnp.int32), cfg,
        )
        logits.append(lg[:, -1, :])
    return jnp.stack(logits, axis=1), cache


class TestRglruChunkedPrefill:
    def test_matches_sequential(self, rglru_setup):
        """Acceptance: one chunked prefill pass == P decode steps —
        logits and every cache leaf (h, conv, rotating window KV)."""
        cfg, model, params = rglru_setup
        rep = check_prefill_fidelity(
            cfg, params, _prompts(2, 13, seed=1, vocab=cfg.vocab),
            max_len=32,
        )
        assert rep.max_abs_diff <= 1e-5, rep.max_abs_diff

    def test_nonzero_position_past_window(self, rglru_setup):
        """A second prompt segment prefilled at pos > 0, long enough
        that the rotating window wraps (P > window): the continuation
        must match decoding the segment token-by-token."""
        cfg, model, params = rglru_setup
        assert cfg.window and cfg.window < 13
        p1 = _prompts(2, 6, seed=2, vocab=cfg.vocab)
        p2 = _prompts(2, 13, seed=3, vocab=cfg.vocab)
        B = 2
        # sequential over both segments
        cache_s = model.init_cache(cfg, B, 64)
        for t in range(6):
            _, cache_s = model.decode_step(
                params, cache_s, jnp.asarray(p1[:, t:t + 1], jnp.int32),
                jnp.full((B,), t, jnp.int32), cfg)
        logits_seq = []
        for t in range(13):
            lg, cache_s = model.decode_step(
                params, cache_s, jnp.asarray(p2[:, t:t + 1], jnp.int32),
                jnp.full((B,), 6 + t, jnp.int32), cfg)
            logits_seq.append(lg[:, -1, :])
        # chunked: segment 1 chunked at pos 0, segment 2 chunked at pos 6
        cache_c = model.init_cache(cfg, B, 64)
        _, cache_c = model.prefill_step(
            params, cache_c, jnp.asarray(p1, jnp.int32),
            jnp.zeros((B,), jnp.int32), cfg)
        logits_c, cache_c = model.prefill_step(
            params, cache_c, jnp.asarray(p2, jnp.int32),
            jnp.full((B,), 6, jnp.int32), cfg)
        np.testing.assert_allclose(
            logits_c, jnp.stack(logits_seq, 1), rtol=1e-5, atol=1e-5)
        _tree_allclose(cache_c, cache_s)

    def test_ragged_lengths(self, rglru_setup):
        """Per-row length: each row's carried state must equal its OWN
        length-step sequential state, not the padded chunk width's."""
        cfg, model, params = rglru_setup
        prompts = _prompts(2, 13, seed=4, vocab=cfg.vocab)
        _, cache5 = _sequential_reference(
            model, cfg, params, prompts[:, :5], max_len=32)
        _, cache13 = _sequential_reference(
            model, cfg, params, prompts, max_len=32)
        cache = model.init_cache(cfg, 2, 32)
        _, cache = model.prefill_step(
            params, cache, jnp.asarray(prompts, jnp.int32),
            jnp.zeros((2,), jnp.int32), cfg,
            length=jnp.asarray([5, 13], jnp.int32))
        got0 = jax.tree_util.tree_map(lambda x: np.asarray(x)[0], cache)
        want0 = jax.tree_util.tree_map(lambda x: np.asarray(x)[0], cache5)
        _tree_allclose(got0, want0)
        got1 = jax.tree_util.tree_map(lambda x: np.asarray(x)[1], cache)
        want1 = jax.tree_util.tree_map(lambda x: np.asarray(x)[1], cache13)
        _tree_allclose(got1, want1)

    def test_masked_slots_nan_inert(self, rglru_setup):
        """A slot-masked row's state survives bitwise — even when it
        holds NaN — and its garbage never reaches active rows."""
        cfg, model, params = rglru_setup
        prompts = _prompts(2, 9, seed=5, vocab=cfg.vocab)
        logits_seq, _ = _sequential_reference(
            model, cfg, params, prompts, max_len=32)
        cache = jax.tree_util.tree_map(
            lambda x: (x.at[0].set(jnp.nan)
                       if jnp.issubdtype(x.dtype, jnp.floating) else x),
            model.init_cache(cfg, 2, 32))
        logits, new_cache = model.prefill_step(
            params, cache, jnp.asarray(prompts, jnp.int32),
            jnp.zeros((2,), jnp.int32), cfg,
            slot_mask=jnp.asarray([False, True]))
        _tree_bitwise_rows(new_cache, cache, rows=[0])
        assert bool(jnp.all(jnp.isfinite(logits[1])))
        np.testing.assert_allclose(
            logits[1], logits_seq[1], rtol=1e-5, atol=1e-5)


class TestXlstmChunkedPrefill:
    def test_matches_sequential(self, xlstm_setup):
        """Acceptance: chunked mLSTM scan + in-program sLSTM scan == P
        decode steps (logits ≤ 1e-5; states allclose — the reordered
        f32 reduction shifts the last bit of deep-layer normalizers)."""
        cfg, model, params = xlstm_setup
        prompts = _prompts(2, 13, seed=6, vocab=cfg.vocab)
        logits_seq, cache_seq = _sequential_reference(
            model, cfg, params, prompts, max_len=32)
        cache = model.init_cache(cfg, 2, 32)
        logits, cache = model.prefill_step(
            params, cache, jnp.asarray(prompts, jnp.int32),
            jnp.zeros((2,), jnp.int32), cfg)
        np.testing.assert_allclose(
            logits, logits_seq, rtol=1e-5, atol=1e-5)
        _tree_allclose(cache, cache_seq)

    def test_ragged_lengths(self, xlstm_setup):
        cfg, model, params = xlstm_setup
        prompts = _prompts(2, 11, seed=7, vocab=cfg.vocab)
        _, cache4 = _sequential_reference(
            model, cfg, params, prompts[:, :4], max_len=32)
        _, cache11 = _sequential_reference(
            model, cfg, params, prompts, max_len=32)
        cache = model.init_cache(cfg, 2, 32)
        _, cache = model.prefill_step(
            params, cache, jnp.asarray(prompts, jnp.int32),
            jnp.zeros((2,), jnp.int32), cfg,
            length=jnp.asarray([4, 11], jnp.int32))
        got0 = jax.tree_util.tree_map(lambda x: np.asarray(x)[0], cache)
        want0 = jax.tree_util.tree_map(lambda x: np.asarray(x)[0], cache4)
        _tree_allclose(got0, want0)
        got1 = jax.tree_util.tree_map(lambda x: np.asarray(x)[1], cache)
        want1 = jax.tree_util.tree_map(lambda x: np.asarray(x)[1], cache11)
        _tree_allclose(got1, want1)

    def test_masked_slots_nan_inert(self, xlstm_setup):
        cfg, model, params = xlstm_setup
        prompts = _prompts(2, 9, seed=8, vocab=cfg.vocab)
        logits_seq, _ = _sequential_reference(
            model, cfg, params, prompts, max_len=32)
        cache = jax.tree_util.tree_map(
            lambda x: (x.at[0].set(jnp.nan)
                       if jnp.issubdtype(x.dtype, jnp.floating) else x),
            model.init_cache(cfg, 2, 32))
        logits, new_cache = model.prefill_step(
            params, cache, jnp.asarray(prompts, jnp.int32),
            jnp.zeros((2,), jnp.int32), cfg,
            slot_mask=jnp.asarray([False, True]))
        _tree_bitwise_rows(new_cache, cache, rows=[0])
        assert bool(jnp.all(jnp.isfinite(logits[1])))
        np.testing.assert_allclose(
            logits[1], logits_seq[1], rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# serve integration: the unified predicate + both fronts
# --------------------------------------------------------------------------


class TestServeIntegration:
    def test_supports_batched_prefill_predicate(self, rglru_setup,
                                                xlstm_setup):
        """The single serve-front predicate admits the recurrent families
        and MoE (and the step builders follow it)."""
        for cfg, model, _ in (rglru_setup, xlstm_setup):
            assert supports_batched_prefill(cfg)
            assert model.prefill_takes_length
            assert make_batched_prefill_step(cfg) is not None
            assert make_slot_prefill_step(cfg) is not None
        # MoE routing is dropless and per token: it joins the same grid
        moe = get_config("phi3.5-moe-42b-a6.6b", smoke=True)
        assert supports_batched_prefill(moe)
        assert make_slot_prefill_step(moe) is not None

    @pytest.mark.parametrize("name", ["recurrentgemma-2b", "xlstm-350m"])
    def test_generate_chunked_matches_sequential(self, name):
        """BatchedServer end-to-end: the chunked grid prefill emits the
        same greedy tokens as the forced sequential fill, and reports
        last_prefill_mode == 'chunked'."""
        cfg = get_config(name, smoke=True)
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(1), cfg)
        prompts = _prompts(2, 13, seed=9, vocab=cfg.vocab)
        srv = BatchedServer(cfg, params, max_len=64, mode="forge",
                            backend="interpret")
        res = srv.generate(prompts, 5)
        assert res["prefill_mode"] == "chunked"
        srv_seq = BatchedServer(cfg, params, max_len=64, mode="forge",
                                backend="interpret", prefill="sequential")
        res_seq = srv_seq.generate(prompts, 5)
        assert res_seq["prefill_mode"] == "sequential"
        np.testing.assert_array_equal(res["tokens"], res_seq["tokens"])

    def test_scheduler_swap_in_through_chunked_grid(self):
        """SlotScheduler on a recurrent family now admits through the
        slot-masked chunked prefill (prefill_dispatches > 0) with exact
        token fidelity — the in-loop masked-fill replay is retired to
        the ``--prefill sequential`` fallback."""
        cfg = get_config("xlstm-350m", smoke=True)
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(3), cfg)
        server = BatchedServer(cfg, params, max_len=32, mode="forge",
                               backend="interpret")
        sched = SlotScheduler(server, max_slots=2)
        sched.warmup()

        def _p(n, seed):
            return _prompts(1, n, seed=seed, vocab=cfg.vocab)[0]

        reqs = [
            Request(rid=0, prompt=_p(3, 30), max_new=6),
            Request(rid=1, prompt=_p(5, 31), max_new=2),
            Request(rid=2, prompt=_p(4, 32), max_new=3, arrival=1),
        ]
        out = sched.run(reqs)
        assert out["prefill_dispatches"] > 0
        assert out["swaps"] >= 1
        solo = BatchedServer(cfg, params, max_len=32, mode="forge",
                             backend="interpret")
        for r in reqs:
            want = solo.generate(r.prompt[None, :], r.max_new)["tokens"][0]
            np.testing.assert_array_equal(
                out["results"][r.rid]["tokens"], want)
