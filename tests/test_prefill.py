"""Whole-prompt batched prefill: chunk-causal fidelity vs sequential
decode, the 2-D (batch × sequence) serve grid, and fallback paths
(ISSUE 4 acceptance criteria)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.metrics import check_prefill_fidelity
from repro.launch.serve import BatchedServer
from repro.models import get_model


@pytest.fixture(scope="module")
def smoke_setup():
    cfg = get_config("forge-125m", smoke=True)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    return cfg, model, params


def _prompts(batch, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 512, (batch, n)).astype(np.int32)


class TestPrefillStepFidelity:
    def test_matches_sequential_decode(self, smoke_setup):
        """Acceptance: one prefill_step pass over the (B, P) block
        produces the same per-position logits AND the same KV cache as
        P sequential decode_step calls, within 1e-5."""
        cfg, model, params = smoke_setup
        rep = check_prefill_fidelity(
            cfg, params, _prompts(3, 7), max_len=16
        )
        assert rep.max_abs_diff <= 1e-5

    def test_nonzero_start_position(self, smoke_setup):
        """A chunk written at pos > 0 (e.g. a second prompt segment)
        continues the causal stream exactly."""
        cfg, model, params = smoke_setup
        prompts = _prompts(2, 6, seed=1)
        max_len = 16
        cache_seq = model.init_cache(cfg, 2, max_len)
        for i in range(6):
            _, cache_seq = model.decode_step(
                params, cache_seq, jnp.asarray(prompts[:, i:i + 1]),
                jnp.asarray(i, jnp.int32), cfg,
            )
        cache_b = model.init_cache(cfg, 2, max_len)
        _, cache_b = model.prefill_step(
            params, cache_b, jnp.asarray(prompts[:, :2]),
            jnp.asarray(0, jnp.int32), cfg,
        )
        logits_b, cache_b = model.prefill_step(
            params, cache_b, jnp.asarray(prompts[:, 2:]),
            jnp.asarray(2, jnp.int32), cfg,
        )
        for a, b in zip(jax.tree_util.tree_leaves(cache_seq),
                        jax.tree_util.tree_leaves(cache_b)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=1e-5, rtol=1e-5,
            )

    def test_chunk_mask_is_causal(self, smoke_setup):
        """Perturbing a LATER prompt token must not change any earlier
        position's logits — the chunk-causal length mask at work."""
        cfg, model, params = smoke_setup
        p1 = _prompts(2, 8, seed=2)
        p2 = p1.copy()
        p2[:, -1] = (p2[:, -1] + 7) % cfg.vocab
        cache = model.init_cache(cfg, 2, 16)
        l1, _ = model.prefill_step(
            params, cache, jnp.asarray(p1), jnp.asarray(0, jnp.int32), cfg
        )
        cache = model.init_cache(cfg, 2, 16)
        l2, _ = model.prefill_step(
            params, cache, jnp.asarray(p2), jnp.asarray(0, jnp.int32), cfg
        )
        np.testing.assert_array_equal(
            np.asarray(l1[:, :-1, :]), np.asarray(l2[:, :-1, :])
        )
        assert np.abs(np.asarray(l1[:, -1, :])
                      - np.asarray(l2[:, -1, :])).max() > 0


class TestServePrefillGrid:
    def test_grid_compiles_bounded(self, smoke_setup):
        """Acceptance: the prompt-length sweep {17,32,48,100} × batch
        {1,4} under pow2×ladder compiles ≤ 6 prefill programs (vs 8
        exact cells), all served batched with zero recompiles on the
        repeat pass."""
        cfg, _, params = smoke_setup
        server = BatchedServer(
            cfg, params, max_len=128, mode="forge", backend="interpret",
            bucket_policy="pow2", seq_bucket_policy="ladder:32,64,128",
        )
        groups = [
            _prompts(B, P, seed=B * 100 + P)
            for B in (1, 4) for P in (17, 32, 48, 100)
        ]
        for g in groups:
            res = server.generate(g, 2)
            assert res["prefill_mode"] == "batched"
            assert res["tokens"].shape == (g.shape[0], 2)
        pf = server.prefill_bucketed.stats
        assert pf.compiles <= 6  # vs 8 exact (batch, length) cells
        assert len(server.prefill_bucketed.programs) == pf.compiles
        # every grid cell is warm: the repeat pass runs zero Phase 1-4
        for g in groups:
            assert server.generate(g, 2)["compile_s"] == 0.0
        assert pf.compiles <= 6
        assert pf.pad_waste > 0  # P=17 rode the S32 rung, B=1 rode B2

    def test_batched_matches_sequential_tokens(self, smoke_setup):
        """The batched-prefill server must emit the same greedy tokens
        as the forced-sequential server (same backend, same bucket)."""
        cfg, _, params = smoke_setup
        p = _prompts(3, 9, seed=3)
        batched = BatchedServer(cfg, params, max_len=32, mode="forge",
                                backend="segment_jit")
        seq = BatchedServer(cfg, params, max_len=32, mode="forge",
                            backend="segment_jit", prefill="sequential")
        rb = batched.generate(p, 4)
        rs = seq.generate(p, 4)
        assert rb["prefill_mode"] == "batched"
        assert rs["prefill_mode"] == "sequential"
        assert seq.prefill_bucketed is None
        np.testing.assert_array_equal(rb["tokens"], rs["tokens"])
        assert rb["ttft_s"] > 0 and rs["ttft_s"] > 0

    def test_moe_family_has_no_batched_prefill(self):
        """MoE routing is dropless and per token, so a whole-prompt block
        reproduces sequential decode: the family exposes prefill_step, the
        serve fronts build the batched prefill, and the block's logits
        equal token-by-token decode (float32; the tolerance covers the
        matmuls' different row blocking, routing picks the same experts)."""
        from repro.launch.steps import make_batched_prefill_step
        from repro.models import transformer

        cfg = get_config("phi3.5-moe-42b-a6.6b", smoke=True).with_(dtype="float32")
        assert cfg.family == "moe"
        model = get_model(cfg)
        assert model.prefill_step is transformer.prefill_step
        assert make_batched_prefill_step(cfg) is not None
        params = model.init(jax.random.PRNGKey(2), cfg)
        toks = jnp.asarray(_prompts(2, 7, seed=6), jnp.int32)
        cache = model.init_cache(cfg, 2, 16)
        block, _ = model.prefill_step(params, cache, toks, jnp.int32(0), cfg)
        steps = []
        for t in range(7):
            lg, cache = model.decode_step(params, cache, toks[:, t:t + 1], jnp.int32(t), cfg)
            steps.append(lg[:, 0])
        np.testing.assert_allclose(np.asarray(block), np.stack(steps, 1),
                                   rtol=1e-4, atol=1e-4)

    def test_prompt_beyond_ladder_falls_back(self, smoke_setup):
        """A prompt longer than the top sequence rung (or than max_len)
        is admitted through the sequential path, not rejected."""
        cfg, _, params = smoke_setup
        server = BatchedServer(
            cfg, params, max_len=32, mode="forge", backend="interpret",
            seq_bucket_policy="ladder:8",
        )
        res = server.generate(_prompts(2, 12, seed=4), 2)
        assert res["prefill_mode"] == "sequential"
        assert res["tokens"].shape == (2, 2)
        # ... while a prompt inside the ladder still runs batched
        res = server.generate(_prompts(2, 6, seed=5), 2)
        assert res["prefill_mode"] == "batched"
