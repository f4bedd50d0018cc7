"""Per-architecture smoke tests (reduced configs): one forward + one
decode step on CPU, shape and NaN assertions, Forge-vs-raw fidelity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, input_specs, shape_applicable
from repro.models import get_model, losses

B, S = 2, 16
KEY = jax.random.PRNGKey(0)


def _forward(model, params, cfg, tokens, key):
    if cfg.family == "encdec":
        frames = jax.random.normal(key, (B, S, cfg.d_model),
                                   jnp.dtype(cfg.dtype))
        return model.apply(params, frames, tokens, cfg), frames
    if cfg.family == "vlm":
        patches = jax.random.normal(key, (B, 4, cfg.d_model),
                                    jnp.dtype(cfg.dtype))
        return model.module.apply(params, tokens, cfg,
                                  patch_embeds=patches), patches
    return model.apply(params, tokens, cfg), None


@pytest.fixture(scope="module", params=ARCH_IDS)
def arch_setup(request):
    cfg = get_config(request.param, smoke=True)
    model = get_model(cfg)
    params = model.init(KEY, cfg)
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
    return cfg, model, params, tokens


class TestSmokeForward:
    def test_forward_shapes_no_nan(self, arch_setup):
        cfg, model, params, tokens = arch_setup
        logits, _ = _forward(model, params, cfg, tokens, KEY)
        lo = np.asarray(logits, np.float32)
        assert lo.shape[0] == B and lo.shape[-1] == cfg.vocab
        assert np.all(np.isfinite(lo)), f"{cfg.name}: non-finite logits"

    def test_decode_step(self, arch_setup):
        cfg, model, params, tokens = arch_setup
        tok = tokens[:, :1]
        pos = jnp.asarray(0, jnp.int32)
        if cfg.family == "encdec":
            frames = jax.random.normal(KEY, (B, S, cfg.d_model),
                                       jnp.dtype(cfg.dtype))
            cache = model.init_cache(params, frames, cfg, max_len=32)
        elif cfg.family in ("hybrid", "ssm"):
            cache = model.init_cache(cfg, B, 32)
        else:
            cache = model.init_cache(cfg, B, 32)
        logits, cache2 = model.decode_step(params, cache, tok, pos, cfg)
        lo = np.asarray(logits, np.float32)
        assert lo.shape == (B, 1, cfg.vocab)
        assert np.all(np.isfinite(lo))
        # cache must have been written (not all zeros anymore) for attn archs
        if cfg.family in ("dense", "moe", "vlm"):
            assert any(float(jnp.sum(jnp.abs(leaf.astype(jnp.float32)))) > 0
                       for leaf in jax.tree_util.tree_leaves(cache2))

    def test_train_grad_finite(self, arch_setup):
        cfg, model, params, tokens = arch_setup
        if cfg.family in ("encdec", "vlm"):
            pytest.skip("grad smoke covered via dense/moe/ssm paths")

        def loss_fn(p):
            logits = model.apply(p, tokens, cfg)
            return losses.cross_entropy(logits[:, :-1], tokens[:, 1:])

        loss, grads = jax.value_and_grad(loss_fn)(params)
        assert np.isfinite(float(loss))
        leaves = jax.tree_util.tree_leaves(grads)
        assert all(np.all(np.isfinite(np.asarray(g, np.float32)))
                   for g in leaves)


class TestForgeFidelity:
    def test_fuse_matches_raw(self, arch_setup):
        """cfg.fuse='forge' vs 'none' must agree (scan-family archs)."""
        cfg, model, params, tokens = arch_setup
        if cfg.family not in ("dense", "moe", "vlm"):
            pytest.skip("forge block integration is scan-family only")
        logits_f, _ = _forward(model, params, cfg, tokens, KEY)
        cfg_n = cfg.with_(fuse="none")
        logits_n, _ = _forward(model, params, cfg_n, tokens, KEY)
        lf = np.asarray(logits_f, np.float32)
        ln = np.asarray(logits_n, np.float32)
        # fused kernels reorder float accumulation, so isolated logits can
        # exceed a pointwise 2e-2 tolerance: pin the bulk tight, bound the
        # outlier tail.  MoE gets a looser tail bound — top-k routing is
        # discontinuous and a borderline token can flip experts outright.
        min_within, max_tail = (
            (0.995, 0.15) if cfg.family == "moe" else (0.999, 0.1)
        )
        within = np.abs(lf - ln) <= 2e-2 + 2e-2 * np.abs(ln)
        assert within.mean() >= min_within, (
            f"{(~within).sum()} / {within.size} logits off"
        )
        assert np.max(np.abs(lf - ln)) < max_tail


class TestConfigs:
    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_full_config_loads(self, arch):
        cfg = get_config(arch)
        assert cfg.param_count() > 1e8
        assert cfg.n_layers >= 24 or cfg.family in ("encdec",)

    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_input_specs_all_shapes(self, arch):
        cfg = get_config(arch)
        for shape in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
            runs, reason = shape_applicable(cfg, shape)
            if not runs:
                assert reason
                continue
            specs = input_specs(cfg, shape)
            leaves = jax.tree_util.tree_leaves(specs)
            assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)

    def test_long_500k_applicability(self):
        assert shape_applicable(get_config("xlstm-350m"), "long_500k")[0]
        assert shape_applicable(get_config("recurrentgemma-2b"), "long_500k")[0]
        assert not shape_applicable(get_config("deepseek-7b"), "long_500k")[0]

    def test_registry_complete(self):
        assert len(ARCH_IDS) == 11

    def test_moe_active_params(self):
        cfg = get_config("kimi-k2-1t-a32b")
        assert 0.9e12 < cfg.param_count() < 1.2e12
        assert 25e9 < cfg.active_param_count() < 40e9
