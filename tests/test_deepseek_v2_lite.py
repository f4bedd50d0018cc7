"""DeepSeek-V2-Lite's mechanisms at CPU size, on seeded random weights:
latent attention through the paged latent store (prefill, then absorbed
decode) against the plain reference, the expert layer's share of one
rank, per-token MoE prefill, YaRN tables and the two-group store."""
import importlib.util
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import get_model, layers as L, transformer
from repro.models.moe import moe_ffn

REF_PATH = Path(__file__).resolve().parents[1] / "perfbench" / "reference" / "deepseek_v2_lite.py"
ARCH = "deepseek-v2-lite"


def _reference():
    name = "deepseek_v2_lite_reference"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, REF_PATH)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod  # a dataclass looks its module up there
        spec.loader.exec_module(mod)
    return sys.modules[name]


def _ref_cfg(mc):
    """The reference's configuration for a program config (held experts
    from id 0, as the benchmark's cut)."""
    return {
        "hidden_size": mc.d_model, "intermediate_size": mc.dense_d_ff,
        "moe_intermediate_size": mc.d_ff, "num_hidden_layers": mc.n_layers,
        "num_attention_heads": mc.n_heads, "vocab_size": mc.vocab,
        "kv_lora_rank": mc.kv_lora_rank, "qk_nope_head_dim": mc.qk_nope_head_dim,
        "qk_rope_head_dim": mc.qk_rope_head_dim, "v_head_dim": mc.v_head_dim,
        "first_k_dense_replace": mc.first_dense_layers, "n_routed_experts": mc.n_held,
        "published": {"n_routed_experts": mc.n_experts},
        "n_shared_experts": mc.shared_experts, "num_experts_per_tok": mc.top_k,
        "norm_topk_prob": mc.norm_topk_prob, "routed_scaling_factor": mc.routed_scaling_factor,
        "rms_norm_eps": 1e-6, "rope_theta": mc.rope_theta, "tie_word_embeddings": False,
        "torch_dtype": "float32",
        "rope_scaling": {"factor": mc.yarn_factor, "beta_fast": mc.yarn_beta_fast,
                         "beta_slow": mc.yarn_beta_slow, "mscale": mc.yarn_mscale,
                         "mscale_all_dim": mc.yarn_mscale_all_dim,
                         "original_max_position_embeddings": mc.yarn_original_max_pos},
    }


def _weights(ref, cfg, params):
    """The program's weights under the reference's names: (globals,
    [(group, layer weights)] in layer order), read at the layout's paths."""
    def at(path):
        node = params
        for k in path.split("/"):
            node = node[k]
        return node

    glob, groups = ref.layout(cfg)
    g = {name: at(path).astype(jnp.float32) for name, _, _, path in glob}
    layers = []
    for gi, (count, tensors) in enumerate(groups):
        for i in range(count):
            layers.append((gi, {name: at(path)[i].astype(jnp.float32)
                                for name, _, _, path in tensors}))
    return g, layers


def _ref_logits(ref, cfg, params, tokens, precision="float32"):
    g, layers = _weights(ref, cfg, params)
    fns = ref.make_layer_fns(cfg, precision)
    with jax.default_matmul_precision("highest"):
        h = ref.embed(g, tokens)
        for gi, w in layers:
            h = fns[gi](h, w)
        B, S, d = h.shape
        return np.asarray(ref.make_head_fn(cfg, precision)(h.reshape(B * S, d), g)).reshape(B, S, -1)


def _paged_run(mc, params, tokens, P, page_size=4, max_len=32):
    """Paged prefill of tokens[:, :P], then absorbed paged decode of each
    later token; returns the logits at every position (B, S, V)."""
    model = get_model(mc)
    B, S = tokens.shape
    MP = max_len // page_size
    cache = model.init_paged_cache(mc, B, max_len, num_pages=B * MP + 1, page_size=page_size)
    cache["page_table"] = jnp.asarray(1 + np.arange(B * MP).reshape(B, MP), jnp.int32)
    mask = jnp.ones((B,), bool)
    lg, cache = model.paged_prefill_step(params, cache, tokens[:, :P], jnp.zeros((B,), jnp.int32),
                                         mc, slot_mask=mask)
    out = [np.asarray(lg, np.float32)]
    for t in range(P, S):
        lg, cache = model.paged_decode_step(params, cache, tokens[:, t:t + 1],
                                            jnp.full((B,), t, jnp.int32), mc, slot_mask=mask)
        out.append(np.asarray(lg, np.float32))
    return np.concatenate(out, 1), cache


@pytest.fixture(scope="module")
def v2():
    mc = get_config(ARCH, smoke=True)
    ref = _reference()
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, mc.vocab, (2, 12)), jnp.int32)
    return mc, ref, tokens


def test_paged_prefill_then_absorbed_decode_matches_reference(v2):
    """Prefill 7 tokens into the latent pages, decode 5 more through them:
    every position's logits against the published (non-absorbed) float32
    reference.  The tolerance, 2e-3 of the logits' spread, covers float32
    reassociation (absorption, grouped matmuls); the same program in
    bfloat16 misses it by far more (its rounding is ~1e-2 relative)."""
    mc, ref, tokens = v2
    mc32 = mc.with_(dtype="float32")
    params = get_model(mc32).init(jax.random.PRNGKey(3), mc32)
    want = _ref_logits(ref, _ref_cfg(mc32), params, tokens)
    got, cache = _paged_run(mc32, params, tokens, P=7)
    tol = 2e-3 * float(np.std(want))
    assert np.max(np.abs(got - want)) < tol
    # the store holds one latent row per token per layer, no per-head K/V
    assert set(cache) == {"kv_pages", "page_table", "expert_load"}
    assert cache["kv_pages"].shape[-1] == mc.kv_lora_rank + mc.qk_rope_head_dim
    bf = mc.with_(dtype="bfloat16")
    got_bf, _ = _paged_run(bf, jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 and a.ndim > 1
        and a.shape[-1] != mc.n_experts else a, params), tokens, P=7)
    assert np.max(np.abs(got_bf - want)) > 5 * tol


def test_expert_shares_sum_to_uncut_layer(v2):
    """Expert parallelism over 8 ranks of 1 expert each: each rank's layer
    output less the shared experts, summed, plus the shared experts once,
    is the uncut reference MoE layer (all 8 experts held)."""
    mc, ref, _ = v2
    mc = mc.with_(dtype="float32", experts_held=0)
    cfg = _ref_cfg(mc)
    params = get_model(mc).init(jax.random.PRNGKey(4), mc)
    _, layers = _weights(ref, cfg, params)
    gi, w = layers[-1]
    assert gi == 1 and w["w_gate"].shape[0] == mc.n_experts
    h = jnp.asarray(np.random.default_rng(5).standard_normal((2, 6, mc.d_model)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.make_layer_fns(cfg)[1](h, dict(w, wo=jnp.zeros_like(w["wo"]))) - h
        x = L.rms_norm(h, w["ffn_norm"])
        moe = params["blocks"]["moe"]
        one = jax.tree_util.tree_map(lambda a: a[-1], moe)
        shared = L.swiglu_ffn(x, one["shared"])
        total = shared
        for r in range(mc.n_experts):
            share = dict(one, w_gate=one["w_gate"][r:r + 1], w_up=one["w_up"][r:r + 1],
                         w_down=one["w_down"][r:r + 1])
            y, _ = moe_ffn(x, share, top_k=mc.top_k, first=r, norm_topk_prob=False)
            total = total + (y - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("paged", [False, True], ids=["batched", "paged"])
@pytest.mark.parametrize("arch", [ARCH, "phi3.5-moe-42b-a6.6b"])
def test_moe_prefill_matches_token_by_token_decode(arch, paged):
    """Dropless per-token routing: one (B, S) prefill block gives the logits
    of S single-token decode steps, through the contiguous cache and
    through the page store (float32; tolerance for the matmuls' row
    blocking)."""
    mc = get_config(arch, smoke=True).with_(dtype="float32")
    model = get_model(mc)
    params = model.init(jax.random.PRNGKey(6), mc)
    tokens = jnp.asarray(np.random.default_rng(7).integers(0, mc.vocab, (2, 6)), jnp.int32)
    steps = []
    if paged:
        block, _ = _paged_run(mc, params, tokens, P=6)
        one, _ = _paged_run(mc, params, tokens, P=1)
        steps = one
    else:
        cache = model.init_cache(mc, 2, 16)
        block, _ = model.prefill_step(params, cache, tokens, jnp.int32(0), mc)
        for t in range(6):
            lg, cache = model.decode_step(params, cache, tokens[:, t:t + 1], jnp.int32(t), mc)
            steps.append(np.asarray(lg)[:, 0])
        steps = np.stack(steps, 1)
    np.testing.assert_allclose(np.asarray(block), steps, rtol=1e-4, atol=1e-4)


def test_yarn_tables_match_formula():
    """YaRN at the published numbers: the frequency ramp between the
    correction dims floor(10.47) = 10 and ceil(22.5) = 23 of 64, cos/sin
    unscaled (mscale equals mscale_all_dim), and softmax scale
    (0.1 * 0.707 * ln 40 + 1)^2 / sqrt(192)."""
    mc = get_config(ARCH)
    dim, theta = mc.qk_rope_head_dim, mc.rope_theta
    inv = L.yarn_inv_freq(dim, theta, 40.0, 4096, 32.0, 1.0)
    base = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - 10) / (23 - 10), 0, 1)
    np.testing.assert_allclose(inv, base * (1 - ramp) + base / 40 * ramp, rtol=1e-6)
    assert np.allclose(inv[:10], base[:10]) and np.allclose(inv[23:], base[23:] / 40)
    pos = jnp.arange(5, dtype=jnp.int32)
    cos, sin = transformer._rope_for(mc, pos, None)
    ang = np.arange(5)[:, None] * inv[None]
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang), rtol=1e-5, atol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert transformer.mla_softmax_scale(mc) == pytest.approx(m * m / math.sqrt(192))
    ref = _reference()
    np.testing.assert_allclose(ref.yarn_inv_freq(dim, theta, _ref_cfg(mc)["rope_scaling"]),
                               inv, rtol=1e-6)


def test_two_group_store_indexing(v2):
    """The dense first layer and the MoE layers are two groups over one
    store indexed 0..L-1: after a paged prefill every layer's rows hold
    what the contiguous cache holds for that layer, and no two layers'
    rows agree."""
    mc, _, tokens = v2
    model = get_model(mc)
    assert [g for g, _, _ in transformer.layer_groups(mc)] == ["dense_blocks", "blocks"]
    params = model.init(jax.random.PRNGKey(8), mc)
    assert "ffn" in params["dense_blocks"] and "moe" in params["blocks"]
    assert params["dense_blocks"]["norm1"]["scale"].shape[0] == 1
    assert params["blocks"]["norm1"]["scale"].shape[0] == mc.n_layers - 1
    _, cache = _paged_run(mc, params, tokens[:, :8], P=8, page_size=4, max_len=8)
    pt = np.asarray(cache["page_table"])
    rows = np.asarray(cache["kv_pages"], np.float32)[:, pt].reshape(mc.n_layers, 2, 8, -1)
    ccache = model.init_cache(mc, 2, 8)
    _, ccache = model.prefill_step(params, ccache, tokens[:, :8], jnp.int32(0), mc)
    np.testing.assert_allclose(rows, np.asarray(ccache["kv"], np.float32), rtol=2e-2, atol=2e-2)
    for a in range(mc.n_layers):
        for b in range(a):
            assert not np.allclose(rows[a], rows[b])


def _prefill_rows(mc, params, tokens, lengths, **kw):
    """One paged prefill of edge-padded rows of the given real lengths."""
    model = get_model(mc)
    B, S = tokens.shape
    cache = model.init_paged_cache(mc, B, 16, num_pages=B * 4 + 1, page_size=4)
    cache["page_table"] = jnp.asarray(1 + np.arange(B * 4).reshape(B, 4), jnp.int32)
    padded = np.asarray(tokens).copy()
    for b, n in enumerate(lengths):
        padded[b, n:] = padded[b, n - 1]
    return model.paged_prefill_step(params, cache, jnp.asarray(padded), jnp.zeros((B,), jnp.int32),
                                    mc, slot_mask=jnp.ones((B,), bool), **kw)


@pytest.mark.parametrize("arch", [ARCH, "deepseek-7b"])
def test_serve_prefill_step_gives_last_real_column(arch):
    """The serve path's prefill step returns each row's logits at its last
    real prompt column alone, (B, vocab): that column of the whole block's
    logits, with the store written as the whole block writes it."""
    from repro.launch.steps import make_paged_prefill_step, paged_store

    mc = get_config(arch, smoke=True).with_(dtype="float32")
    params = get_model(mc).init(jax.random.PRNGKey(9), mc)
    tokens = jnp.asarray(np.random.default_rng(10).integers(0, mc.vocab, (2, 8)), jnp.int32)
    lengths = [3, 8]
    full, want = _prefill_rows(mc, params, tokens, lengths)
    step = make_paged_prefill_step(mc)
    padded = np.asarray(tokens).copy()
    padded[0, 3:] = padded[0, 2]
    last = jnp.asarray([n - 1 for n in lengths], jnp.int32)
    logits, store = jax.jit(step)(params, paged_store(want), want["page_table"],
                                  jnp.asarray(padded), jnp.zeros((2,), jnp.int32),
                                  jnp.ones((2,), bool), last)
    assert logits.shape == (2, mc.vocab)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full)[[0, 1], [2, 7]], rtol=1e-5, atol=1e-5)
    _, ref_store = _prefill_rows(mc, params, tokens, lengths)
    for k in store:
        if k != transformer.EXPERT_LOAD:
            np.testing.assert_array_equal(np.asarray(store[k]), np.asarray(ref_store[k]))


def test_expert_counter_counts_real_columns_only(v2):
    """The expert counter's prefill row counts the picks of real prompt
    columns only: rows of 3 and 6 real tokens padded to 8 count what the
    two prompts count when each is prefilled at its own length, and fewer
    than the whole padded block would."""
    mc, _, _ = v2
    params = get_model(mc).init(jax.random.PRNGKey(11), mc)
    tokens = jnp.asarray(np.random.default_rng(12).integers(0, mc.vocab, (2, 8)), jnp.int32)
    lengths = [3, 6]
    last = jnp.asarray([n - 1 for n in lengths], jnp.int32)
    _, cache = _prefill_rows(mc, params, tokens, lengths, last=last)
    got = int(cache[transformer.EXPERT_LOAD][1, 0])
    want = 0
    for b, n in enumerate(lengths):
        _, one = _prefill_rows(mc, params, tokens[b:b + 1, :n], [n])
        want += int(one[transformer.EXPERT_LOAD][1, 0])
    assert got == want > 0
    _, whole = _prefill_rows(mc, params, tokens, lengths)
    assert int(whole[transformer.EXPERT_LOAD][1, 0]) > got
