"""Layouts, program trees, layer groups and counted work as the reference
modules state them: the shipped dense layout bit for bit as before, and a
two-group MoE layout taken through the harness with no edit to it."""
from __future__ import annotations

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench import correct, flops, program, weights

SEED = 2**31 + 12345

#: sha256 (first 16 hex digits) of each tensor made for tiny.json at SEED, as
#: the harness made them before layouts named program paths
TINY = {
    "embed": "59102bca1224a91e", "final_norm/scale": "22fbc1b12153bd99",
    "lm_head": "297bb14969f4c59b",
    "blocks/norm1/scale": "65be6b3406126b89", "blocks/norm2/scale": "a990ba3549a6491d",
    "blocks/attn/wq": "95bfb729e603faea", "blocks/attn/wk": "b1d017b4656a89c0",
    "blocks/attn/wv": "f3893ebc61aafde6", "blocks/attn/wo": "788c25b273bd200c",
    "blocks/ffn/w_gate": "0eab6c7e9930bd91", "blocks/ffn/w_up": "b9bc20828ecbc656",
    "blocks/ffn/w_down": "a6914df8bc10f996",
}
#: layer 1 of tiny.json as the reference makes it again, upcast
TINY_LAYER1 = {
    "attn_norm": "0cf110a81d243eb3", "ffn_norm": "ff9e11cb7220adad",
    "wq": "a02ab09079b087fc", "wk": "489e01a984af106d", "wv": "ec508d3c3f1439dc",
    "wo": "b8a9645885b15180", "w_gate": "eda72e98800c09c0", "w_up": "7c30cc408a830d68",
    "w_down": "a867b0941e0e559f",
}


def digest(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]


def test_dense_weights_are_bitwise_as_before():
    cfg = tiny.spec()["config"]
    ref = correct.load_reference(cfg["reference"])
    glob, groups = ref.layout(cfg)
    made = weights.make_all(SEED, glob, groups, cfg["torch_dtype"])
    assert {path: digest(a) for path, a in made.items()} == TINY
    group, w = weights.layer_maker(glob, groups, cfg["torch_dtype"])(SEED, 1)
    assert group == 0
    assert {name: digest(a) for name, a in w.items()} == TINY_LAYER1
    params = program.program_params(cfg, SEED, ref)
    program.check_params(program.program_config(cfg), params)
    assert digest(params["blocks"]["attn"]["wq"]) == TINY["blocks/attn/wq"]


@pytest.fixture
def moe(monkeypatch):
    """The two-group MoE configuration and its test-only reference module."""
    monkeypatch.setattr(correct, "REFERENCE_DIR", tiny.HERE)
    cfg = json.loads((tiny.DATA / "tiny_moe.json").read_text())
    return cfg, correct.load_reference(cfg["reference"])


def test_moe_program_config_is_held_to_the_reference(moe):
    cfg, _ = moe
    mc = program.program_config(cfg)
    assert (mc.family, mc.n_layers, mc.n_experts, mc.top_k) == ("moe", 3, 2, 2)
    with pytest.raises(SystemExit):
        program.program_config(dict(cfg, hidden_size=96))
    with pytest.raises(SystemExit):  # the program would hold all 4 experts
        program.program_config(dict(cfg, program={"arch": cfg["program"]["arch"], "smoke": True}))


def test_moe_params_are_the_programs_tree(moe):
    from repro.models import get_model

    cfg, ref = moe
    mc = program.program_config(cfg)
    params = program.program_params(cfg, SEED, ref)
    program.check_params(mc, params)
    want = jax.eval_shape(lambda: get_model(mc).init(jax.random.PRNGKey(0), mc))
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(want)
    assert params["blocks"]["moe"]["router"].dtype == jnp.float32
    del params["blocks"]["moe"]["router"]
    with pytest.raises(SystemExit):
        program.check_params(mc, params)


def test_each_layer_is_made_in_its_group(moe):
    cfg, ref = moe
    glob, groups = ref.layout(cfg)
    assert [count for count, _ in groups] == [1, 2]
    made = weights.make_all(SEED, glob, groups, cfg["torch_dtype"])
    assert made["blocks/moe/w_gate"].shape == (3, 2, 64, 64)
    one = weights.layer_maker(glob, groups, cfg["torch_dtype"])
    for layer, want in enumerate([0, 1, 1]):
        group, w = one(SEED, layer)
        assert group == want
        assert w["w_gate"].shape == (2, 64, 64)
        for name, _, _, path in groups[group][1]:
            np.testing.assert_array_equal(np.asarray(w[name]),
                                          np.asarray(made[path][layer], np.float32))


def test_the_reference_runs_each_layer_through_its_groups_function(moe):
    cfg, ref = moe
    ref.CALLS.clear()
    g = correct.reference_gaps(cfg, SEED, [np.arange(1, 9, dtype=np.int32)],
                               [np.array([3, 4, 5], np.int32)])
    assert ref.CALLS == [0, 1, 1]
    assert g["gap"].shape == (3,) and np.all(g["gap"] >= 0)


def test_window_work_takes_the_modules_weight_bytes_per_dispatch(moe):
    cfg, ref = moe
    w = ref.counted_work(cfg)
    # 40 + 16 tokens prefilled in 2 dispatches, 8 + 4 decoded in 6
    out = flops.window_work(w, [(40, 0, 9), (24, 8, 5)], 2, 6)
    pre = flops.prefill_work(w, 40, 0)[1] + flops.prefill_work(w, 24, 8)[1]
    dec = flops.decode_work(w, 40, 9)[1] + flops.decode_work(w, 24, 5)[1]
    assert out["prefill_bytes"] == pytest.approx(pre + 2 * w.dispatch_weight_bytes(28))
    assert out["decode_bytes"] == pytest.approx(dec + 6 * w.dispatch_weight_bytes(2))
    # a dispatch of more tokens reads more of the held experts
    assert w.dispatch_weight_bytes(1) < w.dispatch_weight_bytes(2) < w.dispatch_weight_bytes(28)
