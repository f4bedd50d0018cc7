"""The DeepSeek-V2-Lite reference module through the harness: its layout
is the program's tree at the cut, its counted work is the hand count, its
fp8 control separates from float32, and a tiny cell of it runs the whole
path on the CPU with the expert counter read."""
from __future__ import annotations

import json

import jax
import numpy as np
import pytest

import tiny
from bench import correct, layer_work, program

SEED = 2**31 + 54321
CONFIG = json.loads((tiny.HERE.parents[1] / "perfbench" / "configs"
                     / "deepseek-v2-lite.json").read_text())
TINY = json.loads((tiny.DATA / "tiny_v2_lite.json").read_text())


def test_layout_is_the_programs_tree_at_the_cut():
    ref = correct.load_reference(CONFIG["reference"])
    mc = program.program_config(CONFIG)
    assert (mc.n_experts, mc.experts_held, mc.n_layers, mc.first_dense_layers) == (64, 8, 27, 1)
    params = jax.eval_shape(lambda: program.program_params(CONFIG, 0, ref))
    program.check_params(mc, params)  # exits on any difference
    assert params["blocks"]["moe"]["w_gate"].shape == (26, 8, 2048, 1408)
    assert params["blocks"]["moe"]["router"].shape == (26, 2048, 64)
    assert params["dense_blocks"]["ffn"]["w_gate"].shape == (1, 2048, 10944)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert 3.10e9 < n < 3.12e9


def test_counted_work_by_hand():
    w = layer_work.counted("deepseek-v2-lite")
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    assert attn == 13_762_560
    moe = 2048 * 64 + 3 * 2048 * 2816 + 6 * 8 / 64 * 3 * 2048 * 1408
    assert w.matmul_params == int(27 * attn + 3 * 2048 * 10944 + 26 * moe + 2048 * 102400)
    assert w.kv_bytes_per_token == 27 * 576 * 2 == 31_104
    assert w.attn_flops(10) == 2 * 27 * 16 * (2 * 512 + 64) * 10
    # a one-token dispatch reads the held experts its 6 picks can reach
    expert_bytes = 26 * 3 * 2048 * 1408 * 2
    assert w.dispatch_weight_bytes(1) - w.dispatch_weight_bytes(0) == pytest.approx(
        expert_bytes * 8 * (6 / 64))
    f, b = w.latent_attention_work(queries=2, keys=5, dispatches=1)
    assert f == 27 * 2 * 512 * 16 * 256 * 2 + w.attn_flops(5)
    assert b == 31_104 * 5 + 27 * 512 * 16 * 256 * 2


def test_fp8_control_separates_from_float32():
    """On a tiny configuration: tokens the float32 reference puts first
    (greedy) read a gap of 0; the fp8 pass's picks read gaps well above
    rounding."""
    from bench import weights

    ref = correct.load_reference(TINY["reference"])
    glob, groups = ref.layout(TINY)
    g = weights.global_maker(glob, TINY["torch_dtype"])(SEED)
    make = weights.layer_maker(glob, groups, TINY["torch_dtype"])
    layers = [make(SEED, layer) for layer in range(3)]
    fns, head = ref.make_layer_fns(TINY), ref.make_head_fn(TINY)
    seq = list(np.random.default_rng(0).integers(1, 512, 24))
    with jax.default_matmul_precision("highest"):
        for _ in range(12):  # greedy continuation of the first 24 tokens
            h = ref.embed(g, np.asarray([seq], np.int32))
            for group, w in layers:
                h = fns[group](h, w)
            seq.append(int(np.argmax(head(h[0, -1:], g))))
    prompt, served = np.asarray(seq[:24], np.int32), np.asarray(seq[24:], np.int32)
    out = correct.reference_gaps(TINY, SEED, [prompt], [served], control=True)
    assert out["gap"].max() < 1e-4
    assert out["control_gap"].max() > 1e-2


def test_tiny_cell_runs_the_whole_path_and_reads_the_expert_counter():
    sp = dict(tiny.spec(), config=TINY)
    run = tiny.R.run_cell(sp, SEED, 3.0, False, require_chip=False)
    assert run["failed"] == 0
    load = layer_work.expert_load(run["ctx"])
    picks, slots, touched = load["decode"]
    assert 0 < picks and 0 < touched <= slots and slots % (2 * 4) == 0
    read = tiny.R.reader("moe.tokens_per_expert.batch")
    assert read(run["ctx"]) == pytest.approx(picks / slots)
    check = correct.check(TINY, sp["cell"], run["ctx"]["res"], SEED)
    assert check["tokens"] > 0 and check["max_logit_gap"] < 0.5


def test_layer_readers_read_the_scopes_and_counter_and_fall_silent_without():
    """The expert-counter and latent-attention readers on a traced run's
    context: a share from the scope times and the served requests, picks
    per held expert from the counter; None where the program keeps no
    expert counter and emits no such scope (a parent tree)."""
    from types import SimpleNamespace

    reqs = [SimpleNamespace(rid=0, prompt=[1] * 200), SimpleNamespace(rid=1, prompt=[1] * 300)]
    res = {"requests": reqs, "results": {0: {"tokens": [1] * 300}, 1: {"tokens": [1] * 400}},
           "skips": {0: 0, 1: 0}, "prefill_dispatches": 2, "decode_dispatches": 400,
           "expert_load": [[1000, 26 * 8 * 400, 900], [500, 26 * 8 * 2, 300]]}
    peak = {"bf16_flop_s": 197e12, "hbm_bytes_s": 819e9}
    ctx = {"res": res, "peak": peak,
           "spans": {"scope_s": {"mla.attend": 2.0, "kv.gather": 1.0, "moe.experts": 3.0}}}
    work = layer_work.counted("deepseek-v2-lite")
    got = {m: tiny.R.reader(m)(ctx) for m in
           ("mla.attend_roofline.batch", "moe.tokens_per_expert.batch")}
    assert got["moe.tokens_per_expert.batch"] == pytest.approx(1000 / (26 * 8 * 400))
    keys = layer_work.served_keys(ctx)
    want = layer_work.least_seconds(
        [work.latent_attention_work(*keys["prefill"], 2),
         work.latent_attention_work(*keys["decode"], 400)], peak)
    assert got["mla.attend_roofline.batch"] == pytest.approx(100 * want / 3.0)
    assert 0 < got["mla.attend_roofline.batch"] < 100
    parent = {"res": {k: v for k, v in res.items() if k != "expert_load"}, "peak": peak,
              "spans": {"scope_s": {"attn": 2.0, "kv.gather": 1.0, "mlp": 3.0}}}
    for m in got:
        assert tiny.R.reader(m)(parent) is None
