"""FLOP and byte counts against hand-computed decode figures, and the peaks table."""
from __future__ import annotations

import json

import pytest

import tiny
from bench import correct, flops

ROOT = tiny.HERE.parents[1]


def config(name):
    return json.loads((ROOT / "perfbench" / "configs" / f"{name}.json").read_text())


def dims(name):
    """The counted work of a shipped configuration, from its reference module."""
    cfg = config(name)
    return correct.load_reference(cfg["reference"]).counted_work(cfg)


def test_phi3_decode_by_hand():
    d = dims("phi3-mini-3.8b")
    # per layer: q, k, v, o 4 x 3072^2 = 37,748,736; gate, up, down 3 x 3072 x 8192
    # = 75,497,472; 32 layers; LM head 3072 x 32064 = 98,500,608
    assert d.matmul_params == 32 * (37_748_736 + 75_497_472) + 98_500_608 == 3_722_379_264
    assert d.kv_bytes_per_token == 2 * 32 * 32 * 96 * 2 == 393_216
    assert d.weight_bytes == 3_722_379_264 * 2 + 65 * 3072 * 4
    assert d.dispatch_weight_bytes(1) == d.dispatch_weight_bytes(8) == d.weight_bytes
    # one decode step with 1000 keys: 2 x params + 4 x 32 x 32 x 96 x 1000
    assert d.token_flops(1000) == 7_444_758_528 + 393_216_000
    f, b = flops.decode_work(d, prompt=999, served=2)
    assert f == d.token_flops(1000)
    assert b == 1000 * 393_216 + 393_216 + 3072 * 2


def test_deepseek_7b_16_layers_by_hand():
    d = dims("deepseek-7b")
    # per layer 4 x 4096^2 + 3 x 4096 x 11008 = 202,375,168; 16 layers;
    # LM head 4096 x 102400 = 419,430,400
    assert d.matmul_params == 16 * 202_375_168 + 419_430_400 == 3_657_433_088
    assert d.kv_bytes_per_token == 2 * 16 * 32 * 128 * 2 == 262_144
    assert d.attn_flops(512) == 4 * 16 * 32 * 128 * 512


#: (prompt, skip, served) per request; a request that served nothing counts no work
REQUESTS = [(100, 0, 50), (700, 512, 1), (64, 16, 300), (1000, 0, 24), (33, 0, 0)]


@pytest.mark.parametrize("name,want", [
    ("phi3-mini-3.8b", {"prefill_flops": 10190587428864.0, "prefill_bytes": 53385400320.0,
                        "decode_flops": 2798726676480.0, "decode_bytes": 3171449026560.0}),
    ("deepseek-7b", {"prefill_flops": 9935587901440.0, "prefill_bytes": 52057653248.0,
                     "decode_flops": 2738296193024.0, "decode_bytes": 3104367419392.0}),
])
def test_window_work_of_the_shipped_configurations(name, want):
    """Exactly the counts of the harness before the count moved into the
    reference modules (7 prefill and 421 decode dispatches)."""
    assert flops.window_work(dims(name), REQUESTS, 7, 421) == want


def test_prefill_counts_only_the_suffix():
    d = dims("phi3-mini-3.8b")
    f, _ = flops.prefill_work(d, prompt=100, skip=64)
    keys = sum(p + 1 for p in range(64, 100))
    assert f == 2 * d.matmul_params * 36 + d.attn_flops(keys)


def test_roofline_takes_the_larger_bound_per_phase():
    w = {"prefill_flops": 197e12, "prefill_bytes": 1.0,
         "decode_flops": 1.0, "decode_bytes": 819e9}
    pk = flops.peaks("TPU v5 lite")
    assert flops.roofline_seconds(w, pk) == pytest.approx(2.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("TPU v99")
