"""The comparison that decides ``correct``, on a tiny cell on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests

Drives the whole run path of ``run.py`` (weights, warm-up, shared prefixes,
the open-loop window, the sample, the reference) with the chip check
skipped, and shows the comparison fail where it must: the fp8 control reads
above the limit, and so does the served output when the timed path is broken
underneath (decode returns the page store unchanged; decode tokens altered
where they are produced).
"""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
from tiny import HERE, run_tiny, spec

SEED = 2**31 + 12345


def _wrap_decode(cell, fn):
    """Route every decode-front call through ``fn(mod, args) -> outputs``."""
    front = cell.srv.bucketed
    program_for = front.program_for

    class Broken:
        def __init__(self, mod):
            self._mod = mod

        def __call__(self, *args):
            return fn(self._mod, args)

        def __getattr__(self, attr):
            return getattr(self._mod, attr)

    def broken_program_for(*a, **k):
        mod, key, rest = program_for(*a, **k)
        return Broken(mod), key, rest

    front.program_for = broken_program_for


def test_sound_run_is_correct_and_control_fails():
    out, check = run_tiny(SEED, control=True)
    limit = out["checks"]["max_logit_gap"]["limit"]
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert check["prefix_hits"] > 0 and check["swapped_in"] > 0
    assert check["max_logit_gap"] <= limit
    assert check["control_max_logit_gap"] > limit, check
    assert list(out)[-1] == "checks"


def test_decode_state_left_unchanged_is_not_correct():
    def plant(cell):
        def stale(mod, args):
            tok, _ = mod(*args)
            return tok, args[1]  # the page store as it came in

        _wrap_decode(cell, stale)

    out, check = run_tiny(SEED + 1, plant=plant)
    assert out["correct"] is False, check


def test_altered_decode_token_is_not_correct():
    vocab = spec()["config"]["vocab_size"]

    def plant(cell):
        def altered(mod, args):
            import jax.numpy as jnp

            tok, store = mod(*args)
            return tok.at[0, 0].set((tok[0, 0] + 1) % vocab).astype(jnp.int32), store

        _wrap_decode(cell, altered)

    out, check = run_tiny(SEED + 2, plant=plant)
    assert out["correct"] is False, check


def test_layer_weights_match_the_weights_served():
    """The reference's one-layer weights are the program's, bit for bit."""
    import jax
    from bench import correct, weights

    cfg = spec()["config"]
    ref = correct.load_reference(cfg["reference"])
    glob, groups = ref.layout(cfg)
    served = weights.make_all(SEED, glob, groups, cfg["torch_dtype"])
    one = weights.layer_maker(glob, groups, cfg["torch_dtype"])
    for layer in range(cfg["num_hidden_layers"]):
        group, w = one(SEED, layer)
        for name, _, _, path in groups[group][1]:
            np.testing.assert_array_equal(np.asarray(w[name]),
                                          np.asarray(served[path][layer], np.float32))
    g = weights.global_maker(glob, cfg["torch_dtype"])(SEED)
    for name, _, _, path in glob:
        np.testing.assert_array_equal(np.asarray(g[name]), np.asarray(served[path], np.float32))
    assert jax.devices()[0].platform == "cpu"


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(HERE.parent / "run.py"), "--workload",
                        "phi3-mini-3.8b.agent", "--seed", "1", "--seconds", "1", "--trace", "0"],
                       env=env, capture_output=True, text=True, timeout=300,
                       cwd=str(HERE.parents[1]))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
