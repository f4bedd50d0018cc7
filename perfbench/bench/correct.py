"""What decides ``correct``: served tokens against the plain reference.

After the window, a sample of the finished requests, drawn from the seed and
holding the longest ones, is run through the reference once: each prompt
followed by the tokens the program served for it.  At every position where
the program emitted a token, the gap is the reference's best logit minus its
logit for the served token (0 when the program chose the reference's best).
The number compared is the widest gap over the sample.

Greedy decoding with the program's bf16 arithmetic picks a token within
rounding of the reference's best; a wrong KV page, a skipped write or an
altered token picks one a whole logit spread away.  The control (the
reference in fp8, see ``dense_llama``) reads the reference's gap at the token
that the fp8 pass puts first, at the same positions.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

#: where a configuration's ``reference`` module is found by name
REFERENCE_DIR = Path(__file__).resolve().parents[1] / "reference"


def load_reference(name: str):
    """The configuration's plain reference module.  It gives
    ``program_fields(cfg)`` (the program's config fields it stands for),
    ``layout(cfg)`` (see :mod:`bench.weights`), ``embed(globals, tokens)``,
    ``make_layer_fns(cfg, precision)`` (one ``(h, layer weights) -> h`` per
    layout group), ``make_head_fn(cfg, precision)`` (``(h, globals) ->
    logits``) and ``counted_work(cfg)`` (see :mod:`bench.flops`).  Weights
    are float32, by the layout's names.  Loaded once per process."""
    path = REFERENCE_DIR / f"{name}.py"
    key = f"perfbench_reference_{name}"
    mod = sys.modules.get(key)
    if mod is None or Path(mod.__file__) != path:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod  # a dataclass looks its module up there
        spec.loader.exec_module(mod)
    return mod


def sample(res: Dict, seed: int, min_tokens: int) -> List[int]:
    """Request ids to compare: the one with the most served tokens, the one
    with the longest sequence, one that hit the prefix tree and one swapped
    in mid-generation (where there are such), then others in an order drawn
    from the seed until ``min_tokens`` served tokens are covered."""
    results, reqs, skips = res["results"], res["requests"], res["skips"]
    done = [r.rid for r in reqs if "error" not in results.get(r.rid, {"error": 1})
            and len(results[r.rid]["tokens"])]
    if not done:
        return []
    n_tok = {rid: len(results[rid]["tokens"]) for rid in done}
    length = {r.rid: len(r.prompt) + n_tok.get(r.rid, 0) for r in reqs}
    picks = [max(done, key=lambda r: (n_tok[r], -r)), max(done, key=lambda r: (length[r], -r))]
    hit = [r for r in done if skips.get(r, 0) > 0]
    swapped = [r for r in done if results[r].get("swapped_in")]
    rng = np.random.default_rng((int(seed) + 7) % 2**64)
    for group in (hit, swapped):
        if group:
            picks.append(group[int(rng.integers(len(group)))])
    for rid in rng.permutation(done).tolist():
        if sum(n_tok[r] for r in set(picks)) >= min_tokens:
            break
        picks.append(rid)
    out = []
    for r in picks:
        if r not in out:
            out.append(r)
    return out


def _pow2(n: int, least: int = 1) -> int:
    return max(least, 1 << (int(n) - 1).bit_length())


def reference_gaps(cfg: Dict, seed: int, prompts: List[np.ndarray],
                   served: List[np.ndarray], *, control: bool = False,
                   rows_per_chunk: int = 256) -> Dict[str, np.ndarray]:
    """Per served token: ``gap`` of the served token in the float32
    reference and, with ``control``, ``control_gap``: the float32 gap of
    the token the fp8 pass puts first.  One layer's weights at a time, each
    through the layer function of its group."""
    ref = load_reference(cfg["reference"])
    glob, groups = ref.layout(cfg)
    dtype = cfg["torch_dtype"]
    make_layer = weights.layer_maker(glob, groups, dtype)
    make_glob = weights.global_maker(glob, dtype)
    seqs = [np.concatenate([p, s[:-1]]).astype(np.int32) for p, s in zip(prompts, served)]
    # powers of two, so that a handful of compiled shapes serve every run;
    # padding rows and trailing positions never reach a compared row
    toks = np.zeros((_pow2(len(seqs)), _pow2(max(len(x) for x in seqs), 128)), np.int32)
    for i, x in enumerate(seqs):
        toks[i, : len(x)] = x
    # rows of the hidden state at which a served token was emitted
    rows_b = np.concatenate([np.full(len(s), i) for i, s in enumerate(served)])
    rows_s = np.concatenate([len(p) - 1 + np.arange(len(s)) for p, s in zip(prompts, served)])
    target = np.concatenate(served).astype(np.int32)

    def hidden_rows(precision: str, g):
        layer_fns = ref.make_layer_fns(cfg, precision)
        h = ref.embed(g, jnp.asarray(toks))
        for layer in range(weights.n_layers(groups)):
            group, w = make_layer(seed, layer)
            h = layer_fns[group](h, w)
        return h[jnp.asarray(rows_b), jnp.asarray(rows_s)]

    def logits_chunks(precision: str):
        head_fn = ref.make_head_fn(cfg, precision)
        g = make_glob(seed)
        h = hidden_rows(precision, g)
        n = h.shape[0]
        h = jnp.pad(h, ((0, -n % rows_per_chunk), (0, 0)))
        for a in range(0, n, rows_per_chunk):
            lg = head_fn(h[a:a + rows_per_chunk], g)
            yield a, lg[: min(rows_per_chunk, n - a)]

    with jax.default_matmul_precision("highest"):
        ctl_tok = None
        if control:
            ctl_tok = np.zeros_like(target)
            for a, lg in logits_chunks("fp8"):
                ctl_tok[a:a + lg.shape[0]] = np.asarray(jnp.argmax(lg, -1))
        gap = np.zeros(len(target), np.float64)
        ctl_gap = np.zeros(len(target), np.float64)
        for a, lg in logits_chunks("float32"):
            n = lg.shape[0]
            best = jnp.max(lg, -1)
            at = jnp.take_along_axis(lg, jnp.asarray(target[a:a + n])[:, None], -1)[:, 0]
            gap[a:a + n] = np.asarray(best - at)
            if control:
                c = jnp.take_along_axis(lg, jnp.asarray(ctl_tok[a:a + n])[:, None], -1)[:, 0]
                ctl_gap[a:a + n] = np.asarray(best - c)
    out = {"gap": gap, "rows": np.stack([rows_b, rows_s], 1)}
    if control:
        out["control_gap"] = ctl_gap
    return out


def check(cfg: Dict, cell: Dict, res: Dict, seed: int, *,
          control: bool = False) -> Dict[str, object]:
    """The comparison of one run: widest gap, its limit, and what was covered."""
    picks = sample(res, seed, int(cell["check"]["min_tokens"]))
    by_rid = {r.rid: r for r in res["requests"]}
    prompts = [np.asarray(by_rid[r].prompt) for r in picks]
    served = [np.asarray(res["results"][r]["tokens"]) for r in picks]
    out: Dict[str, object] = {"requests": len(picks),
                              "tokens": int(sum(len(s) for s in served)),
                              "prefix_hits": sum(res["skips"].get(r, 0) > 0 for r in picks),
                              "swapped_in": sum(bool(res["results"][r].get("swapped_in"))
                                                for r in picks)}
    if not picks:
        out["max_logit_gap"] = None
        return out
    g = reference_gaps(cfg, seed, prompts, served, control=control)
    out["max_logit_gap"] = float(np.max(g["gap"]))
    if control:
        out["control_max_logit_gap"] = float(np.max(g["control_gap"]))
    return out
