"""Bring-up smoke run of the Forge serve path on one TPU chip.

    python chip_smoke.py

One process, three phases, seeded random weights, no downloads:

* kernels — each Pallas kernel compiled for the chip at real widths
  (phi3-mini-3.8b, a 32/8-head GQA layout, recurrentgemma-2b's RG-LRU
  width), run once and compared with its oracle in ``kernels/ref.py``;
* fidelity — forge-125m at full width in float32: the Forge
  ``segment_jit`` prefill against the unfused jit model, logits compared
  under ``jax.default_matmul_precision("highest")``;
* serve — phi3-mini-3.8b at full published width through the normal
  path (``BatchedServer(mode="forge", backend="segment_jit",
  paged=True)`` behind ``SlotScheduler``), then the Forge prefill
  logits of the served prompts against the plain ``mode="jit"``,
  ``fuse="none"`` contiguous-cache prefill on the same bf16 weights.

The timings printed are bring-up numbers from one run, not benchmark
results.  The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every check passed.
Exits non-zero without that line when no TPU is present: there is no
CPU fallback.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

SEED = 0

# -- tolerances, stated before any run ---------------------------------------
#: bf16 kernel outputs against the float32 oracle on the same inputs:
#: np.allclose(out, ref, atol, rtol) — a few bf16 ulps at unit scale
KERNEL_ATOL = KERNEL_RTOL = 2e-2
#: forge-125m, float32, highest matmul precision: max |logit difference|
#: and mean KL(ref || forge) per position.  The paper reports < 2.1e-5.
F32_MAX_ABS, F32_KL = 1e-4, 1e-8
#: phi3-mini-3.8b, bf16, 32 layers: max |logit difference| and mean KL.
#: Forge segments round every bf16 op where the whole-step jit program
#: rounds once per fusion, so the two paths differ by accumulated bf16
#: rounding (unit roundoff 2^-9), not by a wrong result (which gives KL
#: of order 1).
BF16_MAX_ABS, BF16_KL = 0.5, 5e-3

# -- the serve phase, sized for one 16 GB chip --------------------------------
SERVE_ARCH = "phi3-mini-3.8b"
MAX_LEN, MAX_SLOTS, PAGE_SIZE = 1024, 4, 16
#: four full-length slots of pages plus the reserved trash page
KV_PAGES = MAX_SLOTS * MAX_LEN // PAGE_SIZE + 1
N_REQUESTS, PROMPT_LENS, NEW_TOKENS = 8, (100, 250), (16, 32)


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write(
            f"chip_smoke: no TPU present (JAX found {dev.platform!r}); "
            "this run needs the chip and has no CPU fallback\n"
        )
        raise SystemExit(1)
    return dev


def init_params(cfg, seed: int):
    """Seeded weights, built on the device in one program: no float32
    copy of a stacked weight sits beside the bf16 parameters."""
    import jax

    from repro.models import get_model

    model = get_model(cfg)
    return jax.jit(model.init, static_argnums=1)(jax.random.PRNGKey(seed), cfg)


def logit_gap(ref, out, lens):
    """(max |ref - out|, mean KL(ref || out)) over each row's valid
    positions; logits are (B, S, V)."""
    import jax
    import jax.numpy as jnp

    ref = jnp.asarray(ref, jnp.float32)
    out = jnp.asarray(out, jnp.float32)
    valid = jnp.arange(ref.shape[1])[None, :] < jnp.asarray(lens)[:, None]
    diff = jnp.max(jnp.where(valid[..., None], jnp.abs(ref - out), 0.0))
    lp, lq = jax.nn.log_softmax(ref, -1), jax.nn.log_softmax(out, -1)
    kl = jnp.sum(jnp.exp(lp) * (lp - lq), -1)
    kl = jnp.sum(jnp.where(valid, kl, 0.0)) / jnp.sum(valid)
    return float(diff), float(kl)


def pad_prompts(prompts, width: int) -> np.ndarray:
    out = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        out[i, : len(p)] = p
    return out


def forge_prefill_logits(srv, tokens: np.ndarray):
    """Logits of the server's own paged Forge prefill program for a
    (B, S) block starting at position 0, row b on pages 1 + b*MP ..."""
    import jax.numpy as jnp

    B, S = tokens.shape
    srv.warmup([B], prompt_lens=[S])  # builds the fronts; no-op when warm
    MP = srv.max_pages_per_slot
    table = 1 + np.arange(B * MP, dtype=np.int32).reshape(B, MP)
    logits, _ = srv.prefill_bucketed(
        srv.params, srv.page_store, jnp.asarray(table), jnp.asarray(tokens),
        jnp.zeros((B,), jnp.int32), jnp.ones((B,), bool),
    )
    return logits


def jit_prefill_logits(cfg, params, tokens: np.ndarray):
    """The plain path: one ``jax.jit`` prefill of the unfused model into
    a contiguous KV cache."""
    import jax
    import jax.numpy as jnp

    from repro.models import get_model

    ref_cfg = cfg.with_(fuse="none")
    model = get_model(ref_cfg)
    B, S = tokens.shape
    cache = model.init_cache(ref_cfg, B, S)
    step = jax.jit(
        lambda p, c, t: model.prefill_step(p, c, t, jnp.int32(0), ref_cfg)[0],
        donate_argnums=(1,),
    )
    return step(params, cache, jnp.asarray(tokens))


def paged_server(cfg, params, *, max_len: int, kv_pages: int):
    from repro.launch.serve import BatchedServer

    return BatchedServer(
        cfg, params, max_len=max_len, mode="forge", backend="segment_jit",
        paged=True, kv_page_size=PAGE_SIZE, kv_pages=kv_pages,
    )


def make_prompts(rng, vocab: int, n: int):
    lo, hi = PROMPT_LENS
    return [rng.integers(0, vocab, (int(rng.integers(lo, hi + 1)),)).astype(np.int32)
            for _ in range(n)]


# -- phases --------------------------------------------------------------------


def kernel_cases(rng):
    """(name, kernel call, float32 oracle, args) at real widths."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.fused_linear import fused_linear_pallas
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.rg_lru import rg_lru_chunked, rg_lru_pallas
    from repro.kernels.rms_norm import rms_norm_pallas

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.bfloat16)

    def gate(*shape):  # recurrence gates in (0, 1)
        return jnp.asarray(1.0 / (1.0 + np.exp(-rng.standard_normal(shape) - 2.0)),
                           jnp.bfloat16)

    B, S = 4, 512
    q, k, v = (normal(B, 32, S, 96) for _ in range(3))
    gq, gk, gv = normal(B, 32, S, 128), normal(B, 8, S, 128), normal(B, 8, S, 128)
    MP = MAX_LEN // PAGE_SIZE
    table = 1 + rng.permutation(B * MP).astype(np.int32).reshape(B, MP)
    pos = rng.integers(0, MAX_LEN, (B,)).astype(np.int32)
    pq = normal(B, 32, 96)
    pk, pv = normal(KV_PAGES, 32, PAGE_SIZE, 96), normal(KV_PAGES, 32, PAGE_SIZE, 96)
    x2, w = normal(2048, 3072), normal(3072, 8192, scale=3072 ** -0.5)
    nw = normal(3072, scale=0.1) + 1.0
    rx, ra, rh = normal(B, S, 2560), gate(B, S, 2560), normal(B, 2560)
    return [
        ("flash_attention", lambda q, k, v: flash_attention(q, k, v, causal=True),
         lambda q, k, v: ref.sdpa_ref(q, k, v, causal=True), (q, k, v)),
        ("flash_attention_gqa",
         lambda q, k, v: flash_attention(q, k, v, causal=True, groups=4),
         lambda q, k, v: ref.sdpa_ref(q, k, v, causal=True), (gq, gk, gv)),
        ("paged_attention", paged_attention, ref.paged_sdpa_ref,
         (pq, pk, pv, jnp.asarray(table), jnp.asarray(pos))),
        ("fused_linear", lambda x, w: fused_linear_pallas(x, w, act="silu"),
         lambda x, w: ref.fused_linear_ref(x, w, act="silu"), (x2, w)),
        ("rms_norm", rms_norm_pallas, ref.rms_norm_ref, (x2, nw)),
        ("rg_lru", rg_lru_pallas, ref.rg_lru_ref, (rx, ra, rh)),
        ("rg_lru_chunked", rg_lru_chunked, ref.rg_lru_chunk_ref, (rx, ra, rh)),
    ]


def kernel_phase(rng) -> bool:
    import jax
    import jax.numpy as jnp

    ok = True
    for name, kernel, oracle, args in kernel_cases(rng):
        t0 = time.perf_counter()
        compiled = jax.jit(kernel).lower(*args).compile()
        has_kernel = "tpu_custom_call" in compiled.as_text()
        outs = jax.block_until_ready(compiled(*args))
        t_run = time.perf_counter() - t0
        up = [a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a for a in args]
        with jax.default_matmul_precision("highest"):
            refs = jax.jit(oracle)(*up)
        worst, close = 0.0, True
        for o, r in zip(jax.tree_util.tree_leaves(outs),
                        jax.tree_util.tree_leaves(refs)):
            o, r = np.asarray(o, np.float32), np.asarray(r, np.float32)
            worst = max(worst, float(np.max(np.abs(o - r))))
            close = close and np.allclose(o, r, atol=KERNEL_ATOL, rtol=KERNEL_RTOL)
        passed = has_kernel and close
        ok = ok and passed
        log(f"[kernel] {name}: tpu_custom_call={has_kernel} "
            f"max_abs_diff={worst!r} compile+run_s={t_run!r} "
            f"{'ok' if passed else 'FAILED'}")
    return ok


def fidelity_f32_phase(rng) -> bool:
    """forge-125m at full width in float32: Forge segment_jit prefill
    logits against the unfused jit model, highest matmul precision."""
    import jax

    from repro.configs import get_config

    cfg = get_config("forge-125m").with_(dtype="float32")
    max_len = 256
    with jax.default_matmul_precision("highest"):
        params = init_params(cfg, SEED)
        prompts = make_prompts(rng, cfg.vocab, 4)
        lens = [len(p) for p in prompts]
        tokens = pad_prompts(prompts, max_len)
        srv = paged_server(cfg, params, max_len=max_len,
                           kv_pages=4 * max_len // PAGE_SIZE + 1)
        out = forge_prefill_logits(srv, tokens)
        ref = jit_prefill_logits(cfg, params, tokens)
        diff, kl = logit_gap(ref, out, lens)
    ok = diff <= F32_MAX_ABS and kl <= F32_KL
    log(f"[fidelity] forge-125m float32 segment_jit vs unfused jit: "
        f"max_abs_logit_diff={diff!r} (tol {F32_MAX_ABS}, paper < 2.1e-5) "
        f"mean_kl={kl!r} (tol {F32_KL}) {'ok' if ok else 'FAILED'}")
    return ok


def serve_phase(rng, dev) -> bool:
    import jax

    from repro.configs import get_config
    from repro.launch.serve import Request, SlotScheduler

    cfg = get_config(SERVE_ARCH)
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(cfg, SEED))
    log(f"[serve] {cfg.name}: {cfg.n_layers}L d_model={cfg.d_model} "
        f"heads={cfg.n_heads}x{cfg.head_dim_} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab} {cfg.dtype}; init_s={time.perf_counter() - t0!r}")
    srv = paged_server(cfg, params, max_len=MAX_LEN, kv_pages=KV_PAGES)
    sched = SlotScheduler(srv, max_slots=MAX_SLOTS)
    prompts = make_prompts(rng, cfg.vocab, N_REQUESTS)
    reqs = [Request(rid=i, prompt=p,
                    max_new=int(rng.integers(NEW_TOKENS[0], NEW_TOKENS[1] + 1)))
            for i, p in enumerate(prompts)]
    warm_s = sched.warmup(prompt_lens=sorted({len(p) for p in prompts}))
    log(f"[serve] warmup (Forge Phases 1-4 + XLA compile of every rung and "
        f"prefill cell) compile_s={warm_s!r}")
    res = sched.run(reqs)
    results = res["results"]
    errors = {rid: r["error"] for rid, r in results.items() if "error" in r}
    short = [r.rid for r in reqs if r.rid not in errors
             and len(results.get(r.rid, {}).get("tokens", ())) != r.max_new]
    ok = (len(results) == len(reqs) and not errors and not short
          and res["tick_failures"] == 0 and res["dispatch_retries"] == 0
          and res["aborted"] is False)
    log(f"[serve] requests={len(reqs)} completed={len(results) - len(errors)} "
        f"errors={errors} short={short} tick_failures={res['tick_failures']} "
        f"dispatch_retries={res['dispatch_retries']} aborted={res['aborted']} "
        f"compiles_post_warmup={res['compiles']} swaps={res['swaps']}")
    log(f"[serve] bring-up timings, one run, not a benchmark: "
        f"ttft_p50_s={res['ttft_p50_s']!r} ttft_p99_s={res['ttft_p99_s']!r} "
        f"tok_per_s={res['tok_per_s']!r}")

    # fidelity (a): the served prompts through the Forge prefill program
    # against the plain jit prefill, same bf16 weights
    sample = prompts[:MAX_SLOTS]
    lens = [len(p) for p in sample]
    width = srv._seq_bucket_extent(max(lens))
    tokens = pad_prompts(sample, width)
    out = forge_prefill_logits(srv, tokens)
    ref = jit_prefill_logits(cfg, params, tokens)
    diff, kl = logit_gap(ref, out, lens)
    fid_ok = diff <= BF16_MAX_ABS and kl <= BF16_KL
    log(f"[fidelity] {cfg.name} bf16 forge prefill vs jit fuse=none contiguous: "
        f"max_abs_logit_diff={diff!r} (tol {BF16_MAX_ABS}) mean_kl={kl!r} "
        f"(tol {BF16_KL}) {'ok' if fid_ok else 'FAILED'}")
    stats = dev.memory_stats() or {}
    log(f"[serve] peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")
    return ok and fid_ok


def main() -> int:
    dev = require_tpu()
    import jax

    from repro.launch.serve import setup_jax_compile_cache

    cache = setup_jax_compile_cache()
    count = len(jax.devices())
    log(f"[device] platform={dev.platform} kind={dev.device_kind} count={count} "
        f"jax={jax.__version__} compile_cache={cache}")
    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    phases = {}
    for name, phase in (("kernels", lambda: kernel_phase(rng)),
                        ("fidelity_f32", lambda: fidelity_f32_phase(rng)),
                        ("serve", lambda: serve_phase(rng, dev))):
        t = time.perf_counter()
        phases[name] = phase()
        log(f"[phase] {name}: {'ok' if phases[name] else 'FAILED'} "
            f"wall_s={time.perf_counter() - t!r}")
    log(f"[done] wall_s={time.perf_counter() - t0!r}")
    if not all(phases.values()):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
