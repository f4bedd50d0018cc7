"""Decoder-only transformer LM (dense + MoE + VLM backbones).

The block body is written unfused; when ``cfg.fuse == 'forge'`` it is
captured and optimized by the Forge pipeline once per (config, shape) and
the resulting executor is scanned over the layer-stacked parameters —
keeping the HLO small enough for 512-way GSPMD while the fusion happens
inside the block exactly as the paper prescribes.

Entry points:

* ``init(key, cfg)``                         — parameter pytree
* ``apply(params, tokens, cfg, ...)``        — full-sequence logits
  (training forward / inference prefill)
* ``init_cache(cfg, batch, max_len)``        — stacked KV cache
* ``decode_step(params, cache, tok, pos, cfg)`` — one-token serve step
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..configs.base import ModelConfig
from . import attention as A
from . import layers as L
from . import moe as MOE

Params = Dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def block_init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 4)
    dt = _dtype(cfg)
    p: Params = {
        "norm1": L.norm_init(cfg.d_model, cfg.norm),
        "attn": A.attn_init(
            ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_,
            qkv_bias=cfg.qkv_bias, dtype=dt,
        ),
        "norm2": L.norm_init(cfg.d_model, cfg.norm),
    }
    if cfg.family == "moe":
        p["moe"] = MOE.moe_init(
            ks[1], cfg.d_model, cfg.d_ff, cfg.n_experts,
            shared_experts=cfg.shared_experts, shared_d_ff=cfg.shared_d_ff,
            dtype=dt,
        )
    else:
        p["ffn"] = L.ffn_init(
            ks[1], cfg.d_model, cfg.d_ff, cfg.ffn, bias=cfg.ffn_bias, dtype=dt
        )
    return p


def init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 3)
    dt = _dtype(cfg)
    emb = L.embed_init(ks[0], cfg.vocab, cfg.d_model, dt)
    if cfg.scan_layers:
        blocks = jax.vmap(lambda k: block_init(k, cfg))(
            jax.random.split(ks[1], cfg.n_layers)
        )
    else:
        blocks = [
            block_init(k, cfg) for k in jax.random.split(ks[1], cfg.n_layers)
        ]
    params: Params = {
        "embed": emb,
        "blocks": blocks,
        "final_norm": L.norm_init(cfg.d_model, cfg.norm),
    }
    if not cfg.tie_embeddings:
        # tied configs store ONE copy; apply() reuses params["embed"]
        # (donation-safe; Phase-1's id()-dedup covers user-tied pytrees)
        params["lm_head"] = L.dense_init(ks[2], cfg.d_model, cfg.vocab, dt)
    return params


# --------------------------------------------------------------------------
# block bodies (the Forge capture targets)
# --------------------------------------------------------------------------


def block_apply(
    p: Params,
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    cfg: ModelConfig,
) -> jax.Array:
    h = L.apply_norm(x, p["norm1"], cfg.norm)
    attn_out, _ = A.attention(
        h, p["attn"], n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        rope_cos=cos, rope_sin=sin, causal=True,
    )
    x = x + attn_out
    h = L.apply_norm(x, p["norm2"], cfg.norm)
    if cfg.family == "moe":
        ffn_out = MOE.moe_ffn(
            h, p["moe"], n_experts=cfg.n_experts, top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor,
        )
    else:
        ffn_out = L.apply_ffn(h, p["ffn"], cfg.ffn)
    return x + ffn_out


def block_decode(
    p: Params,
    x: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    cfg: ModelConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    h = L.apply_norm(x, p["norm1"], cfg.norm)
    attn_out, new_cache = A.attention(
        h, p["attn"], n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        rope_cos=cos, rope_sin=sin,
        cache={"k": k_cache, "v": v_cache}, cache_pos=pos,
    )
    x = x + attn_out
    h = L.apply_norm(x, p["norm2"], cfg.norm)
    if cfg.family == "moe":
        ffn_out = MOE.moe_ffn(
            h, p["moe"], n_experts=cfg.n_experts, top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor,
        )
    else:
        ffn_out = L.apply_ffn(h, p["ffn"], cfg.ffn)
    return x + ffn_out, new_cache["k"], new_cache["v"]


def block_paged_decode(
    p: Params,
    x: jax.Array,
    k_store: jax.Array,  # (n_layers, num_pages, page_size, KVH * D)
    v_store: jax.Array,
    layer: jax.Array,  # int32 scalar: this block's layer in the store
    page_table: jax.Array,  # (B, max_pages) int32, shared by all layers
    pos: jax.Array,  # scalar or per-row (B,) write position
    write_mask: jax.Array,  # bool (B,) — rows allowed to write (slot mask)
    cos: jax.Array,
    sin: jax.Array,
    cfg: ModelConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Block body against the paged KV pool (decode and chunked prefill).

    The body takes the whole stacked store and its ``layer`` index and
    returns the whole store with this step's tokens written into that
    layer's rows, so the layer loop carries the store and XLA updates it
    in place (no per-layer slab is sliced out or written back).

    Unlike :func:`block_decode`, the slot mask rides *inside* the body:
    the page store has no batch axis to gate post hoc, so inactive rows'
    writes are routed to the trash page by the scatter itself."""
    with jax.named_scope("attn"):
        h = L.apply_norm(x, p["norm1"], cfg.norm)
        attn_out, new_cache = A.attention(
            h, p["attn"], n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            rope_cos=cos, rope_sin=sin,
            cache={"k_pages": k_store, "v_pages": v_store,
                   "page_table": page_table},
            cache_pos=pos, kv_layer=layer, write_mask=write_mask,
            kv_kernel=cfg.kv_kernel,
        )
        x = x + attn_out
    with jax.named_scope("mlp"):
        h = L.apply_norm(x, p["norm2"], cfg.norm)
        if cfg.family == "moe":
            ffn_out = MOE.moe_ffn(
                h, p["moe"], n_experts=cfg.n_experts, top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor,
            )
        else:
            ffn_out = L.apply_ffn(h, p["ffn"], cfg.ffn)
        x = x + ffn_out
    return x, new_cache["k_pages"], new_cache["v_pages"]


# --------------------------------------------------------------------------
# Forge integration: compile the block body once per (cfg, shapes)
# --------------------------------------------------------------------------

from ._forge import forge_body  # noqa: E402  (shared across families)


def _body_fn(cfg: ModelConfig, mode: str, example_args) -> Any:
    enabled = cfg.fuse == "forge"
    if mode.startswith("paged_"):
        base = block_paged_decode
        # the pallas kernel is itself the fused dispatch: capturing a
        # pallas_call through the Phase-1 tracer buys nothing and the
        # passes don't know the primitive — run the body raw
        enabled = enabled and cfg.kv_kernel == "ref"
        mode = f"{mode}[{cfg.kv_kernel}]"  # keep body-cache keys distinct
    else:
        base = block_apply if mode == "apply" else block_decode

    def raw(*args):
        return base(*args, cfg=cfg)

    return forge_body(
        raw, f"{cfg.name}/{mode}", example_args,
        enabled=enabled, remat=cfg.remat,
    )


# --------------------------------------------------------------------------
# forward paths
# --------------------------------------------------------------------------


def _positions_default(B: int, S: int) -> jax.Array:
    return jnp.arange(S, dtype=jnp.int32)


def _rope_for(cfg: ModelConfig, positions: jax.Array,
              mrope_positions: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    if cfg.family == "vlm" and mrope_positions is not None:
        return L.mrope_tables(
            mrope_positions, cfg.head_dim_, cfg.mrope_sections, cfg.rope_theta
        )
    return L.rope_tables(positions, cfg.head_dim_, cfg.rope_theta)


def apply(
    params: Params,
    tokens: Optional[jax.Array],
    cfg: ModelConfig,
    *,
    embeds: Optional[jax.Array] = None,
    mrope_positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Full-sequence forward: (B, S) tokens [or (B, S, D) embeds] → logits."""
    if embeds is None:
        x = L.embed(tokens, params["embed"])
    else:
        x = embeds
    B, S, _ = x.shape
    cos, sin = _rope_for(cfg, _positions_default(B, S), mrope_positions)

    one_block = (
        jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
        if cfg.scan_layers else params["blocks"][0]
    )
    body = _body_fn(cfg, "apply", (one_block, x, cos, sin))

    if cfg.scan_layers:
        def step(carry, p_layer):
            return body(p_layer, carry, cos, sin), None

        x, _ = lax.scan(step, x, params["blocks"])
    else:
        for p_layer in params["blocks"]:
            x = body(p_layer, x, cos, sin)

    x = L.apply_norm(x, params["final_norm"], cfg.norm)
    return L.lm_head(x, params.get("lm_head", params["embed"]), transpose=cfg.tie_embeddings)


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int
) -> Dict[str, jax.Array]:
    dt = _dtype(cfg)
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, max_len, cfg.head_dim_)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def init_paged_cache(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    *,
    num_pages: int,
    page_size: int,
) -> Dict[str, jax.Array]:
    """Paged decode state: a page store per K and V plus one page table
    shared by every layer (a logical page holds all layers' K/V for its
    token block, so the allocator hands out one index per block).

    Each store is ``(n_layers, num_pages, page_size, n_kv_heads *
    head_dim)``: token-major rows, one row holding one token's K (or V)
    for every head.  A step writes whole rows (all heads of a token at
    once), and the flat row keeps the minor dimension a multiple of 128
    lanes where a head alone (96 at phi3) is not, so the store is never
    padded or re-laid out around the write.

    Page 0 is the reserved trash page (see core/paging.py): a zero-filled
    table points every slot there, masked/pad writes scatter there, and
    the length masks keep whatever accumulates in it out of the softmax.
    """
    if max_len % page_size:
        raise ValueError(f"max_len {max_len} not a multiple of page_size {page_size}")
    dt = _dtype(cfg)
    shape = (cfg.n_layers, num_pages, page_size, cfg.n_kv_heads * cfg.head_dim_)
    return {
        "k_pages": jnp.zeros(shape, dt),
        "v_pages": jnp.zeros(shape, dt),
        "page_table": jnp.zeros((batch, max_len // page_size), jnp.int32),
    }


def supports_batched_prefill(cfg: ModelConfig) -> bool:
    """Whole-block prefill reproduces sequential decode only when no op
    couples tokens across the (B, S) block — false for MoE, whose
    capacity routing is first-come-first-served over the flattened
    token stream (see :func:`prefill_step`)."""
    return cfg.family != "moe"


def _cached_forward(
    params: Params,
    cache: Dict[str, jax.Array],
    x: jax.Array,  # (B, S, D) embedded inputs
    pos: jax.Array,  # int32 — cache write position, scalar or per-row (B,)
    cos: jax.Array,
    sin: jax.Array,
    cfg: ModelConfig,
    mode: str,
    slot_mask: Optional[jax.Array] = None,  # bool (B,) — active decode slots
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Shared decode/prefill scaffold: layer loop over the block-decode
    body against the KV cache, final norm, LM head.  ``mode`` keys the
    forge_body compile cache ("decode" vs "prefill").

    ``slot_mask`` gates the cache update per batch row (outside the
    compiled block body, so the body graph is mask-free): inactive rows
    keep their previous KV bitwise — write-inert even under NaN inputs
    (see :func:`~repro.models.layers.slot_gate`).
    """
    one_block = (
        jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
        if cfg.scan_layers else params["blocks"][0]
    )
    k0, v0 = cache["k"][0], cache["v"][0]
    body = _body_fn(cfg, mode, (one_block, x, k0, v0, pos, cos, sin))

    if cfg.scan_layers:
        def step(carry, xs):
            p_layer, kc, vc = xs
            y, nk, nv = body(p_layer, carry, kc, vc, pos, cos, sin)
            nk = L.slot_gate(slot_mask, nk, kc)
            nv = L.slot_gate(slot_mask, nv, vc)
            return y, (nk, nv)

        x, (new_k, new_v) = lax.scan(
            step, x, (params["blocks"], cache["k"], cache["v"])
        )
    else:
        ks, vs = [], []
        for i, p_layer in enumerate(params["blocks"]):
            x, nk, nv = body(p_layer, x, cache["k"][i], cache["v"][i],
                             pos, cos, sin)
            ks.append(L.slot_gate(slot_mask, nk, cache["k"][i]))
            vs.append(L.slot_gate(slot_mask, nv, cache["v"][i]))
        new_k, new_v = jnp.stack(ks), jnp.stack(vs)

    x = L.apply_norm(x, params["final_norm"], cfg.norm)
    logits = L.lm_head(x, params.get("lm_head", params["embed"]), transpose=cfg.tie_embeddings)
    return logits, {"k": new_k, "v": new_v}


def _paged_cached_forward(
    params: Params,
    cache: Dict[str, jax.Array],
    x: jax.Array,  # (B, S, D) embedded inputs
    pos: jax.Array,  # int32 write position, scalar or per-row (B,)
    cos: jax.Array,
    sin: jax.Array,
    cfg: ModelConfig,
    mode: str,
    slot_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """:func:`_cached_forward` against the paged KV pool.  The page table
    is read-only inside the model (allocation is host-side, in the serve
    layer); the slot mask rides inside the body because the batch-free
    page store cannot be gated per row after the fact.

    The stacked store rides in the layer loop's carry, beside ``x``, and
    each block writes its tokens into its own layer's rows of it in
    place: the loop's ``xs`` are the layer weights and the layer index
    only, so no layer's slab is sliced out of the store or stacked back
    into a new one."""
    B = x.shape[0]
    mask = (jnp.ones((B,), jnp.bool_) if slot_mask is None
            else jnp.asarray(slot_mask, jnp.bool_))
    pt = cache["page_table"]
    one_block = (
        jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
        if cfg.scan_layers else params["blocks"][0]
    )
    k_store, v_store = cache["k_pages"], cache["v_pages"]
    layer0 = jnp.zeros((), jnp.int32)
    body = _body_fn(cfg, mode, (one_block, x, k_store, v_store, layer0, pt,
                                pos, mask, cos, sin))

    if cfg.scan_layers:
        def step(carry, xs):
            p_layer, layer = xs
            return body(p_layer, *carry, layer, pt, pos, mask, cos, sin), None

        layers = jnp.arange(cfg.n_layers, dtype=jnp.int32)
        (x, k_store, v_store), _ = lax.scan(
            step, (x, k_store, v_store), (params["blocks"], layers)
        )
    else:
        for i, p_layer in enumerate(params["blocks"]):
            x, k_store, v_store = body(p_layer, x, k_store, v_store,
                                       jnp.int32(i), pt, pos, mask, cos, sin)

    with jax.named_scope("logits"):
        x = L.apply_norm(x, params["final_norm"], cfg.norm)
        logits = L.lm_head(x, params.get("lm_head", params["embed"]),
                           transpose=cfg.tie_embeddings)
    return logits, {"k_pages": k_store, "v_pages": v_store, "page_table": pt}


def paged_decode_step(
    params: Params,
    cache: Dict[str, jax.Array],
    token: jax.Array,  # (B, 1) int32
    pos: jax.Array,  # int32 write position — scalar or per-row (B,)
    cfg: ModelConfig,
    *,
    slot_mask: Optional[jax.Array] = None,  # bool (B,): active slots
    embeds: Optional[jax.Array] = None,
    mrope_positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """:func:`decode_step` against the paged KV pool — same logits,
    bitwise, on active rows (tests/test_paged_kv.py holds the line)."""
    if embeds is None:
        x = L.embed(token, params["embed"])
    else:
        x = embeds
    cos, sin = _rope_for(cfg, L.decode_positions(pos), mrope_positions)
    return _paged_cached_forward(params, cache, x, pos, cos, sin, cfg,
                                 "paged_decode", slot_mask=slot_mask)


def paged_prefill_step(
    params: Params,
    cache: Dict[str, jax.Array],
    tokens: jax.Array,  # (B, S) int32 — a whole (padded) prompt block
    pos: jax.Array,  # int32 first write position — scalar or per-row (B,)
    cfg: ModelConfig,
    *,
    slot_mask: Optional[jax.Array] = None,  # bool (B,): rows to prefill
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """:func:`prefill_step` against the paged KV pool.

    Beyond the contiguous version, ``pos`` may be per-row (B,): each row
    anchors its chunk at its own start position.  That is the prefix-
    reuse entry point — a row whose leading pages came from the prefix
    tree prefills only the suffix, with ``pos`` at its skip offset, in
    the same dispatch as rows starting from zero."""
    if cfg.family == "moe":
        raise NotImplementedError(
            "MoE capacity routing couples tokens across the block; "
            "prefill sequentially through paged_decode_step"
        )
    x = L.embed(tokens, params["embed"])
    S = x.shape[1]
    offs = jnp.arange(S, dtype=jnp.int32)
    positions = (pos[:, None] + offs) if getattr(pos, "ndim", 0) == 1 else pos + offs
    cos, sin = _rope_for(cfg, positions, None)
    return _paged_cached_forward(params, cache, x, pos, cos, sin, cfg,
                                 "paged_prefill", slot_mask=slot_mask)


def decode_step(
    params: Params,
    cache: Dict[str, jax.Array],
    token: jax.Array,  # (B, 1) int32
    pos: jax.Array,  # int32 write position — scalar or per-row (B,)
    cfg: ModelConfig,
    *,
    slot_mask: Optional[jax.Array] = None,  # bool (B,): active slots
    embeds: Optional[jax.Array] = None,
    mrope_positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One serve step: logits for the next token + updated cache.

    With ``pos`` a per-row vector, every batch row decodes at its own
    position (per-row RoPE rotation, KV write and causal mask) — the
    primitive behind slot-level continuous batching.  ``slot_mask``
    additionally freezes inactive rows' cache updates (their logits are
    garbage and must be ignored by the caller).
    """
    if embeds is None:
        x = L.embed(token, params["embed"])
    else:
        x = embeds
    cos, sin = _rope_for(cfg, L.decode_positions(pos), mrope_positions)
    return _cached_forward(params, cache, x, pos, cos, sin, cfg, "decode",
                           slot_mask=slot_mask)


def prefill_step(
    params: Params,
    cache: Dict[str, jax.Array],
    tokens: jax.Array,  # (B, S) int32 — a whole (padded) prompt block
    pos: jax.Array,  # scalar int32 — first write position
    cfg: ModelConfig,
    *,
    slot_mask: Optional[jax.Array] = None,  # bool (B,): rows to prefill
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Whole-prompt batched prefill: one forward pass writes the S-token
    block into the KV cache at ``[pos, pos + S)``.

    Equivalent to S sequential :func:`decode_step` calls (the causal
    length mask inside :func:`~repro.models.attention.attention` keeps
    query i from seeing keys beyond ``pos + i``) but dispatches one
    program instead of S — time-to-first-token stops scaling with
    per-token dispatch count.  Returns the full (B, S, vocab) logits
    (the serve path reads the last *valid* column) plus the updated
    cache.

    ``slot_mask`` restricts the cache write to the marked rows — the
    slot scheduler's mid-generation swap-in prefills a queued prompt
    into a finished slot's KV rows while every other slot's cache stays
    bitwise untouched.
    """
    if cfg.family == "moe":
        # capacity routing is first-come-first-served over the flattened
        # token stream: a (B, S) block routes/evicts differently than S
        # single steps, diverging far beyond the 1e-5 fidelity bound
        raise NotImplementedError(
            "MoE capacity routing couples tokens across the block; "
            "prefill sequentially through decode_step"
        )
    x = L.embed(tokens, params["embed"])
    S = x.shape[1]
    positions = pos + jnp.arange(S, dtype=jnp.int32)
    cos, sin = _rope_for(cfg, positions, None)
    return _cached_forward(params, cache, x, pos, cos, sin, cfg, "prefill",
                           slot_mask=slot_mask)
