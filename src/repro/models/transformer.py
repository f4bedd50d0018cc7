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
from typing import Any, Dict, List, Optional, Tuple

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


def block_init(key, cfg: ModelConfig, dense: bool = False) -> Params:
    """One block; ``dense`` gives an MoE config's leading dense layer."""
    ks = jax.random.split(key, 4)
    dt = _dtype(cfg)
    if cfg.mla:
        attn = A.mla_init(
            ks[0], cfg.d_model, cfg.n_heads, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, dtype=dt,
        )
    else:
        attn = A.attn_init(
            ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_,
            qkv_bias=cfg.qkv_bias, dtype=dt,
        )
    p: Params = {
        "norm1": L.norm_init(cfg.d_model, cfg.norm),
        "attn": attn,
        "norm2": L.norm_init(cfg.d_model, cfg.norm),
    }
    if cfg.family == "moe" and not dense:
        p["moe"] = MOE.moe_init(
            ks[1], cfg.d_model, cfg.d_ff, cfg.n_experts, held=cfg.n_held,
            shared_experts=cfg.shared_experts, shared_d_ff=cfg.shared_d_ff,
            dtype=dt,
        )
    else:
        p["ffn"] = L.ffn_init(
            ks[1], cfg.d_model, cfg.dense_d_ff if dense else cfg.d_ff, cfg.ffn,
            bias=cfg.ffn_bias, dtype=dt,
        )
    return p


def layer_groups(cfg: ModelConfig) -> List[Tuple[str, int, int]]:
    """(params key, first layer, layers) of each stacked layer group, in
    layer order: the leading dense layers of an MoE config, then the rest.
    Layer indices (the KV store's) run across the groups."""
    F = cfg.first_dense_layers
    groups = [("dense_blocks", 0, F)] if F else []
    return groups + [("blocks", F, cfg.n_layers - F)]


def init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 3)
    dt = _dtype(cfg)
    emb = L.embed_init(ks[0], cfg.vocab, cfg.d_model, dt)
    keys = jax.random.split(ks[1], cfg.n_layers)
    params: Params = {"embed": emb}
    for name, first, n in layer_groups(cfg):
        dense = name == "dense_blocks"
        if cfg.scan_layers:
            params[name] = jax.vmap(lambda k: block_init(k, cfg, dense))(
                keys[first:first + n])
        else:
            params[name] = [block_init(k, cfg, dense) for k in keys[first:first + n]]
    params["final_norm"] = L.norm_init(cfg.d_model, cfg.norm)
    if not cfg.tie_embeddings:
        # tied configs store ONE copy; apply() reuses params["embed"]
        # (donation-safe; Phase-1's id()-dedup covers user-tied pytrees)
        params["lm_head"] = L.dense_init(ks[2], cfg.d_model, cfg.vocab, dt)
    return params


# --------------------------------------------------------------------------
# block bodies (the Forge capture targets)
# --------------------------------------------------------------------------


def _attention(p: Params, h: jax.Array, cos, sin, cfg: ModelConfig, **kw):
    """The attention sub-layer of ``cfg``: MLA or multi-head attention."""
    if cfg.mla:
        if kw.pop("kv_kernel", "ref") != "ref":
            raise NotImplementedError("latent attention has no paged kernel: kv_kernel='ref'")
        return A.mla_attention(
            h, p, n_heads=cfg.n_heads, nope=cfg.qk_nope_head_dim,
            rope_cos=cos, rope_sin=sin, scale=mla_softmax_scale(cfg), **kw,
        )
    return A.attention(
        h, p, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        rope_cos=cos, rope_sin=sin, **kw,
    )


def _ffn(p: Params, h: jax.Array, cfg: ModelConfig):
    """(output, the MoE layer's picks or None): the MoE layer where the
    block has one, else its dense FFN."""
    if "moe" in p:
        return MOE.moe_ffn(
            h, p["moe"], top_k=cfg.top_k,
            norm_topk_prob=cfg.norm_topk_prob, scaling=cfg.routed_scaling_factor,
        )
    return L.apply_ffn(h, p["ffn"], cfg.ffn), None


def block_apply(
    p: Params,
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    cfg: ModelConfig,
) -> jax.Array:
    h = L.apply_norm(x, p["norm1"], cfg.norm)
    attn_out, _ = _attention(p["attn"], h, cos, sin, cfg)
    x = x + attn_out
    h = L.apply_norm(x, p["norm2"], cfg.norm)
    return x + _ffn(p, h, cfg)[0]


def block_decode(
    p: Params,
    x: jax.Array,
    cache: Dict[str, jax.Array],  # this layer's rows of the contiguous cache
    pos: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    cfg: ModelConfig,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    h = L.apply_norm(x, p["norm1"], cfg.norm)
    attn_out, new_cache = _attention(p["attn"], h, cos, sin, cfg,
                                     cache=cache, cache_pos=pos)
    x = x + attn_out
    h = L.apply_norm(x, p["norm2"], cfg.norm)
    return x + _ffn(p, h, cfg)[0], new_cache


def block_paged_decode(
    p: Params,
    x: jax.Array,
    store: Dict[str, jax.Array],  # every leaf (n_layers, num_pages, ...)
    layer: jax.Array,  # int32 scalar: this block's layer in the store
    page_table: jax.Array,  # (B, max_pages) int32, shared by all layers
    pos: jax.Array,  # scalar or per-row (B,) write position
    write_mask: jax.Array,  # bool (B,) — rows allowed to write (slot mask)
    cos: jax.Array,
    sin: jax.Array,
    count_mask: Optional[jax.Array] = None,  # bool (B, S): tokens the counter counts
    *,
    cfg: ModelConfig,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Block body against the paged KV pool (decode and chunked prefill).

    The body takes the whole stacked store and its ``layer`` index and
    returns the whole store with this step's tokens written into that
    layer's rows, so the layer loop carries the store and XLA updates it
    in place (no per-layer slab is sliced out or written back).

    Unlike :func:`block_decode`, the slot mask rides *inside* the body:
    the page store has no batch axis to gate post hoc, so inactive rows'
    writes are routed to the trash page by the scatter itself.  An MoE
    block also adds its held picks of the tokens in ``count_mask`` (the
    real columns of the active rows) to the store's ``expert_load``
    counter (see :func:`init_paged_cache`)."""
    with jax.named_scope("attn"):
        h = L.apply_norm(x, p["norm1"], cfg.norm)
        attn_out, new_cache = _attention(
            p["attn"], h, cos, sin, cfg,
            cache={**{k: v for k, v in store.items() if k != EXPERT_LOAD},
                   "page_table": page_table},
            cache_pos=pos, kv_layer=layer, write_mask=write_mask,
            kv_kernel=cfg.kv_kernel,
        )
        x = x + attn_out
    store = {**store, **new_cache}
    with jax.named_scope("mlp"):
        h = L.apply_norm(x, p["norm2"], cfg.norm)
        ffn_out, picks = _ffn(p, h, cfg)
        x = x + ffn_out
    if picks is not None and count_mask is not None:
        H = p["moe"]["w_gate"].shape[0]
        live = jax.nn.one_hot(picks, H, dtype=jnp.int32) * count_mask[:, :, None, None]
        add = jnp.stack([jnp.sum(live), jnp.int32(H),
                         jnp.sum(jnp.max(live, axis=(0, 1, 2)))])
        phase = 0 if x.shape[1] == 1 else 1
        store[EXPERT_LOAD] = store[EXPERT_LOAD].at[phase].add(add)
    return x, store


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


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(nope + rope), times YaRN's mscale(mscale_all_dim) squared."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _rope_for(cfg: ModelConfig, positions: jax.Array,
              mrope_positions: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    if cfg.family == "vlm" and mrope_positions is not None:
        return L.mrope_tables(
            mrope_positions, cfg.head_dim_, cfg.mrope_sections, cfg.rope_theta
        )
    dim = cfg.qk_rope_head_dim if cfg.mla else cfg.head_dim_
    if not cfg.yarn_factor:
        return L.rope_tables(positions, dim, cfg.rope_theta)
    inv_freq = L.yarn_inv_freq(dim, cfg.rope_theta, cfg.yarn_factor,
                               cfg.yarn_original_max_pos, cfg.yarn_beta_fast,
                               cfg.yarn_beta_slow)
    scale = (L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
             / L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    return L.rope_tables(positions, dim, inv_freq=inv_freq, scale=scale)


def _one_block(cfg: ModelConfig, blocks):
    return (jax.tree_util.tree_map(lambda a: a[0], blocks)
            if cfg.scan_layers else blocks[0])


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

    for name, _, _ in layer_groups(cfg):
        blocks = params[name]
        body = _body_fn(cfg, "apply", (_one_block(cfg, blocks), x, cos, sin))
        if cfg.scan_layers:
            def step(carry, p_layer):
                return body(p_layer, carry, cos, sin), None

            x, _ = lax.scan(step, x, blocks)
        else:
            for p_layer in blocks:
                x = body(p_layer, x, cos, sin)

    x = L.apply_norm(x, params["final_norm"], cfg.norm)
    return L.lm_head(x, params.get("lm_head", params["embed"]), transpose=cfg.tie_embeddings)


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int
) -> Dict[str, jax.Array]:
    """Contiguous decode cache, stacked over layers: ``k``/``v`` of shape
    (L, B, KVH, max_len, D), or for MLA one latent row per token, ``kv``
    (L, B, max_len, kv_lora_rank + qk_rope_head_dim)."""
    dt = _dtype(cfg)
    if cfg.mla:
        row = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        return {"kv": jnp.zeros((cfg.n_layers, batch, max_len, row), dt)}
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, max_len, cfg.head_dim_)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


#: the paged store's counter of an MoE config: int32 (2, 3), rows decode
#: and prefill dispatches, columns (picks of active rows that reached a
#: held expert, held experts, held experts with at least one such pick),
#: each summed over the MoE layers of every dispatch since the store was
#: made.  It rides through the step with the store, so reading it costs
#: no host sync per tick.
EXPERT_LOAD = "expert_load"


def init_paged_cache(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    *,
    num_pages: int,
    page_size: int,
) -> Dict[str, jax.Array]:
    """Paged decode state: the page store plus one page table shared by
    every layer (a logical page holds all layers' KV for its token block,
    so the allocator hands out one index per block).

    Multi-head attention stores K and V, each ``(n_layers, num_pages,
    page_size, n_kv_heads * head_dim)``: token-major rows, one row holding
    one token's K (or V) for every head.  A step writes whole rows (all
    heads of a token at once), and the flat row keeps the minor dimension
    a multiple of 128 lanes where a head alone (96 at phi3) is not, so the
    store is never padded or re-laid out around the write.  MLA stores one
    row per token, ``kv_pages`` ``(n_layers, num_pages, page_size,
    kv_lora_rank + qk_rope_head_dim)``: the normed latent, then the
    rotated rope key (576 values at DeepSeek-V2, one flat row, so the
    64-wide key is not padded to 128 lanes on its own).  An MoE config
    adds the :data:`EXPERT_LOAD` counter.  Every entry but ``page_table``
    is the store the server carries.

    Page 0 is the reserved trash page (see core/paging.py): a zero-filled
    table points every slot there, masked/pad writes scatter there, and
    the length masks keep whatever accumulates in it out of the softmax.
    """
    if max_len % page_size:
        raise ValueError(f"max_len {max_len} not a multiple of page_size {page_size}")
    dt = _dtype(cfg)
    if cfg.mla:
        row = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        store = {"kv_pages": jnp.zeros((cfg.n_layers, num_pages, page_size, row), dt)}
    else:
        shape = (cfg.n_layers, num_pages, page_size, cfg.n_kv_heads * cfg.head_dim_)
        store = {"k_pages": jnp.zeros(shape, dt), "v_pages": jnp.zeros(shape, dt)}
    if cfg.family == "moe":
        store[EXPERT_LOAD] = jnp.zeros((2, 3), jnp.int32)
    store["page_table"] = jnp.zeros((batch, max_len // page_size), jnp.int32)
    return store


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

    Every cache leaf is stacked over layers; each group of layers scans
    its own slice.  ``slot_mask`` gates the cache update per batch row
    (outside the compiled block body, so the body graph is mask-free):
    inactive rows keep their previous KV bitwise — write-inert even under
    NaN inputs (see :func:`~repro.models.layers.slot_gate`).
    """
    out = []
    for name, first, n in layer_groups(cfg):
        blocks = params[name]
        part = {k: v[first:first + n] for k, v in cache.items()}
        one = {k: v[0] for k, v in part.items()}
        body = _body_fn(cfg, mode, (_one_block(cfg, blocks), x, one, pos, cos, sin))
        if cfg.scan_layers:
            def step(carry, xs):
                p_layer, c = xs
                y, nc = body(p_layer, carry, c, pos, cos, sin)
                return y, L.slot_gate(slot_mask, nc, c)

            x, new = lax.scan(step, x, (blocks, part))
        else:
            layers = []
            for i, p_layer in enumerate(blocks):
                c = {k: v[i] for k, v in part.items()}
                x, nc = body(p_layer, x, c, pos, cos, sin)
                layers.append(L.slot_gate(slot_mask, nc, c))
            new = {k: jnp.stack([c[k] for c in layers]) for k in part}
        out.append(new)
    new_cache = (out[0] if len(out) == 1 else
                 {k: jnp.concatenate([o[k] for o in out]) for k in cache})

    x = L.apply_norm(x, params["final_norm"], cfg.norm)
    logits = L.lm_head(x, params.get("lm_head", params["embed"]), transpose=cfg.tie_embeddings)
    return logits, new_cache


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
    last: Optional[jax.Array] = None,  # int32 (B,): the one column to give logits of
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """:func:`_cached_forward` against the paged KV pool.  The page table
    is read-only inside the model (allocation is host-side, in the serve
    layer); the slot mask rides inside the body because the batch-free
    page store cannot be gated per row after the fact.  With ``last``, the
    logits are those of column ``last[b]`` of each row alone, ``(B, 1,
    vocab)``, and the columns after it (prompt padding) are not counted.

    The store (every entry of ``cache`` but ``page_table``) rides in the
    layer loop's carry, beside ``x``, and each block writes its tokens
    into its own layer's rows of it in place: the loop's ``xs`` are the
    layer weights and the layer index only, so no layer's slab is sliced
    out of the store or stacked back into a new one.  Each group of
    layers is one loop, at its own layer indices of the one store."""
    B = x.shape[0]
    mask = (jnp.ones((B,), jnp.bool_) if slot_mask is None
            else jnp.asarray(slot_mask, jnp.bool_))
    pt = cache["page_table"]
    store = {k: v for k, v in cache.items() if k != "page_table"}
    # the expert counter's tokens: the real columns of the active rows
    # (an argument of MoE bodies only, so other bodies are unchanged)
    count = ()
    if EXPERT_LOAD in store:
        real = mask[:, None]
        if last is not None:
            real = real & (jnp.arange(x.shape[1], dtype=jnp.int32) <= last[:, None])
        count = (jnp.broadcast_to(real, x.shape[:2]),)
    for name, first, n in layer_groups(cfg):
        blocks = params[name]
        layer0 = jnp.asarray(first, jnp.int32)
        body = _body_fn(cfg, mode, (_one_block(cfg, blocks), x, store, layer0, pt,
                                    pos, mask, cos, sin, *count))
        if cfg.scan_layers:
            def step(carry, xs):
                p_layer, layer = xs
                return body(p_layer, *carry, layer, pt, pos, mask, cos, sin, *count), None

            layers = jnp.arange(first, first + n, dtype=jnp.int32)
            (x, store), _ = lax.scan(step, (x, store), (blocks, layers))
        else:
            for i, p_layer in enumerate(blocks):
                x, store = body(p_layer, x, store, jnp.int32(first + i), pt, pos,
                                mask, cos, sin, *count)

    if last is not None:
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)
    with jax.named_scope("logits"):
        x = L.apply_norm(x, params["final_norm"], cfg.norm)
        logits = L.lm_head(x, params.get("lm_head", params["embed"]),
                           transpose=cfg.tie_embeddings)
    return logits, {**store, "page_table": pt}


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
    last: Optional[jax.Array] = None,  # int32 (B,): each row's last real column
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """:func:`prefill_step` against the paged KV pool.

    Beyond the contiguous version, ``pos`` may be per-row (B,): each row
    anchors its chunk at its own start position.  That is the prefix-
    reuse entry point — a row whose leading pages came from the prefix
    tree prefills only the suffix, with ``pos`` at its skip offset, in
    the same dispatch as rows starting from zero.  Given ``last``, only
    column ``last[b]`` of each row goes through the head: logits ``(B, 1,
    vocab)``, the next token's, where the whole block's would be ``(B, S,
    vocab)``."""
    x = L.embed(tokens, params["embed"])
    S = x.shape[1]
    offs = jnp.arange(S, dtype=jnp.int32)
    positions = (pos[:, None] + offs) if getattr(pos, "ndim", 0) == 1 else pos + offs
    cos, sin = _rope_for(cfg, positions, None)
    return _paged_cached_forward(params, cache, x, pos, cos, sin, cfg,
                                 "paged_prefill", slot_mask=slot_mask, last=last)


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
    length mask inside the attention keeps query i from seeing keys
    beyond ``pos + i``, and MoE routing is per token) but dispatches one
    program instead of S — time-to-first-token stops scaling with
    per-token dispatch count.  Returns the full (B, S, vocab) logits
    (the serve path reads the last *valid* column) plus the updated
    cache.

    ``slot_mask`` restricts the cache write to the marked rows — the
    slot scheduler's mid-generation swap-in prefills a queued prompt
    into a finished slot's KV rows while every other slot's cache stays
    bitwise untouched.
    """
    x = L.embed(tokens, params["embed"])
    S = x.shape[1]
    positions = pos + jnp.arange(S, dtype=jnp.int32)
    cos, sin = _rope_for(cfg, positions, None)
    return _cached_forward(params, cache, x, pos, cos, sin, cfg, "prefill",
                           slot_mask=slot_mask)
