"""Multi-head / grouped-query attention, written unfused.

The decomposed chain below (projections → RoPE → GQA broadcast-expand →
dot → scale → iota-where mask → softmax → dot → out-proj) is exactly what
the Forge attention-fusion pass matches; after Phase 2 the whole middle
collapses into one ``forge.sdpa`` dispatch.

Supports: full causal self-attention (train/prefill), KV-cache single-
token decode, bidirectional encoder attention, cross-attention, local
(banded) attention, and M-RoPE position streams.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..distrib.actsharding import constrain
from . import layers as L

Params = Dict[str, Any]

#: paged-cache attend implementations: "ref" gathers the row's pages and
#: reuses the unfused sdpa; "pallas" is the paged decode kernel compiled
#: for the TPU; "interpret" is that kernel in the Pallas interpreter
KV_KERNELS = ("ref", "pallas", "interpret")


def attn_init(
    key,
    d_model: int,
    n_heads: int,
    n_kv_heads: int,
    head_dim: Optional[int] = None,
    *,
    qkv_bias: bool = False,
    dtype=jnp.bfloat16,
) -> Params:
    hd = head_dim or d_model // n_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], d_model, n_heads * hd, dtype),
        "wk": L.dense_init(ks[1], d_model, n_kv_heads * hd, dtype),
        "wv": L.dense_init(ks[2], d_model, n_kv_heads * hd, dtype),
        "wo": L.dense_init(ks[3], n_heads * hd, d_model, dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((n_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((n_kv_heads * hd,), dtype)
    return p


def _split_heads(x: jax.Array, n_heads: int) -> jax.Array:
    B, S, _ = x.shape
    return x.reshape(B, S, n_heads, -1).transpose(0, 2, 1, 3)


def _merge_heads(x: jax.Array) -> jax.Array:
    B, H, S, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, H * D)


def _expand_kv(k: jax.Array, groups: int) -> jax.Array:
    """The canonical GQA broadcast-expansion (unwrapped by fusion)."""
    if groups == 1:
        return k
    B, KVH, S, D = k.shape
    return jnp.broadcast_to(
        k[:, :, None], (B, KVH, groups, S, D)
    ).reshape(B, KVH * groups, S, D)


def sdpa_unfused(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: Optional[int] = None,
    extra_mask: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Decomposed attention: the fusion pass's input pattern."""
    B, H, Sq, D = q.shape
    KVH, Sk = k.shape[1], k.shape[2]
    groups = H // KVH
    k = _expand_kv(k, groups)
    v = _expand_kv(v, groups)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * (scale if scale is not None else 1.0 / math.sqrt(D))
    if window is not None:
        s = L.local_causal_where(s, Sq, Sk, window)
    elif causal:
        s = L.causal_where(s, Sq, Sk)
    if extra_mask is not None:
        s = s + extra_mask.astype(s.dtype)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return o.astype(v.dtype)


def _gather_rows(store: jax.Array, layer: jax.Array, page_table: jax.Array,
                 n_kv_heads: int, sq: int) -> jax.Array:
    """One layer's per-row KV view out of the token-major store, in the
    ``(B, KVH, MP * ps, D)`` layout a contiguous cache row has: the rows'
    pages in one gather, then split into heads.

    The head-major view is materialized in bf16 (``optimization_barrier``)
    before attention reads it: left to itself, XLA on the TPU converts the
    gathered rows to float32 first and re-lays them out at twice the
    bytes.  A one-token decode step reads the view as whole rows; a
    prefill chunk's matmuls read it as page tiles ``(B, KVH, MP, ps, D)``,
    which keeps each page's ``(ps, D)`` block whole and the score matrix
    out of memory.  Either way the values are the gathered ones, bit for
    bit."""
    B, MP = page_table.shape
    ps = store.shape[2]
    rows = store[layer, page_table].reshape(B, MP, ps, n_kv_heads, -1)
    if sq == 1:
        view = rows.reshape(B, MP * ps, n_kv_heads, -1).transpose(0, 2, 1, 3)
        return lax.optimization_barrier(view)
    tiles = lax.optimization_barrier(rows.transpose(0, 3, 1, 2, 4))
    return tiles.reshape(B, n_kv_heads, MP * ps, -1)


def _write_tokens(store: jax.Array, x: jax.Array, layer: jax.Array,
                  page_table: jax.Array, pos_row: jax.Array,
                  write_mask: Optional[jax.Array]) -> jax.Array:
    """Write this step's K (or V), ``x`` ``(B, KVH, sq, D)``, into layer
    ``layer`` of the token-major store at each row's positions
    ``pos_row[b] + [0, sq)``, through the page table.

    Rows outside ``write_mask`` (inactive slots) and pages past the table
    extent (prefill pad) are routed to the reserved trash page 0, so the
    store needs no batch axis and no post-hoc slot gate.  Trash-routed
    writes may collide (last writer wins): trash content is never
    unmasked, and live destinations are owned by one row each.

    A decode step (``sq == 1``) scatters one whole row per batch row.  A
    prefill chunk writes whole pages instead: it reads the pages its
    tokens touch, lays the new rows over them and scatters the pages
    back, since the TPU scatters a few large windows far faster than
    many rows.  Either way only the touched pages change, bit for bit
    as a row-by-row write would leave them."""
    B, KVH, sq, D = x.shape
    ps, row = store.shape[2], store.shape[3]
    MP = page_table.shape[1]
    rows = x.transpose(0, 2, 1, 3).reshape(B, sq, KVH * D)
    # the pages a chunk of sq tokens can touch, at any offset in a page;
    # those it does not touch at this row's offset go to the trash page
    n = 1 if sq == 1 else (sq - 1) // ps + 2
    j = jnp.arange(n, dtype=jnp.int32)[None, :]
    idx = pos_row[:, None] // ps + j
    ok = jnp.logical_and(idx < MP, j * ps < (pos_row % ps)[:, None] + sq)
    if write_mask is not None:
        ok = jnp.logical_and(ok, write_mask[:, None])
    page = jnp.where(
        ok, jnp.take_along_axis(page_table, jnp.clip(idx, 0, MP - 1), axis=1), 0
    )
    if sq == 1:
        return store.at[layer, page[:, 0], pos_row % ps].set(rows[:, 0])
    buf = store[layer, page].reshape(B, n * ps, row)
    buf = jax.vmap(lambda b, r, o: lax.dynamic_update_slice(b, r, (o, 0)))(
        buf, rows, pos_row % ps
    )
    return store.at[layer, page].set(buf.reshape(B, n, ps, row))


def _paged_update_attend(
    q: jax.Array,  # (B, H, sq, D) post-RoPE queries
    k: jax.Array,  # (B, KVH, sq, D) post-RoPE keys for this step
    v: jax.Array,
    cache: Dict[str, jax.Array],  # k_pages / v_pages / page_table
    cache_pos: jax.Array,  # scalar or per-row (B,) write position
    *,
    layer: Any,  # int32 scalar: the layer of the stacked store
    window: Optional[int],
    write_mask: Optional[jax.Array],  # bool (B,) — rows allowed to write
    kv_kernel: str,  # one of KV_KERNELS
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Paged-cache decode/prefill: scatter this step's K/V into the
    stacked page store through the page table, then attend over the
    row's pages.

    The store is ``(n_layers, num_pages, page_size, KVH * D)``: one row
    per token holds all heads.  The write (:func:`_write_tokens`) is one
    scatter into the whole store, which the layer loop carries, so XLA
    updates it in place; masked rows and prefill pad land on the trash
    page 0.

    The "ref" attend gathers the rows' pages of this layer back into the
    exact contiguous-cache layout and reuses the same masks + sdpa — the
    paged path is **bitwise** the contiguous path on live rows (garbage
    beyond ``pos``, trash reads included, lands on score columns already
    pinned to the additive-mask floor).  "pallas" dispatches the page-
    table-indirected decode kernel instead (see
    kernels/paged_attention.py), compiled for the TPU, on this layer's
    pages re-laid out head-major, the kernel's own interface;
    "interpret" runs that kernel in the Pallas interpreter, for tests off
    the chip.
    """
    from ..kernels.paged_attention import paged_attention as _paged_kernel

    k_store, v_store = cache["k_pages"], cache["v_pages"]
    pt = cache["page_table"].astype(jnp.int32)
    _, NP, ps, _ = k_store.shape
    B, KVH, sq, D = k.shape
    max_len = pt.shape[1] * ps
    layer = jnp.asarray(layer, jnp.int32)

    pos_arr = jnp.asarray(cache_pos, jnp.int32)
    pos_row = jnp.broadcast_to(pos_arr, (B,)) if pos_arr.ndim == 0 else pos_arr
    with jax.named_scope("kv.write"):
        new_k = _write_tokens(k_store, k, layer, pt, pos_row, write_mask)
        new_v = _write_tokens(v_store, v, layer, pt, pos_row, write_mask)

    if kv_kernel not in KV_KERNELS:
        raise ValueError(f"kv_kernel must be one of {KV_KERNELS}, got {kv_kernel!r}")
    if kv_kernel != "ref" and sq == 1:
        def head_major(store):
            return store[layer].reshape(NP, ps, KVH, D).transpose(0, 2, 1, 3)

        out = _paged_kernel(
            q[:, :, 0, :], head_major(new_k), head_major(new_v), pt, pos_row,
            window=window, interpret=kv_kernel == "interpret",
        )[:, :, None, :].astype(v.dtype)
    else:
        # must mirror the contiguous cache branch of attention() exactly:
        # same mask builders, same cache_pos rank, same sdpa — that is the
        # bitwise-equality contract tests/test_paged_kv.py enforces
        with jax.named_scope("kv.gather"):
            k_view = _gather_rows(new_k, layer, pt, KVH, sq)
            v_view = _gather_rows(new_v, layer, pt, KVH, sq)
        if sq > 1:
            mask = L.prefill_length_mask(cache_pos, sq, max_len, window=window)
        elif window is not None:
            idx = lax.broadcasted_iota(jnp.int32, (1, 1, 1, max_len), 3)
            p = L.per_row_pos(cache_pos)
            keep = (idx <= p) & (idx > p - window)
            mask = jnp.where(keep, 0.0, float(np.finfo(np.float32).min))
        else:
            mask = L.decode_length_mask(cache_pos, max_len)
        out = sdpa_unfused(q, k_view, v_view, causal=False, extra_mask=mask)
    return out, {"k_pages": new_k, "v_pages": new_v}


def attention(
    x: jax.Array,
    p: Params,
    *,
    n_heads: int,
    n_kv_heads: int,
    rope_cos: Optional[jax.Array] = None,
    rope_sin: Optional[jax.Array] = None,
    causal: bool = True,
    window: Optional[int] = None,
    extra_mask: Optional[jax.Array] = None,
    kv: Optional[jax.Array] = None,  # cross-attention source
    cache: Optional[Dict[str, jax.Array]] = None,
    cache_pos: Optional[jax.Array] = None,
    cache_valid_len: Optional[jax.Array] = None,  # rotating-buffer masks
    kv_layer: Any = 0,  # int32 scalar — paged cache only: layer of the store
    write_mask: Optional[jax.Array] = None,  # bool (B,) — paged cache only
    kv_kernel: str = "ref",  # paged-cache attend impl (see above)
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Full attention sub-layer.  Returns (out, updated_cache)."""
    src = kv if kv is not None else x
    q = L.linear(x, p["wq"], p.get("bq"))
    k = L.linear(src, p["wk"], p.get("bk"))
    v = L.linear(src, p["wv"], p.get("bv"))
    # Megatron-style activation layout pins (see distrib/actsharding.py):
    # without these GSPMD splits head_dim when KVH % tp != 0 and
    # all-reduces the score matrix (measured: ~10 GiB/dev/layer).
    # Decode keeps GSPMD-inferred layouts: pinning heads conflicts with
    # the sequence-sharded KV cache and re-shards it every step
    # (measured REFUTATION, EXPERIMENTS §Perf iter 1).
    q = _split_heads(q, n_heads)
    k = _split_heads(k, n_kv_heads)
    v = _split_heads(v, n_kv_heads)
    if cache is None:
        q = constrain(q, "heads")
        k = constrain(k, "kv")
        v = constrain(v, "kv")

    if rope_cos is not None:
        q = L.apply_rope(q, rope_cos, rope_sin)
        if kv is None:  # self-attention: keys rotate too
            k = L.apply_rope(k, rope_cos, rope_sin)

    new_cache = None
    if cache is not None and "k_pages" in cache:
        if cache_valid_len is not None:
            raise NotImplementedError(
                "rotating-buffer valid_len masks are a contiguous-cache "
                "feature; paged rows are length-masked through pos"
            )
        out, new_cache = _paged_update_attend(
            q, k, v, cache, cache_pos, layer=kv_layer,
            window=window, write_mask=write_mask, kv_kernel=kv_kernel,
        )
    elif cache is not None:
        # single-token or whole-chunk decode: write at cache_pos, attend
        # to all.  A chunk (Sq > 1, the batched-prefill path) gets a
        # causal length mask — query i at cache position cache_pos + i
        # sees keys <= cache_pos + i — so one forward pass writes the
        # whole prompt block with exact sequential-decode semantics.
        # ``cache_pos`` may be per-row (B,): each batch row then writes
        # (and masks) at its OWN position — the slot-level continuous-
        # batching path, where one program advances rows at ragged
        # decode positions.
        max_len = cache["k"].shape[2]
        sq = q.shape[2]
        if getattr(cache_pos, "ndim", 0) == 1:
            if sq != 1:
                raise NotImplementedError(
                    "per-row cache positions require single-token steps "
                    "(chunked prefill shares one scalar start position)"
                )
            # per-row scatter: select the written column per row.  A
            # vmapped dynamic_update_slice would lower to the same
            # scatter; the explicit select keeps the graph in the flat
            # primitive vocabulary the Forge passes already handle.
            slot_idx = lax.broadcasted_iota(jnp.int32, (1, 1, max_len, 1), 2)
            write = slot_idx == cache_pos[:, None, None, None]
            k_cache = jnp.where(write, k, cache["k"])
            v_cache = jnp.where(write, v, cache["v"])
        else:
            k_cache = lax.dynamic_update_slice_in_dim(cache["k"], k, cache_pos, axis=2)
            v_cache = lax.dynamic_update_slice_in_dim(cache["v"], v, cache_pos, axis=2)
        new_cache = {"k": k_cache, "v": v_cache}
        if cache_valid_len is not None:
            # rotating buffer: slots < valid_len hold live entries; softmax
            # attention is permutation-invariant over keys (RoPE applied
            # pre-cache), so slot order does not matter.  valid_len may be
            # per-row (B,) for ragged decode positions.
            idx = lax.broadcasted_iota(jnp.int32, (1, 1, 1, max_len), 3)
            mask = jnp.where(idx < L.per_row_pos(cache_valid_len), 0.0,
                             float(np.finfo(np.float32).min))
        elif sq > 1:
            mask = L.prefill_length_mask(cache_pos, sq, max_len,
                                         window=window)
        elif window is not None:
            idx = lax.broadcasted_iota(jnp.int32, (1, 1, 1, max_len), 3)
            prow = L.per_row_pos(cache_pos)
            keep = (idx <= prow) & (idx > prow - window)
            mask = jnp.where(keep, 0.0, float(np.finfo(np.float32).min))
        else:
            mask = L.decode_length_mask(cache_pos, max_len)
        out = sdpa_unfused(
            q, k_cache, v_cache, causal=False, extra_mask=mask
        )
    else:
        out = sdpa_unfused(
            q, k, v, causal=causal, window=window, extra_mask=extra_mask
        )
    out = L.linear(_merge_heads(out), p["wo"])
    return constrain(out, "tokens"), new_cache


# --------------------------------------------------------------------------
# multi-head latent attention (MLA, DeepSeek-V2)
# --------------------------------------------------------------------------


def mla_init(key, d_model: int, n_heads: int, rank: int, nope: int, rope: int,
             v_dim: int, *, dtype=jnp.bfloat16) -> Params:
    """Published layout: ``wq`` (d, H·(nope+rope)); ``wkv_a`` (d, rank+rope),
    the latent and the shared rope key; ``kv_norm`` over the latent;
    ``wkv_b`` (rank, H·(nope+v)), each head's key and value up-projection;
    ``wo`` (H·v, d)."""
    ks = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(ks[0], d_model, n_heads * (nope + rope), dtype),
        "wkv_a": L.dense_init(ks[1], d_model, rank + rope, dtype),
        "kv_norm": L.norm_init(rank),
        "wkv_b": L.dense_init(ks[2], rank, n_heads * (nope + v_dim), dtype),
        "wo": L.dense_init(ks[3], n_heads * v_dim, d_model, dtype),
    }


def _latent_attend(q_nope: jax.Array, q_rope: jax.Array, rows: jax.Array,
                   w_ukv: jax.Array, scale: float, mask) -> jax.Array:
    """Absorbed attention over latent rows.

    q_nope (B, H, S, nope) and q_rope (B, H, S, rope) post-rope; rows
    (B, T, rank + rope): each key's normed latent, then its rotated rope
    key; w_ukv (rank, H, nope + v): ``wkv_b`` split by head.  The query is
    mapped into latent space by W_UK, scored against the whole row (latent
    and rope key in one product), and the latent sum is mapped out by
    W_UV: no per-head key or value is formed.  ``mask`` is additive
    (1|B, 1, S, T) or a function of the scores."""
    nope = q_nope.shape[-1]
    rank = rows.shape[-1] - q_rope.shape[-1]
    B, H, S, _ = q_nope.shape
    with jax.named_scope("mla.attend"):
        w_uk, w_uv = w_ukv[..., :nope], w_ukv[..., nope:]
        q_lat = jnp.einsum("bhsn,rhn->bhsr", q_nope, w_uk,
                           preferred_element_type=jnp.float32).astype(rows.dtype)
        q = jnp.concatenate([q_lat, q_rope.astype(rows.dtype)], axis=-1)
        s = jnp.einsum("bhsc,btc->bhst", q, rows,
                       preferred_element_type=jnp.float32) * scale
        s = mask(s) if callable(mask) else s + mask
        p = jax.nn.softmax(s, axis=-1).astype(rows.dtype)
        o = jnp.einsum("bhst,btr->bhsr", p, rows[..., :rank],
                       preferred_element_type=jnp.float32).astype(rows.dtype)
        o = jnp.einsum("bhsr,rhv->bshv", o, w_uv,
                       preferred_element_type=jnp.float32).astype(rows.dtype)
    return o.reshape(B, S, -1)


def mla_attention(
    x: jax.Array,
    p: Params,
    *,
    n_heads: int,
    nope: int,
    rope_cos: jax.Array,
    rope_sin: jax.Array,
    scale: float,
    cache: Optional[Dict[str, jax.Array]] = None,
    cache_pos: Optional[jax.Array] = None,
    kv_layer: Any = 0,
    write_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """MLA sub-layer.  Returns (out, updated cache).

    The cache holds one row per token, the normed latent followed by the
    rotated rope key: ``{"kv": (B, T, rank + rope)}`` for one layer of a
    contiguous cache, or the paged store ``{"kv_pages": (L, NP, ps, rank +
    rope), "page_table"}``, written in place like the MHA store (trash
    page and slot mask as in :func:`_write_tokens`).  Without a cache the
    rows of ``x`` itself are attended causally."""
    B, S, _ = x.shape
    rank = p["wkv_b"].shape[0]
    q = L.linear(x, p["wq"]).reshape(B, S, n_heads, -1).transpose(0, 2, 1, 3)
    q_nope = q[..., :nope]
    q_rope = L.apply_rope(q[..., nope:], rope_cos, rope_sin)
    kv = L.linear(x, p["wkv_a"])
    lat = L.rms_norm(kv[..., :rank], p["kv_norm"]["scale"])
    k_rope = L.apply_rope(kv[:, None, :, rank:], rope_cos, rope_sin)[:, 0]
    new = jnp.concatenate([lat, k_rope], axis=-1)  # (B, S, rank + rope)
    w_ukv = p["wkv_b"].reshape(rank, n_heads, -1)

    new_cache = None
    if cache is None:
        rows = new
        mask = lambda s: L.causal_where(s, S, S)  # noqa: E731
    else:
        pos = jnp.asarray(cache_pos, jnp.int32)
        if "kv_pages" in cache:
            pt = cache["page_table"].astype(jnp.int32)
            layer = jnp.asarray(kv_layer, jnp.int32)
            pos_row = jnp.broadcast_to(pos, (B,)) if pos.ndim == 0 else pos
            with jax.named_scope("kv.write"):
                store = _write_tokens(cache["kv_pages"], new[:, None], layer, pt,
                                      pos_row, write_mask)
            with jax.named_scope("kv.gather"):
                rows = _gather_rows(store, layer, pt, 1, S)[:, 0]
            new_cache = {"kv_pages": store}
        else:
            rows = cache["kv"]
            if pos.ndim == 1:
                if S != 1:
                    raise NotImplementedError(
                        "per-row cache positions require single-token steps")
                idx = lax.broadcasted_iota(jnp.int32, (1, rows.shape[1], 1), 1)
                rows = jnp.where(idx == pos[:, None, None], new, rows)
            else:
                rows = lax.dynamic_update_slice_in_dim(rows, new, pos, axis=1)
            new_cache = {"kv": rows}
        T = rows.shape[1]
        mask = (L.decode_length_mask(pos, T) if S == 1
                else L.prefill_length_mask(pos, S, T))
    out = _latent_attend(q_nope, q_rope, rows, w_ukv, scale, mask)
    return L.linear(out, p["wo"]), new_cache


def make_cache(
    batch: int, n_kv_heads: int, max_len: int, head_dim: int, dtype=jnp.bfloat16
) -> Dict[str, jax.Array]:
    shape = (batch, n_kv_heads, max_len, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
