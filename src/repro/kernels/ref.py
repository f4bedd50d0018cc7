"""Pure-jnp reference oracles for every Forge fused kernel.

These are the ground truth the Pallas kernels are validated against
(``tests/test_kernels.py`` sweeps shapes/dtypes with
``np.testing.assert_allclose``) and the backward implementations used by
the ``custom_vjp`` wrappers in :mod:`repro.kernels.ops`.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def sdpa_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    *,
    scale: Optional[float] = None,
    causal: bool = False,
) -> jax.Array:
    """Reference scaled-dot-product attention.

    q: (B, H, Sq, D); k, v: (B, KVH, Sk, D) with H % KVH == 0 (GQA).
    ``mask`` is additive, broadcastable to (B, H, Sq, Sk).
    """
    B, H, Sq, D = q.shape
    KVH = k.shape[1]
    if KVH != H:
        g = H // KVH
        k = jnp.broadcast_to(k[:, :, None], (B, KVH, g) + k.shape[2:]).reshape(
            B, H, *k.shape[2:]
        )
        v = jnp.broadcast_to(v[:, :, None], (B, KVH, g) + v.shape[2:]).reshape(
            B, H, *v.shape[2:]
        )
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if causal:
        Sk = k.shape[2]
        idx_q = lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0) + (Sk - Sq)
        idx_k = lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        s = jnp.where(idx_q >= idx_k, s, jnp.finfo(s.dtype).min)
    if mask is not None:
        s = s + mask.astype(s.dtype)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def gather_pages(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """Gather a contiguous per-row KV view out of a paged store.

    pages: (num_pages, KVH, page_size, D) — one layer's pages, head-major,
    as the paged decode kernel reads them.
    page_table: (B, max_pages) int32 — per-row page indices; unallocated
    entries point at the trash page (0) and are masked out by the caller.

    Returns (B, KVH, max_pages * page_size, D), the same layout a
    contiguous cache row would have.
    """
    NP, KVH, ps, D = pages.shape
    B, MP = page_table.shape
    view = jnp.take(pages, page_table.astype(jnp.int32), axis=0)
    # (B, MP, KVH, ps, D) -> (B, KVH, MP * ps, D)
    return view.transpose(0, 2, 1, 3, 4).reshape(B, KVH, MP * ps, D)


def paged_sdpa_ref(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    pos: jax.Array,
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Reference paged-attention decode step (the kernel's fidelity oracle).

    q: (B, H, D) — one query token per row; k_pages/v_pages:
    (num_pages, KVH, page_size, D); page_table: (B, max_pages) int32;
    pos: (B,) int32 — the query's position (keys at indices <= pos are
    live; garbage beyond pos, including trash-page reads, is masked).
    Returns (B, H, D).
    """
    ps = k_pages.shape[2]
    MP = page_table.shape[1]
    L = MP * ps
    k = gather_pages(k_pages, page_table)
    v = gather_pages(v_pages, page_table)
    idx = jnp.arange(L, dtype=jnp.int32)[None, None, None, :]
    p = pos.astype(jnp.int32)[:, None, None, None]
    keep = idx <= p
    if window is not None:
        keep = jnp.logical_and(keep, idx > p - window)
    mask = jnp.where(keep, 0.0, jnp.finfo(jnp.float32).min)
    out = sdpa_ref(q[:, :, None, :], k, v, mask, scale=scale)
    return out[:, :, 0, :]


def fused_linear_ref(
    x: jax.Array,
    w: jax.Array,
    b: Optional[jax.Array] = None,
    *,
    act: Optional[str] = None,
) -> jax.Array:
    """Reference linear (+bias) (+activation). x: (..., K), w: (K, N)."""
    y = jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)
    if b is not None:
        y = y + b
    return apply_act(y, act)


def apply_act(y: jax.Array, act: Optional[str]) -> jax.Array:
    if act is None or act == "none":
        return y
    if act == "relu":
        return jax.nn.relu(y)
    if act == "silu":
        return jax.nn.silu(y)
    if act == "gelu":
        return jax.nn.gelu(y)
    if act == "gelu_exact":
        return jax.nn.gelu(y, approximate=False)
    if act == "tanh":
        return jnp.tanh(y)
    raise ValueError(f"unknown activation {act!r}")


def swiglu_ref(x: jax.Array, w_gate: jax.Array, w_up: jax.Array) -> jax.Array:
    """Reference SwiGLU gate: silu(x·Wg) ⊙ (x·Wu)."""
    g = jnp.dot(x, w_gate, preferred_element_type=jnp.float32).astype(x.dtype)
    u = jnp.dot(x, w_up, preferred_element_type=jnp.float32).astype(x.dtype)
    return jax.nn.silu(g) * u


def rms_norm_ref(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Reference RMSNorm: x · rsqrt(mean(x², -1) + eps) · w."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def rg_lru_ref(
    x: jax.Array,
    a: jax.Array,
    h0: Optional[jax.Array] = None,
) -> jax.Array:
    """Reference RG-LRU linear recurrence  h_t = a_t ⊙ h_{t-1} + x_t.

    x, a: (B, T, D); returns h: (B, T, D).  Computed with an associative
    scan (the mathematical definition; the Pallas kernel blocks it over T).
    """

    def comb(c1, c2):
        a1, x1 = c1
        a2, x2 = c2
        return a1 * a2, a2 * x1 + x2

    aa, hh = lax.associative_scan(comb, (a, x), axis=1)
    if h0 is not None:
        hh = hh + aa * h0[:, None, :]
    return hh


def rg_lru_chunk_ref(
    x: jax.Array,
    a: jax.Array,
    h0: Optional[jax.Array] = None,
) -> tuple:
    """Chunked-prefill RG-LRU oracle: ``(h, h_last)`` for one chunk.

    The fidelity ground truth for the chunked Pallas kernel
    (:func:`repro.kernels.rg_lru.rg_lru_chunked`): the full in-chunk
    state sequence plus the carry ``h_last = h[:, -1]`` a caller folds
    into the next chunk's ``h0`` — chaining chunks with this carry is
    exactly the unchunked scan.
    """
    h = rg_lru_ref(x, a, h0)
    return h, h[:, -1, :]
