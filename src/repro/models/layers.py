"""Model-zoo building blocks, written UNFUSED on purpose.

Every layer here is expressed as explicit ``jnp`` primitives (no
``jax.nn.dot_product_attention``, no pre-fused kernels) so that Phase-2 of
the Forge pipeline finds the decomposed chains the paper's passes match:
attention appears as dot→scale→where→softmax→dot, FFNs as dot→add→act,
RoPE tables as foldable iota arithmetic.

Conventions:

* params are plain nested dicts of ``jnp`` arrays,
* activations default to bf16 with fp32 accumulation at matmul boundaries
  (``preferred_element_type``), norms computed in fp32,
* the causal mask uses the canonical ``row ≥ col`` iota pattern the
  attention-fusion matcher recognizes.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype=jnp.bfloat16) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.bfloat16) -> jax.Array:
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# norms (computed in fp32, cast back)
# --------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def layer_norm(
    x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-5
) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def apply_norm(x: jax.Array, p: Params, kind: str = "rmsnorm") -> jax.Array:
    if kind == "rmsnorm":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p["bias"])


def norm_init(d: int, kind: str = "rmsnorm", dtype=jnp.float32) -> Params:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


# --------------------------------------------------------------------------
# linear / embedding
# --------------------------------------------------------------------------


def linear(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None) -> jax.Array:
    y = jnp.einsum(
        "...k,kn->...n", x, w, preferred_element_type=jnp.float32
    ).astype(x.dtype)
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


def embed(tokens: jax.Array, table: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0)


def lm_head(x: jax.Array, table_or_w: jax.Array, *, transpose: bool) -> jax.Array:
    """Project to vocab.  ``transpose=True`` -> tied embedding (vocab, d)."""
    from ..distrib.actsharding import constrain

    w = table_or_w.T if transpose else table_or_w
    logits = jnp.einsum(
        "...d,dv->...v", x, w, preferred_element_type=jnp.float32
    )
    # keep logits vocab-sharded through the loss/backward: without the pin
    # the head backward materializes UNSHARDED fp32 logits per device
    # (40 GiB/step on kimi-k2 — EXPERIMENTS §Perf)
    return constrain(logits, "logits")


# --------------------------------------------------------------------------
# RoPE — tables are pure iota arithmetic so constant folding pre-computes
# them (the paper's "RoPE frequency pre-computation" folding)
# --------------------------------------------------------------------------


def rope_tables(
    positions: jax.Array, head_dim: int, theta: float = 10000.0,
    inv_freq: Optional[np.ndarray] = None, scale: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for positions: (..., S) -> (..., S, head_dim/2).

    ``inv_freq`` (head_dim/2,) replaces the plain ``theta`` frequencies
    (YaRN, :func:`yarn_inv_freq`); ``scale`` multiplies both tables."""
    half = head_dim // 2
    if inv_freq is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    if scale == 1.0:
        return jnp.cos(ang), jnp.sin(ang)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature term: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max_pos: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN inverse frequencies (dim/2,): the plain rope frequencies where a
    dimension turns more than ``beta_fast`` times over ``original_max_pos``
    positions, those divided by ``factor`` where it turns fewer than
    ``beta_slow`` times, and a linear ramp between the two."""
    def turn_dim(turns: float) -> float:
        return (dim * math.log(original_max_pos / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turn_dim(beta_fast)), 0)
    high = min(math.ceil(turn_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, H, S, D); cos/sin: (S, D/2) or (B, S, D/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:  # (S, half) -> (1, 1, S, half)
        cos, sin = cos[None, None], sin[None, None]
    elif cos.ndim == 3:  # (B, S, half) -> (B, 1, S, half)
        cos, sin = cos[:, None], sin[:, None]
    o1 = x1 * cos.astype(x.dtype) - x2 * sin.astype(x.dtype)
    o2 = x2 * cos.astype(x.dtype) + x1 * sin.astype(x.dtype)
    return jnp.concatenate([o1, o2], axis=-1)


def mrope_tables(
    positions: jax.Array,  # (3, B, S): temporal / height / width position ids
    head_dim: int,
    sections: Tuple[int, int, int],
    theta: float = 1_000_000.0,
) -> Tuple[jax.Array, jax.Array]:
    """Qwen2-VL multimodal RoPE: the head_dim/2 frequency slots are split
    into (t, h, w) sections, each rotated by its own position stream."""
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang_all = positions.astype(jnp.float32)[..., None] * freqs  # (3, B, S, half)
    parts = []
    start = 0
    for i, sec in enumerate(sections):
        parts.append(ang_all[i, ..., start:start + sec])
        start += sec
    ang = jnp.concatenate(parts, axis=-1)  # (B, S, half)
    return jnp.cos(ang), jnp.sin(ang)


# --------------------------------------------------------------------------
# masks — canonical patterns the fusion matcher understands
# --------------------------------------------------------------------------


def causal_where(s: jax.Array, sq: int, sk: int) -> jax.Array:
    """Apply the canonical causal mask to scores ``s`` (..., sq, sk)."""
    row = lax.broadcasted_iota(jnp.int32, (sq, sk), 0) + (sk - sq)
    col = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    neg = jnp.asarray(jnp.finfo(s.dtype).min, s.dtype)
    return jnp.where(row >= col, s, neg)


def local_causal_where(s: jax.Array, sq: int, sk: int, window: int) -> jax.Array:
    """Banded causal mask (RecurrentGemma local attention)."""
    row = lax.broadcasted_iota(jnp.int32, (sq, sk), 0) + (sk - sq)
    col = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    keep = (row >= col) & (row - col < window)
    neg = jnp.asarray(jnp.finfo(s.dtype).min, s.dtype)
    return jnp.where(keep, s, neg)


def decode_positions(pos: jax.Array) -> jax.Array:
    """RoPE position stream for one decode step: scalar -> (1,) shared
    across rows; per-row (B,) -> (B, 1) so row b rotates by its own
    position (ragged slot decode)."""
    if pos.ndim == 0:
        return pos[None]
    if pos.ndim == 1:
        return pos[:, None]
    return pos


def per_row_pos(pos: jax.Array) -> jax.Array:
    """Broadcast a cache position against (B, H, sq, max_len) scores.

    A scalar position passes through (mask batch dim 1, shared by every
    row); a per-row ``(B,)`` vector reshapes to ``(B, 1, 1, 1)`` so each
    batch row masks against its *own* decode position — the primitive
    that lets slot-level continuous batching run rows at ragged
    positions inside one compiled program.
    """
    return pos[:, None, None, None] if getattr(pos, "ndim", 0) == 1 else pos


def decode_length_mask(pos: jax.Array, max_len: int, dtype=jnp.float32) -> jax.Array:
    """Additive mask: 0 for idx <= pos else -inf.

    ``pos`` scalar -> (1, 1, 1, max_len) shared mask; ``pos`` (B,) ->
    (B, 1, 1, max_len) per-row masks (ragged decode positions).
    """
    idx = lax.broadcasted_iota(jnp.int32, (1, 1, 1, max_len), 3)
    neg = jnp.asarray(jnp.finfo(dtype).min, dtype)
    return jnp.where(idx <= per_row_pos(pos), jnp.asarray(0.0, dtype), neg)


def prefill_length_mask(pos: jax.Array, sq: int, max_len: int,
                        window=None, dtype=jnp.float32) -> jax.Array:
    """Causal length mask (1|B, 1, sq, max_len) for chunked prefill.

    Query row i sits at cache position ``pos + i`` and sees keys
    ``idx <= pos + i`` (with ``window``, also ``idx > pos + i -
    window``) — causal *within* the chunk, so a whole prompt block can
    be written through the decode cache path in one forward pass.
    ``pos`` may be per-row (B,) — each batch row then anchors the chunk
    at its own start position.  Reduces to :func:`decode_length_mask`
    at ``sq == 1``.
    """
    idx = lax.broadcasted_iota(jnp.int32, (1, 1, sq, max_len), 3)
    qpos = per_row_pos(pos) + lax.broadcasted_iota(
        jnp.int32, (1, 1, sq, max_len), 2
    )
    keep = idx <= qpos
    if window is not None:
        keep &= idx > qpos - window
    neg = jnp.asarray(jnp.finfo(dtype).min, dtype)
    return jnp.where(keep, jnp.asarray(0.0, dtype), neg)


def window_chunk_mask(pos: jax.Array, sq: int, slots: int, window: int,
                      dtype=jnp.float32) -> jax.Array:
    """Additive mask for chunked prefill over a ROTATING window cache.

    The key axis is ``[slots rotating-cache entries ; sq chunk keys]``.
    Cache slot s holds the key of absolute position
    ``pos - 1 - ((pos - 1 - s) mod window)`` — the latest pre-chunk
    position congruent to s — and is live only while that position is
    >= 0 (the slot was ever written) AND inside query i's band
    (``> pos + i - window``; beyond it the slot would already have been
    overwritten by the time sequential decode reached ``pos + i``).
    Chunk key j (absolute position pos + j) follows the plain banded
    causal rule.  ``pos`` is per-row (B,); returns (B, 1, sq,
    slots + sq) — attending over the concatenated keys with this mask
    reproduces sequential rotating-window decode exactly.
    """
    p = jnp.asarray(pos, jnp.int32)[:, None, None, None]  # (B, 1, 1, 1)
    i = lax.broadcasted_iota(jnp.int32, (1, 1, sq, 1), 2)
    s = lax.broadcasted_iota(jnp.int32, (1, 1, 1, slots), 3)
    cs = p - 1 - jnp.mod(p - 1 - s, window)  # slot s's absolute position
    keep_cache = (cs >= 0) & (cs > p + i - window)
    j = lax.broadcasted_iota(jnp.int32, (1, 1, 1, sq), 3)
    keep_chunk = (j <= i) & (j > i - window)
    B = p.shape[0]
    keep = jnp.concatenate([
        jnp.broadcast_to(keep_cache, (B, 1, sq, slots)),
        jnp.broadcast_to(keep_chunk, (B, 1, sq, sq)),
    ], axis=3)
    neg = jnp.asarray(jnp.finfo(dtype).min, dtype)
    return jnp.where(keep, jnp.asarray(0.0, dtype), neg)


def window_writeback_index(pos: jax.Array, length: jax.Array, sq: int,
                           slots: int, window: int
                           ) -> Tuple[jax.Array, jax.Array]:
    """Which chunk column lands in each rotating-cache slot after prefill.

    After sequential decode of chunk positions ``pos .. pos+length-1``,
    slot s holds the chunk's LAST write to it: chunk index
    ``length - 1 - ((pos + length - 1 - s) mod window)``, or its
    previous contents when that index is negative (the chunk never
    reached the slot).  ``pos``/``length`` are per-row (B,).  Returns
    ``(idx, valid)``: idx (B, slots) int32 clipped into [0, sq-1] (safe
    to gather with), valid (B, slots) bool — False slots must keep
    their old value.
    """
    p = jnp.asarray(pos, jnp.int32)[:, None]
    n = jnp.asarray(length, jnp.int32)[:, None]
    s = jnp.arange(slots, dtype=jnp.int32)[None, :]
    idx = n - 1 - jnp.mod(p + n - 1 - s, window)
    return jnp.clip(idx, 0, sq - 1), idx >= 0


def gather_last_valid(x: jax.Array, length: jax.Array) -> jax.Array:
    """Per-row element at time index ``length - 1``: (B, S, ...) -> (B, ...).

    The chunked-prefill state extractor: row b's post-prefill recurrent
    state is the scan output at its OWN last real token, not at the
    padded chunk tail.
    """
    idx = (jnp.asarray(length, jnp.int32) - 1).reshape(
        (-1,) + (1,) * (x.ndim - 1)
    )
    return jnp.take_along_axis(x, idx, axis=1)[:, 0]


def conv_state_slice(state: jax.Array, seq: jax.Array,
                     length: jax.Array) -> jax.Array:
    """Trailing causal-conv inputs after consuming ``length`` chunk tokens.

    ``state``: (B, W-1, D) pre-chunk conv state (the W-1 inputs before
    position ``pos``); ``seq``: (B, S, D) the chunk's raw conv inputs.
    Returns (B, W-1, D) — per-row inputs ``length-W+1 .. length-1`` of
    the concatenated stream, exactly the state sequential decode leaves
    behind after its ``length``-th token.
    """
    full = jnp.concatenate([state, seq], axis=1)
    cw = state.shape[1]
    idx = (jnp.asarray(length, jnp.int32)[:, None]
           + jnp.arange(cw, dtype=jnp.int32)[None, :])
    return jnp.take_along_axis(full, idx[:, :, None], axis=1)


def slot_gate(slot_mask: Optional[jax.Array], new_tree: Any, old_tree: Any) -> Any:
    """Per-row select between updated and previous decode state.

    ``slot_mask: bool[B]`` gates every leaf (batch axis 0) of a decode
    state update: active rows take the new value, inactive rows keep the
    old one **bitwise** — `jnp.where` selects rather than multiplies, so
    an inactive slot is write-inert even when its inputs are NaN (the
    masked-slot inertness contract of the slot scheduler).  ``None``
    passes the update through unchanged.
    """
    if slot_mask is None:
        return new_tree

    def blend(n, o):
        m = slot_mask.reshape(slot_mask.shape + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)

    return jax.tree_util.tree_map(blend, new_tree, old_tree)


# --------------------------------------------------------------------------
# FFN variants (unfused: the operator-fusion pass matches these)
# --------------------------------------------------------------------------


def swiglu_ffn(x: jax.Array, p: Params) -> jax.Array:
    g = linear(x, p["w_gate"])
    u = linear(x, p["w_up"])
    h = jax.nn.silu(g) * u
    return linear(h, p["w_down"])


def geglu_ffn(x: jax.Array, p: Params) -> jax.Array:
    g = linear(x, p["w_gate"])
    u = linear(x, p["w_up"])
    h = jax.nn.gelu(g) * u
    return linear(h, p["w_down"])


def gelu_ffn(x: jax.Array, p: Params) -> jax.Array:
    h = jax.nn.gelu(linear(x, p["w_fc"], p.get("b_fc")))
    return linear(h, p["w_out"], p.get("b_out"))


def ffn_init(
    key, d_model: int, d_ff: int, kind: str = "swiglu", bias: bool = False,
    dtype=jnp.bfloat16,
) -> Params:
    ks = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(ks[0], d_model, d_ff, dtype),
            "w_up": dense_init(ks[1], d_model, d_ff, dtype),
            "w_down": dense_init(ks[2], d_ff, d_model, dtype),
        }
    p = {
        "w_fc": dense_init(ks[0], d_model, d_ff, dtype),
        "w_out": dense_init(ks[1], d_ff, d_model, dtype),
    }
    if bias:
        p["b_fc"] = jnp.zeros((d_ff,), dtype)
        p["b_out"] = jnp.zeros((d_model,), dtype)
    return p


def apply_ffn(x: jax.Array, p: Params, kind: str) -> jax.Array:
    if kind == "swiglu":
        return swiglu_ffn(x, p)
    if kind == "geglu":
        return geglu_ffn(x, p)
    return gelu_ffn(x, p)
