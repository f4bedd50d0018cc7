"""Plain reference of a dense Llama-style decoder (Phi-3-mini, DeepSeek LLM 7B).

Pre-norm blocks: RMSNorm → multi-head attention with rotary embeddings
(half-split rotation, ``rope_theta``), causal and, where the configuration
has one, a sliding window → residual; RMSNorm → SwiGLU feed-forward
(``silu(x Wg) * (x Wu)) Wd``) → residual; final RMSNorm; untied LM head.
Written from the published model description in ``jax.numpy`` and float32
with ``highest`` matmul precision, one layer's weights at a time.  It
imports nothing of the program and reads no weight the program holds: each
layer is made again from the seed by :mod:`bench.weights`.

``precision="fp8"`` is the control: the same forward pass with every
matrix multiplication's operands rounded to float8 e4m3 (a scale per row of
activations and per output column of weights, as an fp8 serving path would
use), accumulated in float32.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def layout(cfg: Dict) -> Tuple[List[Tuple[str, Tuple[int, ...], str]],
                               List[Tuple[str, Tuple[int, ...], str]]]:
    """(global tensors, per-layer tensors) as (name, shape, init)."""
    d, ff, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // H
    glob = [
        ("embed", (V, d), "embed"),
        ("final_norm", (d,), "norm"),
        ("lm_head", (d, V), "linear"),
    ]
    per_layer = [
        ("attn_norm", (d,), "norm"),
        ("wq", (d, H * hd), "linear"),
        ("wk", (d, KVH * hd), "linear"),
        ("wv", (d, KVH * hd), "linear"),
        ("wo", (H * hd, d), "linear"),
        ("ffn_norm", (d,), "norm"),
        ("w_gate", (d, ff), "linear"),
        ("w_up", (d, ff), "linear"),
        ("w_down", (ff, d), "linear"),
    ]
    return glob, per_layer


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with a per-slice absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _matmul(precision: str) -> Callable:
    def mm(x, w):
        if precision == "fp8":
            x, w = _fp8(x, -1), _fp8(w, 0)
        return jnp.einsum("...k,kn->...n", x, w, precision="highest")
    return mm


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (B, H, S, D); pos: (S,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def make_layer_fn(cfg: Dict, precision: str = "float32") -> Callable:
    """``(h (B, S, d) float32, layer weights) -> h`` for one block."""
    d = cfg["hidden_size"]
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // H
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    window = cfg.get("sliding_window")
    mm = _matmul(precision)

    def layer(h, w):
        B, S, _ = h.shape
        pos = jnp.arange(S)
        x = _rms(h, w["attn_norm"], eps)
        q = mm(x, w["wq"]).reshape(B, S, H, hd).transpose(0, 2, 1, 3)
        k = mm(x, w["wk"]).reshape(B, S, KVH, hd).transpose(0, 2, 1, 3)
        v = mm(x, w["wv"]).reshape(B, S, KVH, hd).transpose(0, 2, 1, 3)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k = jnp.repeat(k, H // KVH, axis=1)
        v = jnp.repeat(v, H // KVH, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") / np.sqrt(hd)
        keep = pos[:, None] >= pos[None, :]
        if window:
            keep &= pos[:, None] - pos[None, :] < window
        s = jnp.where(keep, s, -jnp.inf)
        a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v, precision="highest")
        h = h + mm(a.transpose(0, 2, 1, 3).reshape(B, S, H * hd), w["wo"])
        x = _rms(h, w["ffn_norm"], eps)
        return h + mm(jax.nn.silu(mm(x, w["w_gate"])) * mm(x, w["w_up"]), w["w_down"])

    return jax.jit(layer)


def make_head_fn(cfg: Dict, precision: str = "float32") -> Callable:
    """``(h (N, d) float32, final_norm, lm_head) -> logits (N, V)``."""
    eps = float(cfg["rms_norm_eps"])
    mm = _matmul(precision)
    return jax.jit(lambda h, norm, head: mm(_rms(h, norm, eps), head))
