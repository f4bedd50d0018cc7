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

For the harness it also states the program's config fields it stands for
(``program_fields``), each weight's place in the program's pytree
(``layout``) and the work a token and a dispatch cost (``counted_work``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Tensor = Tuple[str, Tuple[int, ...], str, str]


def program_fields(cfg: Dict) -> Dict[str, object]:
    """The program's ModelConfig fields this configuration stands for."""
    return {
        "d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
        "n_heads": cfg["num_attention_heads"], "n_kv_heads": cfg["num_key_value_heads"],
        "vocab": cfg["vocab_size"], "rope_theta": float(cfg["rope_theta"]),
        "tie_embeddings": bool(cfg["tie_word_embeddings"]), "ffn": "swiglu",
        "norm": "rmsnorm", "family": "dense",
    }


def layout(cfg: Dict) -> Tuple[List[Tensor], List[Tuple[int, List[Tensor]]]]:
    """(global tensors, [(layers, per-layer tensors)]) as (name, shape,
    init, program path): one group of identical layers."""
    d, ff, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // H
    glob = [
        ("embed", (V, d), "embed", "embed"),
        ("final_norm", (d,), "norm", "final_norm/scale"),
        ("lm_head", (d, V), "linear", "lm_head"),
    ]
    per_layer = [
        ("attn_norm", (d,), "norm", "blocks/norm1/scale"),
        ("wq", (d, H * hd), "linear", "blocks/attn/wq"),
        ("wk", (d, KVH * hd), "linear", "blocks/attn/wk"),
        ("wv", (d, KVH * hd), "linear", "blocks/attn/wv"),
        ("wo", (H * hd, d), "linear", "blocks/attn/wo"),
        ("ffn_norm", (d,), "norm", "blocks/norm2/scale"),
        ("w_gate", (d, ff), "linear", "blocks/ffn/w_gate"),
        ("w_up", (d, ff), "linear", "blocks/ffn/w_up"),
        ("w_down", (ff, d), "linear", "blocks/ffn/w_down"),
    ]
    return glob, [(int(cfg["num_hidden_layers"]), per_layer)]


@dataclass(frozen=True)
class Dims:
    """Counted work of the dense decoder (``bench.flops.Work``): q, k, v, o
    and a SwiGLU feed-forward per layer, an untied LM head; K and V of every
    layer per token; every weight read once per dispatch."""

    layers: int
    d: int
    ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    bytes_per: int = 2

    @classmethod
    def of(cls, cfg: Dict) -> "Dims":
        d, H = cfg["hidden_size"], cfg["num_attention_heads"]
        return cls(layers=cfg["num_hidden_layers"], d=d, ff=cfg["intermediate_size"],
                   heads=H, kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg.get("head_dim") or d // H, vocab=cfg["vocab_size"],
                   bytes_per={"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]])

    @property
    def matmul_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        per_layer = self.d * q + 2 * self.d * kv + q * self.d + 3 * self.d * self.ff
        return self.layers * per_layer + self.d * self.vocab

    @property
    def weight_bytes(self) -> int:
        """Bytes of every weight: matmul weights and norm scales."""
        return self.matmul_params * self.bytes_per + (2 * self.layers + 1) * self.d * 4

    def dispatch_weight_bytes(self, tokens: float) -> int:
        return self.weight_bytes

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * self.bytes_per

    @property
    def embed_bytes_per_token(self) -> int:
        return self.d * self.bytes_per

    def attn_flops(self, keys: int) -> int:
        return 4 * self.layers * self.heads * self.head_dim * keys

    def token_flops(self, keys: int) -> int:
        return 2 * self.matmul_params + self.attn_flops(keys)


counted_work = Dims.of


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


def make_layer_fns(cfg: Dict, precision: str = "float32") -> List[Callable]:
    """``(h (B, S, d) float32, layer weights) -> h`` for one block, for the
    layout's one group."""
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

    return [jax.jit(layer)]


def embed(g: Dict, tokens: jax.Array) -> jax.Array:
    """Token ids (B, S) -> their embedding rows (B, S, d)."""
    return jnp.take(g["embed"], tokens, axis=0)


def make_head_fn(cfg: Dict, precision: str = "float32") -> Callable:
    """``(h (N, d) float32, global weights) -> logits (N, V)``."""
    eps = float(cfg["rms_norm_eps"])
    mm = _matmul(precision)
    return jax.jit(lambda h, g: mm(_rms(h, g["final_norm"], eps), g["lm_head"]))
