"""A test-only reference module: two layer groups over the program's MoE
family, to take a non-dense layout through the harness on the CPU.

The program holds ``experts_held`` of the configuration's
``num_local_experts`` routed experts (``program.overrides``), as a chip's
share under expert parallelism would.  Its layers are split into a first
group of ``first_group_layers`` and the rest, which the program stacks
under one path.  The layer functions do no arithmetic: each records its
group in ``CALLS`` and hands the hidden state on.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

#: the group of each layer function call, in order
CALLS: List[int] = []


def program_fields(cfg: Dict) -> Dict[str, object]:
    return {
        "family": "moe", "d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
        "n_heads": cfg["num_attention_heads"], "n_kv_heads": cfg["num_key_value_heads"],
        "n_experts": cfg["experts_held"], "top_k": cfg["num_experts_per_tok"],
        "vocab": cfg["vocab_size"], "tie_embeddings": bool(cfg["tie_word_embeddings"]),
    }


def _layer(cfg: Dict):
    d, ff, E = cfg["hidden_size"], cfg["intermediate_size"], cfg["experts_held"]
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // H
    return [
        ("attn_norm", (d,), "norm", "blocks/norm1/scale"),
        ("wq", (d, H * hd), "linear", "blocks/attn/wq"),
        ("wk", (d, KVH * hd), "linear", "blocks/attn/wk"),
        ("wv", (d, KVH * hd), "linear", "blocks/attn/wv"),
        ("wo", (H * hd, d), "linear", "blocks/attn/wo"),
        ("ffn_norm", (d,), "norm", "blocks/norm2/scale"),
        ("router", (d, E), "linear_f32", "blocks/moe/router"),
        ("w_gate", (E, d, ff), "linear", "blocks/moe/w_gate"),
        ("w_up", (E, d, ff), "linear", "blocks/moe/w_up"),
        ("w_down", (E, ff, d), "linear", "blocks/moe/w_down"),
    ]


def layout(cfg: Dict):
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    glob = [
        ("embed", (V, d), "embed", "embed"),
        ("final_norm", (d,), "norm", "final_norm/scale"),
        ("lm_head", (d, V), "linear", "lm_head"),
    ]
    first = int(cfg["first_group_layers"])
    return glob, [(first, _layer(cfg)), (int(cfg["num_hidden_layers"]) - first, _layer(cfg))]


def make_layer_fns(cfg: Dict, precision: str = "float32") -> List[Callable]:
    def group_fn(group):
        def layer(h, w):
            CALLS.append(group)
            return h
        return layer

    return [group_fn(0), group_fn(1)]


def embed(g: Dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(g["embed"], tokens, axis=0)


def make_head_fn(cfg: Dict, precision: str = "float32") -> Callable:
    return jax.jit(lambda h, g: h @ g["lm_head"])


@dataclass(frozen=True)
class MoeWork:
    """Attention and the held experts' share of each token; a dispatch of
    ``t`` tokens reads a held expert where one of its tokens picked it
    (``top_k`` of ``experts`` each, uniformly)."""

    layers: int
    attn_params: int
    expert_params: int
    experts: int
    held: int
    top_k: int
    d: int
    vocab: int
    kv_bytes_per_token: int
    bytes_per: int = 2

    @property
    def matmul_params(self) -> int:
        routed = self.top_k * self.held * self.expert_params // self.experts
        return self.layers * (self.attn_params + routed) + self.d * self.vocab

    @property
    def embed_bytes_per_token(self) -> int:
        return self.d * self.bytes_per

    def attn_flops(self, keys: int) -> int:
        return 4 * self.layers * self.d * keys

    def dispatch_weight_bytes(self, tokens: float) -> float:
        touched = self.held * (1.0 - (1.0 - self.top_k / self.experts) ** tokens)
        per_layer = self.attn_params + touched * self.expert_params
        return (self.layers * per_layer + self.d * self.vocab) * self.bytes_per


def counted_work(cfg: Dict) -> MoeWork:
    d, ff, H = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_attention_heads"]
    L, kv = cfg["num_hidden_layers"], cfg["num_key_value_heads"] * d // H
    return MoeWork(layers=L, attn_params=2 * d * d + 2 * d * kv, expert_params=3 * d * ff,
                   experts=cfg["num_local_experts"], held=cfg["experts_held"],
                   top_k=cfg["num_experts_per_tok"], d=d, vocab=cfg["vocab_size"],
                   kv_bytes_per_token=2 * L * kv * 2)
