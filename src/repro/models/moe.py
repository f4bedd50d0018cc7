"""Mixture-of-Experts FFN: dropless per-token routing over a held share.

One formulation serves training and serving alike:

1. a float32 router scores all ``E`` routed experts (its width is the
   published one, whatever this program holds); softmax over the ``E``
   scores, greedy top-k, the k weights optionally renormalised and scaled;
2. the program holds ``H <= E`` experts, ids ``first .. first + H - 1``
   (one chip's share under expert parallelism; ``H == E`` holds all).
   Each (token, pick) whose expert is held goes to it; nothing is dropped
   and no capacity couples the tokens of a block, so a token's result
   depends on that token alone;
3. the picks are sorted by held expert and run as one grouped matmul per
   projection (``lax.ragged_dot``), so each held expert multiplies only
   the rows routed to it; picks of experts held elsewhere sort last and
   are computed by no group;
4. each token sums its held picks' outputs by weight, plus the shared
   experts (a SwiGLU every token passes through).

What the absent experts would add is left out: on one chip the layer runs
without the expert-parallel exchange.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import layers as L

Params = Dict[str, Any]


def moe_init(
    key,
    d_model: int,
    d_ff: int,
    n_experts: int,
    *,
    held: int = 0,
    shared_experts: int = 0,
    shared_d_ff: int = 0,
    dtype=jnp.bfloat16,
) -> Params:
    """Router over ``n_experts``; weights of ``held`` of them (0: all)."""
    ks = jax.random.split(key, 5)
    H = held or n_experts
    scale = 1.0 / math.sqrt(d_model)
    p: Params = {
        "router": (jax.random.normal(ks[0], (d_model, n_experts)) * scale
                   ).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (H, d_model, d_ff))
                   * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (H, d_model, d_ff))
                 * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (H, d_ff, d_model))
                   * (1.0 / math.sqrt(d_ff))).astype(dtype),
    }
    if shared_experts:
        p["shared"] = L.ffn_init(
            ks[4], d_model, shared_d_ff or d_ff * shared_experts,
            kind="swiglu", dtype=dtype,
        )
    return p


def route(xf: jax.Array, router: jax.Array, top_k: int, *,
          norm_topk_prob: bool = True,
          scaling: float = 1.0) -> Tuple[jax.Array, jax.Array]:
    """(weights (T, k) float32, expert ids (T, k)) of each token's picks."""
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router,
                        preferred_element_type=jnp.float32)
    w, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * scaling, idx


def moe_ffn(
    x: jax.Array,
    p: Params,
    *,
    top_k: int,
    first: int = 0,
    norm_topk_prob: bool = True,
    scaling: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y (B, S, D), picks (B, S, k) int32: the held
    expert each of a token's picks went to, ``H`` where it is held
    elsewhere)."""
    B, S, D = x.shape
    T = B * S
    H = p["w_gate"].shape[0]
    xf = x.reshape(T, D)

    with jax.named_scope("moe.route"):
        w, idx = route(xf, p["router"], top_k, norm_topk_prob=norm_topk_prob,
                       scaling=scaling)
        local = idx - first
        held = (local >= 0) & (local < H)
        # held picks sorted by expert; the rest sort last, in no group
        key = jnp.where(held, local, H).reshape(-1)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(jax.nn.one_hot(key, H, dtype=jnp.int32), axis=0)
        rows = xf[order // top_k]

    with jax.named_scope("moe.experts"):
        def grouped(a, wt):
            return lax.ragged_dot(a, wt, sizes, preferred_element_type=jnp.float32)

        h = (jax.nn.silu(grouped(rows, p["w_gate"]).astype(x.dtype))
             * grouped(rows, p["w_up"]).astype(x.dtype))
        out = grouped(h, p["w_down"])  # (T*k, D) float32, sorted
        # back to (token, pick) order, each token's picks summed by weight
        out = jnp.zeros_like(out).at[order].set(out).reshape(T, top_k, D)
        wk = jnp.where(held, w, 0.0)
        y = jnp.einsum("tkd,tk->td", jnp.where(held[..., None], out, 0.0), wk)

    if "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + L.swiglu_ffn(xf, p["shared"]).astype(jnp.float32)
    return y.astype(x.dtype).reshape(B, S, D), key.reshape(B, S, top_k)

