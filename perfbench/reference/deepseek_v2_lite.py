"""Plain reference of DeepSeek-V2-Lite, one chip's share of its experts.

Written from the published description (arXiv:2405.04434 and the model's
``config.json``) in ``jax.numpy`` and float32 with ``highest`` matmul
precision, one layer's weights at a time; it imports nothing of the
program and reads no weight the program holds (:mod:`bench.weights` makes
each layer again from the seed).

Pre-norm blocks.  Attention is multi-head latent attention as published,
not absorbed: ``x Wq`` gives each head a 128-wide "nope" query and a
64-wide rope query; ``x Wkv_a`` gives a 512-wide latent, RMS-normed, and
one 64-wide rope key shared by the heads; the latent is up-projected by
``Wkv_b`` to each head's 128-wide key and value; the key is the nope part
beside the rotated shared key; causal softmax attention at scale
mscale^2 / sqrt(192); ``Wo``.  Rope is YaRN (factor 40 over 4096 original
positions, beta 32 and 1; cos and sin unscaled since ``mscale`` equals
``mscale_all_dim``; mscale = 0.1 * 0.707 * ln 40 + 1).  Layer 0's
feed-forward is a SwiGLU of width 10944; every later layer's is the
expert layer: a float32 router scores all 64 routed experts, softmax,
greedy top 6, weights not renormalised (``routed_scaling_factor`` 1);
of the routed experts (SwiGLU, width 1408) this chip holds
``n_routed_experts`` (ids 0..7 of 64, rank 0 of 8-way expert
parallelism) and adds, for every token, each held expert it picked,
times its weight; what the other 56 experts would add is left out, as
in the program; plus the 2 shared experts (one SwiGLU of width 2816).
Final RMSNorm, untied LM head.

Departures from the published model, each without effect on a run with
seeded weights: rope pairs are half-split (the checkpoint's interleaved
pairs are a column permutation of ``Wq`` and ``Wkv_a`` at load), and the
experts absent from this chip are left out as above.

``precision="fp8"`` is the control: every matrix multiplication's operands
rounded to float8 e4m3 (a scale per row of activations and per output
column of weights), accumulated in float32; the router stays float32, as
in the program.

For the harness it also states the program's config fields it stands for
(``program_fields``), each weight's place in the program's pytree
(``layout``: the dense layer and the MoE layers as two groups) and the
work a token and a dispatch cost (``counted_work``, by the program's
path: absorbed latent attention over the latent rows in both phases, the
held experts' grouped matmuls over the picks that reach them).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Tensor = Tuple[str, Tuple[int, ...], str, str]


def _router_width(cfg: Dict) -> int:
    return int(cfg.get("published", {}).get("n_routed_experts", cfg["n_routed_experts"]))


def _yarn(cfg: Dict) -> Dict:
    return cfg["rope_scaling"]


def program_fields(cfg: Dict) -> Dict[str, object]:
    """The program's ModelConfig fields this configuration stands for."""
    ys = _yarn(cfg)
    return {
        "family": "moe", "d_model": cfg["hidden_size"], "n_heads": cfg["num_attention_heads"],
        "d_ff": cfg["moe_intermediate_size"], "vocab": cfg["vocab_size"],
        "n_experts": _router_width(cfg), "experts_held": cfg["n_routed_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "norm_topk_prob": bool(cfg["norm_topk_prob"]),
        "routed_scaling_factor": float(cfg["routed_scaling_factor"]),
        "shared_experts": cfg["n_shared_experts"],
        "shared_d_ff": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "first_dense_layers": cfg["first_k_dense_replace"],
        "dense_d_ff": cfg["intermediate_size"], "kv_lora_rank": cfg["kv_lora_rank"],
        "qk_nope_head_dim": cfg["qk_nope_head_dim"],
        "qk_rope_head_dim": cfg["qk_rope_head_dim"], "v_head_dim": cfg["v_head_dim"],
        "rope_theta": float(cfg["rope_theta"]), "yarn_factor": float(ys["factor"]),
        "yarn_original_max_pos": ys["original_max_position_embeddings"],
        "yarn_beta_fast": float(ys["beta_fast"]), "yarn_beta_slow": float(ys["beta_slow"]),
        "yarn_mscale": float(ys["mscale"]), "yarn_mscale_all_dim": float(ys["mscale_all_dim"]),
        "tie_embeddings": bool(cfg["tie_word_embeddings"]), "ffn": "swiglu", "norm": "rmsnorm",
    }


def _attn(cfg: Dict, path: str) -> List[Tensor]:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    r, nope, rope, v = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    return [
        ("attn_norm", (d,), "norm", f"{path}/norm1/scale"),
        ("wq", (d, H * (nope + rope)), "linear", f"{path}/attn/wq"),
        ("wkv_a", (d, r + rope), "linear", f"{path}/attn/wkv_a"),
        ("kv_norm", (r,), "norm", f"{path}/attn/kv_norm/scale"),
        ("wkv_b", (r, H * (nope + v)), "linear", f"{path}/attn/wkv_b"),
        ("wo", (H * v, d), "linear", f"{path}/attn/wo"),
        ("ffn_norm", (d,), "norm", f"{path}/norm2/scale"),
    ]


def layout(cfg: Dict) -> Tuple[List[Tensor], List[Tuple[int, List[Tensor]]]]:
    """(global tensors, [(layers, per-layer tensors)]): the leading dense
    layers, then the MoE layers, as (name, shape, init, program path)."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    ff, f = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    E, fs = cfg["n_routed_experts"], cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    glob = [
        ("embed", (V, d), "embed", "embed"),
        ("final_norm", (d,), "norm", "final_norm/scale"),
        ("lm_head", (d, V), "linear", "lm_head"),
    ]
    dense = _attn(cfg, "dense_blocks") + [
        ("w_gate", (d, ff), "linear", "dense_blocks/ffn/w_gate"),
        ("w_up", (d, ff), "linear", "dense_blocks/ffn/w_up"),
        ("w_down", (ff, d), "linear", "dense_blocks/ffn/w_down"),
    ]
    moe = _attn(cfg, "blocks") + [
        ("router", (d, _router_width(cfg)), "linear_f32", "blocks/moe/router"),
        ("w_gate", (E, d, f), "linear", "blocks/moe/w_gate"),
        ("w_up", (E, d, f), "linear", "blocks/moe/w_up"),
        ("w_down", (E, f, d), "linear", "blocks/moe/w_down"),
        ("shared_gate", (d, fs), "linear", "blocks/moe/shared/w_gate"),
        ("shared_up", (d, fs), "linear", "blocks/moe/shared/w_up"),
        ("shared_down", (fs, d), "linear", "blocks/moe/shared/w_down"),
    ]
    F = int(cfg["first_k_dense_replace"])
    return glob, [(F, dense), (int(cfg["num_hidden_layers"]) - F, moe)]


# -- the forward pass ---------------------------------------------------------


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with a per-slice absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _matmul(precision: str) -> Callable:
    def mm(x, w):
        if precision == "fp8":
            x, w = _fp8(x, -1), _fp8(w, -2)
        return jnp.einsum("...k,kn->...n", x, w, precision="highest")
    return mm


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, ys: Dict) -> np.ndarray:
    """YaRN's inverse frequencies: the rope frequencies theta^(-2i/dim)
    kept where a pair turns more than beta_fast times over the original
    context, divided by the factor where it turns fewer than beta_slow
    times, and linearly blended between (the published correction range,
    floored and ceiled)."""
    orig, factor = ys["original_max_position_embeddings"], ys["factor"]

    def dim_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(ys["beta_fast"])), 0)
    high = min(math.ceil(dim_of(ys["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extrapolated = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    interpolated = extrapolated / factor
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return interpolated * (1 - keep) + extrapolated * keep


def softmax_scale(cfg: Dict) -> float:
    ys = _yarn(cfg)
    m = yarn_mscale(ys["factor"], ys["mscale_all_dim"]) if ys.get("mscale_all_dim") else 1.0
    return m * m / math.sqrt(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])


def _rope(x, pos, inv_freq, scale):
    """x: (B, H, S, D), half-split pairs; pos: (S,)."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(mm, x, g, u, d):
    return mm(jax.nn.silu(mm(x, g)) * mm(x, u), d)


def make_layer_fns(cfg: Dict, precision: str = "float32") -> List[Callable]:
    """``(h (B, S, d) float32, layer weights) -> h``: the dense layer's
    function and the MoE layers' function, for the layout's two groups."""
    H = cfg["num_attention_heads"]
    r, nope, rope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    eps, ys = float(cfg["rms_norm_eps"]), _yarn(cfg)
    inv_freq = yarn_inv_freq(rope, float(cfg["rope_theta"]), ys)
    rope_scale = yarn_mscale(ys["factor"], ys["mscale"]) / yarn_mscale(
        ys["factor"], ys["mscale_all_dim"])
    scale = softmax_scale(cfg)
    k, E = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    renorm, alpha = bool(cfg["norm_topk_prob"]), float(cfg["routed_scaling_factor"])
    mm = _matmul(precision)

    def mla(h, w):
        B, S, _ = h.shape
        pos = jnp.arange(S)
        x = _rms(h, w["attn_norm"], eps)
        q = mm(x, w["wq"]).reshape(B, S, H, nope + rope).transpose(0, 2, 1, 3)
        kv = mm(x, w["wkv_a"])
        latent = _rms(kv[..., :r], w["kv_norm"], eps)
        kvb = mm(latent, w["wkv_b"]).reshape(B, S, H, -1).transpose(0, 2, 1, 3)
        k_pe = _rope(kv[:, None, :, r:], pos, inv_freq, rope_scale)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, inv_freq, rope_scale)], -1)
        key = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(k_pe, (B, H, S, rope))], -1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, key, precision="highest") * scale
        s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
        a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), kvb[..., nope:],
                       precision="highest")
        return h + mm(a.transpose(0, 2, 1, 3).reshape(B, S, -1), w["wo"])

    def dense_layer(h, w):
        h = mla(h, w)
        x = _rms(h, w["ffn_norm"], eps)
        return h + _swiglu(mm, x, w["w_gate"], w["w_up"], w["w_down"])

    def moe_layer(h, w):
        h = mla(h, w)
        x = _rms(h, w["ffn_norm"], eps)
        probs = jax.nn.softmax(jnp.einsum("...d,de->...e", x, w["router"],
                                          precision="highest"), -1)
        top, idx = jax.lax.top_k(probs, k)
        if renorm:
            top = top / jnp.sum(top, -1, keepdims=True)
        top = top * alpha
        out = _swiglu(mm, x, w["shared_gate"], w["shared_up"], w["shared_down"])
        for e in range(E):  # the held experts, ids 0 .. E-1
            weight = jnp.sum(jnp.where(idx == e, top, 0.0), -1, keepdims=True)
            out = out + weight * _swiglu(mm, x, w["w_gate"][e], w["w_up"][e], w["w_down"][e])
        return h + out

    return [jax.jit(dense_layer), jax.jit(moe_layer)]


def embed(g: Dict, tokens: jax.Array) -> jax.Array:
    """Token ids (B, S) -> their embedding rows (B, S, d)."""
    return jnp.take(g["embed"], tokens, axis=0)


def make_head_fn(cfg: Dict, precision: str = "float32") -> Callable:
    """``(h (N, d) float32, global weights) -> logits (N, V)``."""
    eps = float(cfg["rms_norm_eps"])
    mm = _matmul(precision)
    return jax.jit(lambda h, g: mm(_rms(h, g["final_norm"], eps), g["lm_head"]))


# -- counted work -------------------------------------------------------------


@dataclass(frozen=True)
class MlaMoeWork:
    """Counted work (``bench.flops.Work``) by the program's path.

    Per token and layer: the attention projections, W_UK and W_UV applied
    to the query and the output (absorbed, in both phases: the same
    parameter count as ``Wkv_b``), ``Wo``; the dense layer's SwiGLU or the
    MoE layer's router, shared experts and the held experts its picks
    reach (``top_k * held / experts`` expert SwiGLUs a token on average,
    routing taken as uniform); the LM head.  Per attended key and layer:
    scores against the whole latent row and the latent sum,
    2 * heads * (2 * rank + rope) FLOPs, and the row's bytes.  A dispatch
    of ``t`` tokens reads every weight outside the routed experts once,
    and of those the held experts its picks reach."""

    layers: int
    dense_layers: int
    d: int
    heads: int
    rank: int
    nope: int
    rope: int
    v: int
    dense_ff: int
    expert_ff: int
    shared_ff: int
    experts: int  # the router's width
    held: int
    top_k: int
    vocab: int
    bytes_per: int = 2

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def attn_params(self) -> int:
        d, H = self.d, self.heads
        return (d * H * (self.nope + self.rope) + d * (self.rank + self.rope)
                + self.rank * H * (self.nope + self.v) + H * self.v * d)

    @property
    def expert_params(self) -> int:
        return 3 * self.d * self.expert_ff

    @property
    def held_picks_per_token(self) -> float:
        return self.top_k * self.held / self.experts

    @property
    def matmul_params(self) -> int:
        moe = (self.d * self.experts + 3 * self.d * self.shared_ff
               + self.held_picks_per_token * self.expert_params)
        return int(self.layers * self.attn_params + self.dense_layers * 3 * self.d * self.dense_ff
                   + self.moe_layers * moe + self.d * self.vocab)

    @property
    def kv_bytes_per_token(self) -> int:
        return self.layers * (self.rank + self.rope) * self.bytes_per

    @property
    def embed_bytes_per_token(self) -> int:
        return self.d * self.bytes_per

    def attn_flops(self, keys: int) -> int:
        return 2 * self.layers * self.heads * (2 * self.rank + self.rope) * keys

    def dispatch_weight_bytes(self, tokens: float) -> float:
        b = self.bytes_per
        shared = (self.layers * self.attn_params + self.dense_layers * 3 * self.d * self.dense_ff
                  + self.moe_layers * 3 * self.d * self.shared_ff + self.d * self.vocab) * b
        router = self.moe_layers * self.d * self.experts * 4
        norms = (self.layers * (2 * self.d + self.rank) + self.d) * 4
        touched = self.held * (1.0 - (1.0 - self.top_k / self.experts) ** tokens)
        return shared + router + norms + self.moe_layers * touched * self.expert_params * b

    # -- the per-layer metrics' terms --------------------------------------

    def latent_attention_work(self, queries: float, keys: float,
                              dispatches: float) -> Tuple[float, float]:
        """(FLOPs, bytes) of absorbed latent attention over all layers for
        ``queries`` tokens attending ``keys`` keys in all, in
        ``dispatches`` dispatches: W_UK and W_UV on each query, scores and
        the latent sum per key; bytes of the latent rows read, and of W_UK
        and W_UV once a dispatch."""
        w_ukv = self.rank * self.heads * (self.nope + self.v)
        flops = self.layers * 2 * w_ukv * queries + self.attn_flops(keys)
        bytes_ = (self.kv_bytes_per_token * keys
                  + self.layers * w_ukv * self.bytes_per * dispatches)
        return float(flops), float(bytes_)


def counted_work(cfg: Dict) -> MlaMoeWork:
    return MlaMoeWork(
        layers=int(cfg["num_hidden_layers"]), dense_layers=int(cfg["first_k_dense_replace"]),
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"], rank=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
        dense_ff=cfg["intermediate_size"], expert_ff=cfg["moe_intermediate_size"],
        shared_ff=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        experts=_router_width(cfg), held=cfg["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"], vocab=cfg["vocab_size"],
        bytes_per={"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]])
