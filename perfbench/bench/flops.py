"""Operations and bytes of the serving work, counted from shapes.

Counted whatever implements it, and only the work the model needs:

* FLOPs per token: 2 x the matmul parameters (every layer's attention and
  feed-forward weights plus the LM head; the embedding is a gather), plus
  attention, 4 x layers x heads x head_dim x keys attended (QK^T and PV);
* bytes per dispatch: every weight once (matmul weights in the served dtype,
  norm scales in float32), plus the embedding rows gathered; per token the
  KV it writes and the live context it reads (all layers, K and V).

The peaks come from ``peaks.json``, keyed by ``device_kind``; an unknown
kind is an error.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, Tuple

PEAKS = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


@dataclass(frozen=True)
class Dims:
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
        """Bytes every dispatch reads once: matmul weights and norm scales."""
        return self.matmul_params * self.bytes_per + (2 * self.layers + 1) * self.d * 4

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * self.bytes_per

    def attn_flops(self, keys: int) -> int:
        return 4 * self.layers * self.heads * self.head_dim * keys

    def token_flops(self, keys: int) -> int:
        return 2 * self.matmul_params + self.attn_flops(keys)


def prefill_work(dims: Dims, prompt: int, skip: int) -> Tuple[int, int]:
    """(FLOPs, bytes other than weights) to prefill positions skip..prompt-1."""
    n = prompt - skip
    keys = (skip + 1 + prompt) * n // 2  # sum of p + 1 over the chunk
    flops = 2 * dims.matmul_params * n + dims.attn_flops(keys)
    kvb = dims.kv_bytes_per_token
    return flops, prompt * kvb + n * kvb + n * dims.d * dims.bytes_per


def decode_work(dims: Dims, prompt: int, served: int) -> Tuple[int, int]:
    """(FLOPs, bytes other than weights) of the ``served - 1`` decode steps
    after a prefill of ``prompt`` tokens (the first token came from it)."""
    n = max(served - 1, 0)
    keys = sum(prompt + j for j in range(1, n + 1))
    flops = 2 * dims.matmul_params * n + dims.attn_flops(keys)
    kvb = dims.kv_bytes_per_token
    return flops, keys * kvb + n * kvb + n * dims.d * dims.bytes_per


def window_work(dims: Dims, requests: Iterable[Tuple[int, int, int]],
                prefill_dispatches: int, decode_dispatches: int) -> Dict[str, float]:
    """Totals over ``(prompt, skip, served)`` per request and the window's
    dispatch counts."""
    fp = bp = fd = bd = 0
    for prompt, skip, served in requests:
        if served < 1:
            continue
        f, b = prefill_work(dims, prompt, skip)
        fp, bp = fp + f, bp + b
        f, b = decode_work(dims, prompt, served)
        fd, bd = fd + f, bd + b
    return {
        "prefill_flops": float(fp),
        "prefill_bytes": float(bp + prefill_dispatches * dims.weight_bytes),
        "decode_flops": float(fd),
        "decode_bytes": float(bd + decode_dispatches * dims.weight_bytes),
    }


def roofline_seconds(work: Dict[str, float], peak: Dict[str, float]) -> float:
    """Least time the counted work needs: per phase, the larger of FLOPs over
    peak FLOP/s and bytes over HBM bandwidth (summed over a phase first, so
    it never exceeds the sum of the per-dispatch bounds)."""
    f, b = peak["bf16_flop_s"], peak["hbm_bytes_s"]
    return (max(work["prefill_flops"] / f, work["prefill_bytes"] / b)
            + max(work["decode_flops"] / f, work["decode_bytes"] / b))
