"""Operations and bytes of the serving work, counted from shapes.

Counted whatever implements it, and only the work the model needs.  What
a configuration counts comes from its reference module's
``counted_work(cfg)``, an object with the terms of :class:`Work`; this
module adds them up over a window:

* FLOPs per token: 2 x the matmul parameters a token passes through (the
  active ones), plus attention over the keys it attends;
* bytes: per dispatch the weights it reads, given the tokens in it (every
  weight once for a dense model; an expert layer reads the experts its
  tokens touch); per token the KV it writes, the live context it reads and
  the embedding row it gathers.

The peaks come from ``peaks.json``, keyed by ``device_kind``; an unknown
kind is an error.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, Protocol, Tuple

PEAKS = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


class Work(Protocol):
    """The per-configuration terms of the count."""

    #: matmul parameters one token passes through (active experts only)
    matmul_params: int
    #: KV bytes one token writes, and one attended key reads (all layers)
    kv_bytes_per_token: int
    #: bytes of the embedding row one token gathers
    embed_bytes_per_token: int

    def attn_flops(self, keys: int) -> int:
        """FLOPs of attention over ``keys`` keys, summed over the tokens."""

    def dispatch_weight_bytes(self, tokens: float) -> float:
        """Weight bytes one dispatch of ``tokens`` tokens reads."""


def prefill_work(counted: Work, prompt: int, skip: int) -> Tuple[int, int]:
    """(FLOPs, bytes other than weights) to prefill positions skip..prompt-1."""
    n = prompt - skip
    keys = (skip + 1 + prompt) * n // 2  # sum of p + 1 over the chunk
    flops = 2 * counted.matmul_params * n + counted.attn_flops(keys)
    kvb = counted.kv_bytes_per_token
    return flops, prompt * kvb + n * kvb + n * counted.embed_bytes_per_token


def decode_work(counted: Work, prompt: int, served: int) -> Tuple[int, int]:
    """(FLOPs, bytes other than weights) of the ``served - 1`` decode steps
    after a prefill of ``prompt`` tokens (the first token came from it)."""
    n = max(served - 1, 0)
    keys = sum(prompt + j for j in range(1, n + 1))
    flops = 2 * counted.matmul_params * n + counted.attn_flops(keys)
    kvb = counted.kv_bytes_per_token
    return flops, keys * kvb + n * kvb + n * counted.embed_bytes_per_token


def window_work(counted: Work, requests: Iterable[Tuple[int, int, int]],
                prefill_dispatches: int, decode_dispatches: int) -> Dict[str, float]:
    """Totals over ``(prompt, skip, served)`` per request and the window's
    dispatch counts; each phase's weight bytes per dispatch are taken at its
    mean tokens per dispatch (tokens prefilled, tokens decoded)."""
    fp = bp = fd = bd = 0
    n_pre = n_dec = 0
    for prompt, skip, served in requests:
        if served < 1:
            continue
        f, b = prefill_work(counted, prompt, skip)
        fp, bp = fp + f, bp + b
        f, b = decode_work(counted, prompt, served)
        fd, bd = fd + f, bd + b
        n_pre, n_dec = n_pre + prompt - skip, n_dec + served - 1

    def weights(tokens: int, dispatches: int):
        return dispatches * counted.dispatch_weight_bytes(tokens / dispatches) if dispatches else 0

    return {
        "prefill_flops": float(fp),
        "prefill_bytes": float(bp + weights(n_pre, prefill_dispatches)),
        "decode_flops": float(fd),
        "decode_bytes": float(bd + weights(n_dec, decode_dispatches)),
    }


def roofline_seconds(work: Dict[str, float], peak: Dict[str, float]) -> float:
    """Least time the counted work needs: per phase, the larger of FLOPs over
    peak FLOP/s and bytes over HBM bandwidth (summed over a phase first, so
    it never exceeds the sum of the per-dispatch bounds)."""
    f, b = peak["bf16_flop_s"], peak["hbm_bytes_s"]
    return (max(work["prefill_flops"] / f, work["prefill_bytes"] / b)
            + max(work["decode_flops"] / f, work["decode_bytes"] / b))
