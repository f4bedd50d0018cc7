"""Model zoo: one module per architecture family, uniform API.

``get_model(cfg)`` returns a :class:`ModelAPI` with

* ``init(key, cfg)``                           params pytree
* ``apply(params, *inputs, cfg)``              full-sequence logits
* ``init_cache(...)``                          decode state (None if N/A)
* ``decode_step(params, cache, tok, pos, cfg)`` one-token serve step
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

from ..configs.base import ModelConfig
from . import (
    attention,
    encdec,
    layers,
    losses,
    moe,
    rglru,
    transformer,
    vlm,
    xlstm,
)


@dataclass(frozen=True)
class ModelAPI:
    family: str
    init: Callable
    apply: Callable
    decode_step: Optional[Callable]
    #: whole-prompt batched prefill — (params, cache, tokens(B,S), pos)
    #: -> ((B,S,V) logits, cache); recurrent families fold the chunk
    #: into state via an associative scan (see prefill_takes_length);
    #: None only when a whole-block pass cannot reproduce sequential
    #: decode — those prefill sequentially
    prefill_step: Optional[Callable]
    init_cache: Optional[Callable]
    module: Any
    #: True when the decode cache carries NON-positional state (rg-lru
    #: h/conv, xLSTM cells): a KV row is reusable as-is because the
    #: per-row position mask hides stale entries, but recurrent state
    #: folds every past token in — a slot swap-in must reset the row to
    #: its init_cache values before the new request's first step
    stateful_decode: bool = False
    #: paged-KV entry points (None for families without a paged path):
    #: decode/prefill against a flat page pool + per-slot page table
    #: (see core/paging.py); init_paged_cache(cfg, batch, max_len, *,
    #: num_pages, page_size) builds the state
    paged_decode_step: Optional[Callable] = None
    paged_prefill_step: Optional[Callable] = None
    init_paged_cache: Optional[Callable] = None
    #: True when ``prefill_step`` accepts a per-row ``length=`` kwarg:
    #: recurrent state consumes every chunk token (no positional mask
    #: can hide padding afterwards), so the serve fronts must tell the
    #: scan where each row's real prompt ends
    prefill_takes_length: bool = False


def get_model(cfg: ModelConfig) -> ModelAPI:
    if cfg.family in ("dense", "moe"):
        m = transformer
    elif cfg.family == "hybrid":
        m = rglru
    elif cfg.family == "ssm":
        m = xlstm
    elif cfg.family == "encdec":
        m = encdec
    elif cfg.family == "vlm":
        m = vlm
    else:
        raise ValueError(f"unknown family {cfg.family!r}")
    # a family module owns the knowledge of when a whole-block prefill
    # pass reproduces sequential decode (``supports_batched_prefill``);
    # the registry stays family-agnostic
    prefill = getattr(m, "prefill_step", None)
    supports = getattr(m, "supports_batched_prefill", None)
    if prefill is not None and supports is not None and not supports(cfg):
        prefill = None
    paged_prefill = getattr(m, "paged_prefill_step", None)
    if paged_prefill is not None and supports is not None and not supports(cfg):
        paged_prefill = None
    return ModelAPI(
        family=cfg.family,
        init=m.init,
        apply=m.apply,
        decode_step=getattr(m, "decode_step", None),
        prefill_step=prefill,
        init_cache=getattr(m, "init_cache", None),
        module=m,
        stateful_decode=getattr(m, "STATEFUL_DECODE", False),
        paged_decode_step=getattr(m, "paged_decode_step", None),
        paged_prefill_step=paged_prefill,
        init_paged_cache=getattr(m, "init_paged_cache", None),
        prefill_takes_length=(
            prefill is not None
            and getattr(m, "PREFILL_TAKES_LENGTH", False)
        ),
    )


__all__ = [
    "ModelAPI",
    "get_model",
    "attention",
    "encdec",
    "layers",
    "losses",
    "moe",
    "rglru",
    "transformer",
    "vlm",
    "xlstm",
]
