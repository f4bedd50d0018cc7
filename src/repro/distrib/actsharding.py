"""Activation-sharding policy: explicit ``with_sharding_constraint``
annotations at attention/FFN boundaries (§Perf iteration 1).

Why this exists: GSPMD left alone infers shardings for the attention
internals from the TP-sharded QKV projections.  When ``n_kv_heads`` does
not divide the model axis (e.g. qwen2.5: kv=8 on a 16-way axis) the
inferred layout splits ``head_dim`` across devices, which turns the Q·Kᵀ
contraction into a partial-sum and ALL-REDUCES THE SCORE MATRIX —
~10 GiB/device/layer on the train_4k cells (measured via hloprof).

The policy constrains, Megatron-style:

* q heads      -> ``model`` axis (dropped if H doesn't divide),
* k/v kv-heads -> ``model`` if divisible else REPLICATED (each device
  holds all kv heads: the GQA-correct layout),
* token-major activations (B, S, d) -> batch over dp axes; optionally
  sequence over ``model`` ("sp" flavor) between blocks,
* logits stay vocab-sharded (the CE loss reduces over the sharded axis
  with cheap scalar collectives instead of gathering logits).

The policy is a context set by the launcher/dry-run (models stay pure):
no policy -> every hook is a no-op.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass, field
from typing import Any, List, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .sharding import dp_axes, safe_pspec

_POLICY: List["ActivationPolicy"] = []


@dataclass
class ActivationPolicy:
    mesh: Mesh
    tp_axis: str = "model"
    #: shard the sequence dim of (B,S,d) activations over model between
    #: blocks (sequence parallelism — §Perf lever, off by default)
    sequence_parallel: bool = False
    enabled: bool = True
    #: restrict to a subset of kinds (None = all).  e.g. {"logits"} pins
    #: only the LM-head output — the MoE archs want exactly that (head
    #: pins confirmed, attention pins refuted; EXPERIMENTS §Perf)
    only: Optional[frozenset] = None

    def spec_for(self, kind: str, shape) -> Optional[P]:
        if self.only is not None and kind not in self.only:
            return None
        dp = dp_axes(self.mesh)
        tp = self.tp_axis
        nd = len(shape)
        if kind == "heads":  # (B, H, S, D): q heads over model
            pat = (dp, tp, None, None)
        elif kind == "kv":  # (B, KVH, S, D): shard if divisible else repl
            pat = (dp, tp, None, None)
        elif kind == "tokens":  # (B, S, d)
            pat = (dp, tp if self.sequence_parallel else None, None)
        elif kind == "ffn_hidden":  # (B, S, f): hidden over model
            pat = (dp, None, tp)
        elif kind == "logits":  # (B, S, V): vocab over model
            pat = (dp, None, tp)
        elif kind == "moe_tokens":  # (T, D) flat token stream
            pat = (dp, None)
        else:
            return None
        if len(pat) != nd:
            return None
        return safe_pspec(shape, pat, self.mesh)


def current() -> Optional[ActivationPolicy]:
    return _POLICY[-1] if _POLICY else None


@contextlib.contextmanager
def use_policy(policy: Optional[ActivationPolicy]):
    if policy is None:
        yield
        return
    _POLICY.append(policy)
    try:
        yield
    finally:
        _POLICY.pop()


def constrain(x: jax.Array, kind: str) -> jax.Array:
    """Annotate ``x`` with the policy's layout for ``kind`` (no-op without
    an active policy — smoke tests and single-device runs skip it)."""
    pol = current()
    if pol is None or not pol.enabled:
        return x
    spec = pol.spec_for(kind, x.shape)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(pol.mesh, spec)
    )
