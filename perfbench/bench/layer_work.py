"""Counted work of single layers, for the per-layer roofline readers.

A layer's roofline share is the least time its counted work needs on the
chip (per phase, the larger of FLOPs over peak FLOP/s and bytes over HBM
bandwidth) over the device time the trace gives its scopes.  The counted
work comes from the configuration's reference module (``counted_work``);
the program's counters and the served requests give how much of it a
window held.  A reader names its configuration: such a metric lists the
cells it is read in.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, Optional, Tuple

from . import correct

ROOT = Path(__file__).resolve().parents[2]


def counted(config: str):
    """The reference's counted work for ``perfbench/configs/<config>.json``."""
    cfg = json.loads((ROOT / "perfbench" / "configs" / f"{config}.json").read_text())
    return correct.load_reference(cfg["reference"]).counted_work(cfg)


def least_seconds(phases: Iterable[Tuple[float, float]], peak: Dict[str, float]) -> float:
    """Sum over phases of max(FLOPs / peak FLOP/s, bytes / HBM bandwidth)."""
    return sum(max(f / peak["bf16_flop_s"], b / peak["hbm_bytes_s"]) for f, b in phases)


def scope_seconds(ctx: Dict, *scopes: str) -> Optional[float]:
    """Device seconds in the named program scopes of a traced run; None
    where the trace has none of them."""
    sp = ctx.get("spans")
    if not sp:
        return None
    got = [sp["scope_s"][s] for s in scopes if s in sp["scope_s"]]
    return sum(got) if got else None


def expert_load(ctx: Dict) -> Optional[Dict[str, Tuple[int, int, int]]]:
    """The program's expert counter over the window, by phase: (picks that
    reached a held expert, held-expert slots, held experts picked), summed
    over MoE layers and dispatches; None where the program keeps none."""
    load = ctx["res"].get("expert_load")
    if not load:
        return None
    return {"decode": tuple(load[0]), "prefill": tuple(load[1])}


def served_keys(ctx: Dict) -> Dict[str, Tuple[int, int]]:
    """(queries, keys attended) by phase over the served requests, as
    ``bench.flops`` counts them: a prefill of positions skip..prompt-1,
    then one decode step per later token."""
    res = ctx["res"]
    out = {"prefill": [0, 0], "decode": [0, 0]}
    for r in res["requests"]:
        result = res["results"][r.rid]
        served = len(result["tokens"])
        if "error" in result or served < 1:
            continue
        prompt, skip = len(r.prompt), res["skips"][r.rid]
        n = prompt - skip
        out["prefill"][0] += n
        out["prefill"][1] += (skip + 1 + prompt) * n // 2
        m = served - 1
        out["decode"][0] += m
        out["decode"][1] += sum(prompt + j for j in range(1, m + 1))
    return {k: tuple(v) for k, v in out.items()}
