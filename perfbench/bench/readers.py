"""Arithmetic that per-layer metric readers share.

Each reader in ``perfbench/metrics/<metric>.py`` takes the run's context
and returns a number, or None where the run gave it nothing to read; the
harness then leaves the metric out.  Shares are in %.  The context
(``run.run_cell``) holds ``res`` (the program's ``run()`` result),
``counters`` (the window's differences of the program's counters),
``work`` (``flops.window_work``), ``peak``, ``roofline_s``, ``in_flight_s``,
``setup_s``, and in traced runs ``trace`` (``trace.reduce``) and ``spans``
(``spans.reduce``: the device time by the program's scopes, idle by its
spans); both are None in untraced runs.
"""
from __future__ import annotations

from typing import Dict, Optional

from . import trace


def occupancy(ctx: Dict) -> Optional[float]:
    res = ctx["res"]
    if not res.get("capacity_row_steps"):
        return None
    return 100.0 * res["occupancy"]


def prefill_pad_share(ctx: Dict) -> Optional[float]:
    c = ctx["counters"]
    if not c["prefill_cells"]:
        return None
    return 100.0 * (1.0 - c["tokens_prefilled"] / c["prefill_cells"])


def prefill_skip_share(ctx: Dict) -> Optional[float]:
    c = ctx["counters"]
    n = c["tokens_reused"] + c["tokens_prefilled"]
    return 100.0 * c["tokens_reused"] / n if n else None


def dispatches_per_tick(ctx: Dict) -> Optional[float]:
    c = ctx["counters"]
    if not c["decode_calls"]:
        return None
    return c["decode_dispatches"] / c["decode_calls"]


def step_mfu(ctx: Dict) -> Optional[float]:
    w, s = ctx["work"], ctx["in_flight_s"]
    if not s or not ctx["peak"]:
        return None
    flops = w["prefill_flops"] + w["decode_flops"]
    return 100.0 * flops / (s * ctx["peak"]["bf16_flop_s"])


def kernels_roofline(ctx: Dict) -> Optional[float]:
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s") or ctx["roofline_s"] is None:
        return None
    return 100.0 * ctx["roofline_s"] / tr["busy_s"]


def idle_share(ctx: Dict) -> Optional[float]:
    share = trace.idle_share(ctx["trace"]) if ctx.get("trace") else None
    return None if share is None else 100.0 * share


def loop_idle_share(ctx: Dict) -> Optional[float]:
    sp = ctx.get("spans")
    if not sp or not sp["window_s"]:
        return None
    return 100.0 * sp["loop_idle_s"] / sp["window_s"]
