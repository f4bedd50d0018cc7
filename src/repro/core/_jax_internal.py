"""Centralized imports of jax internals used by the Forge-UGC core.

Everything version-sensitive lives here so the rest of the compiler only
touches this module.  Verified against jax 0.9.0 (the version
``pyproject.toml`` pins).
"""
from __future__ import annotations

from jax._src.core import (
    ClosedJaxpr,
    Jaxpr,
    JaxprEqn,
    Literal,
    Primitive,
    ShapedArray,
    Var,
    eval_jaxpr,
    trace_state_clean,
)

__all__ = [
    "ClosedJaxpr",
    "Jaxpr",
    "JaxprEqn",
    "Literal",
    "Primitive",
    "ShapedArray",
    "Var",
    "eval_jaxpr",
    "jaxpr_as_fun",
    "trace_state_clean",
]


def jaxpr_as_fun(closed: ClosedJaxpr):
    """Return a callable evaluating ``closed`` on positional args."""

    def fun(*args):
        out = eval_jaxpr(closed.jaxpr, closed.consts, *args)
        return out

    return fun
