"""Phase 1 — graph capture (the ``torch.export`` analogue).

``trace_to_graph`` captures an arbitrary JAX-traceable function as an
:class:`~repro.core.graph.Graph` of flat ``lax`` primitives via
``jax.make_jaxpr``.  Wrapper equations (``jit``/``pjit``,
``custom_jvp_call``, ``custom_vjp_call``, ``remat``/``checkpoint``) are
inlined recursively so library functions such as ``jax.nn.softmax`` or
``jax.nn.silu`` appear as flat primitive chains — the ATen-level analogue
the optimization passes pattern-match against.

Exceptions to inlining:

* ``jit`` equations whose name starts with ``forge_`` are kept opaque —
  this is the *custom operator registration* hook (paper §9.5): model code
  can dispatch pre-fused kernels (e.g. the RG-LRU scan) as single graph
  nodes named ``forge.<name>`` that Phase 3 routes to the ``accel`` device.
* control-flow primitives (``scan`` / ``while`` / ``cond``) stay opaque.

Tied-weight resolution (paper §4.2.1): when the example inputs contain the
*same array object* at several pytree leaves (e.g. tied embedding /
LM-head), the duplicate graph inputs are merged onto one canonical input —
matching by object identity exactly like the paper's ``id()`` check.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ._jax_internal import ClosedJaxpr, Literal, ShapedArray, jaxpr_as_fun
from .graph import Graph, GLit, GNode, GVar, Operand

# wrapper primitives inlined during capture
_INLINE_PRIMS = {
    "jit",
    "pjit",
    "closed_call",
    "core_call",
    "custom_jvp_call",
    "custom_vjp_call",
    "custom_vjp_call_jaxpr",
    "remat",
    "checkpoint",
    "remat2",
    "custom_lin",
}

# name prefix that marks an opaque fused dispatch unit
FORGE_MARKER = "forge_"


@dataclass
class CaptureResult:
    graph: Graph
    in_tree: Any
    out_tree: Any
    n_inputs_raw: int
    tied_map: Dict[int, int] = field(default_factory=dict)  # dup leaf idx -> canonical idx
    capture_ms: float = 0.0
    #: per-raw-flat-leaf polymorphic axis vector: one tuple per leaf,
    #: one entry per polymorphic dimension (batch, sequence, …; None =
    #: that dimension is absent from the leaf).  Recorded at capture so
    #: later phases can pad/mask along every polymorphic axis.
    poly_axes: Tuple[Tuple[Optional[int], ...], ...] = ()
    #: the concrete extent of each polymorphic axis at capture time
    poly_extents: Tuple[int, ...] = ()

    @property
    def poly_extent(self) -> Optional[int]:
        """First (batch) polymorphic extent — the 1-D legacy view."""
        return self.poly_extents[0] if self.poly_extents else None

    def poly_axes_flat(self) -> Tuple[Tuple[Optional[int], ...], ...]:
        """Polymorphic axis vectors of the *executor-level* flat inputs.

        The executor signature drops tied duplicate leaves; this view
        drops their axis vectors identically so it zips with
        ``CompiledModule._flatten_inputs`` output.
        """
        if not self.poly_axes:
            return ()
        return tuple(
            a for i, a in enumerate(self.poly_axes) if i not in self.tied_map
        )


def _sub_jaxpr(eqn) -> Optional[ClosedJaxpr]:
    p = eqn.params
    for key in ("jaxpr", "call_jaxpr"):
        sub = p.get(key)
        if sub is None:
            continue
        if isinstance(sub, ClosedJaxpr):
            return sub
        # open jaxpr (e.g. remat) — close with no consts
        try:
            return ClosedJaxpr(sub, ())
        except Exception:
            return None
    return None


def _keep_opaque(eqn) -> bool:
    name = str(eqn.params.get("name", ""))
    return name.startswith(FORGE_MARKER)


def _join_scope(outer: str, inner: str) -> str:
    return f"{outer}/{inner}" if outer and inner else outer or inner


def from_closed_jaxpr(closed: ClosedJaxpr, *, inline: bool = True) -> Graph:
    """Build a Graph from a ClosedJaxpr, inlining wrapper equations."""
    g = Graph()
    env: Dict[Any, Operand] = {}

    def read(atom) -> Operand:
        if isinstance(atom, Literal):
            return GLit(np.asarray(atom.val), getattr(atom, "aval", None))
        return env[atom]

    def write(var, val: Operand) -> None:
        env[var] = val

    for v in closed.jaxpr.invars:
        write(v, g.add_input(v.aval))
    for cv, cval in zip(closed.jaxpr.constvars, closed.consts):
        write(cv, g.add_const(cval, getattr(cv, "aval", None)))

    def process(jaxpr, depth: int, prefix: str) -> None:
        for eqn in jaxpr.eqns:
            pname = eqn.primitive.name
            scope = _join_scope(prefix, str(eqn.source_info.name_stack))
            sub = _sub_jaxpr(eqn) if (inline and pname in _INLINE_PRIMS) else None
            if sub is not None and not _keep_opaque(eqn) and depth < 32:
                # inline: bind sub invars to our operands, consts to consts
                if len(sub.jaxpr.invars) == len(eqn.invars):
                    inner_env = {}
                    for sv, atom in zip(sub.jaxpr.invars, eqn.invars):
                        inner_env[sv] = read(atom)
                    for scv, sval in zip(sub.jaxpr.constvars, sub.consts):
                        inner_env[scv] = g.add_const(sval, getattr(scv, "aval", None))
                    saved = {k: env.get(k) for k in inner_env}
                    env.update(inner_env)
                    process(sub.jaxpr, depth + 1, scope)
                    for ov, sv in zip(eqn.outvars, sub.jaxpr.outvars):
                        write(ov, read(sv))
                    # NOTE: no env cleanup needed — jaxpr vars are unique objects
                    continue
            # opaque node
            op = pname
            # the named scope the equation was traced under; lowering
            # re-enters it so the replayed ops keep their op_name
            meta = {"scope": scope} if scope else {}
            if sub is not None and _keep_opaque(eqn):
                op = "forge." + str(eqn.params.get("name"))[len(FORGE_MARKER):]
                meta["call_jaxpr"] = sub
            node = g.add_node(
                op,
                eqn.primitive,
                dict(eqn.params),
                [read(a) for a in eqn.invars],
                [ov.aval for ov in eqn.outvars],
                meta,
            )
            for ov, gv in zip(eqn.outvars, node.outvars):
                write(ov, gv)

    process(closed.jaxpr, 0, "")
    g.outvars = [read(v) for v in closed.jaxpr.outvars]
    g.validate()
    return g


def resolve_tied_weights(flat_leaves: Sequence[Any]) -> Dict[int, int]:
    """Map duplicate-leaf index -> canonical index, by object identity.

    The JAX analogue of the paper's ``id()``-based tied-weight detection
    (Listing 2): two pytree leaves referencing the same array object are
    one logical parameter.
    """
    seen: Dict[int, int] = {}
    tied: Dict[int, int] = {}
    for i, leaf in enumerate(flat_leaves):
        if not hasattr(leaf, "shape"):
            continue
        key = id(leaf)
        if key in seen:
            tied[i] = seen[key]
        else:
            seen[key] = i
    return tied


def trace_to_graph(
    fn: Callable,
    *example_args: Any,
    tie_weights: bool = True,
    inline: bool = True,
    poly_axes: Any = None,
    poly_axes_nd: Optional[Sequence[Any]] = None,
) -> CaptureResult:
    """Capture ``fn`` as a Graph (Phase 1).

    ``example_args`` may be pytrees of concrete arrays or
    ``jax.ShapeDtypeStruct`` stand-ins (the dry-run path).

    ``poly_axes_nd`` holds one ``vmap``-``in_axes``-style tree prefix
    per polymorphic dimension (batch, sequence, …); ``poly_axes`` is the
    1-D shorthand for a single batch-polymorphic dimension.  The
    per-leaf axes and their concrete extents are recorded on the result
    for the bucketing front
    (:class:`~repro.core.compiler.BucketedModule`) — the captured graph
    itself is still specialized to the example (bucket) shapes.
    """
    t0 = time.perf_counter()
    flat, in_tree = jax.tree_util.tree_flatten(example_args)
    if poly_axes_nd is None and poly_axes is not None:
        poly_axes_nd = (poly_axes,)
    axes_flat: Tuple[Tuple[Optional[int], ...], ...] = ()
    poly_extents: Tuple[int, ...] = ()
    if poly_axes_nd is not None:
        from .shapekey import flatten_axes_nd, infer_extents

        axes_flat = tuple(flatten_axes_nd(poly_axes_nd, example_args))
        poly_extents = infer_extents(flat, axes_flat, len(poly_axes_nd))
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*example_args)
    _, out_tree = jax.tree_util.tree_flatten(out_shape)
    out_tree = jax.tree_util.tree_structure(out_shape)

    g = from_closed_jaxpr(closed, inline=inline)

    tied: Dict[int, int] = {}
    if tie_weights:
        tied = resolve_tied_weights(flat)
        if tied:
            # merge duplicate graph inputs onto their canonical input
            keep: List[GVar] = []
            for i, v in enumerate(g.invars):
                if i in tied:
                    g.replace_all_uses(v, g.invars[tied[i]])
                else:
                    keep.append(v)
            g.invars = keep

    res = CaptureResult(
        graph=g,
        in_tree=in_tree,
        out_tree=out_tree,
        n_inputs_raw=len(flat),
        tied_map=tied,
        capture_ms=(time.perf_counter() - t0) * 1e3,
        poly_axes=axes_flat,
        poly_extents=poly_extents,
    )
    return res


# --------------------------------------------------------------------------
# Graph evaluation (reference interpreter, used by constant folding,
# fidelity checks and as the pre-Phase-4 oracle)
# --------------------------------------------------------------------------


def eval_node(node: GNode, arg_vals: Sequence[Any]) -> List[Any]:
    """Evaluate one node on concrete/traced values."""
    if node.is_fused:
        from .fused_ops import fused_callable  # local import to avoid cycle

        fn = fused_callable(node)
        out = fn(*arg_vals)
    else:
        out = node.prim.bind(*arg_vals, **node.params)
    if not isinstance(out, (list, tuple)):
        out = [out]
    return list(out)


def graph_to_fn(g: Graph) -> Callable:
    """Return a JAX-traceable callable evaluating the graph on flat inputs."""

    def fn(*flat_inputs):
        if len(flat_inputs) != len(g.invars):
            raise TypeError(
                f"graph expects {len(g.invars)} inputs, got {len(flat_inputs)}"
            )
        env: Dict[int, Any] = {}
        for v, val in zip(g.invars, flat_inputs):
            env[v.vid] = val
        for v, val in zip(g.constvars, g.consts):
            env[v.vid] = val

        def read(o: Operand):
            return o.val if isinstance(o, GLit) else env[o.vid]

        for node in g.nodes.values():
            outs = eval_node(node, [read(iv) for iv in node.invars])
            for ov, val in zip(node.outvars, outs):
                env[ov.vid] = val
        return [read(o) for o in g.outvars]

    return fn
