"""RGraph — the mutable register-graph IR at the heart of Forge-UGC.

This is the JAX analogue of the paper's FX ``GraphModule``: a flat,
topologically ordered list of primitive operations over explicit SSA
values.  It is built from a jaxpr (Phase 1, :mod:`repro.core.capture`),
mutated in place by the six optimization passes (Phase 2,
:mod:`repro.core.passes`), and lowered to the typed register IR
(Phase 3, :mod:`repro.core.lowering`).

Design notes
------------
* ``GVar`` is an SSA value with a shape/dtype aval.  ``GLit`` is an
  immediate literal operand (scalars and small arrays frozen at capture
  time — the paper's "frozen args").
* ``GNode`` is one operation.  Multi-output primitives (``scan`` …) are
  supported via ``outvars`` being a list.
* The graph keeps use-def chains (``producer_of`` / ``users_of``) so the
  passes can do O(1) rewiring, mirroring FX's
  ``Node.replace_all_uses_with`` + ``graph.erase_node``.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Set, Tuple, Union

import numpy as np

from ._jax_internal import Primitive, ShapedArray


# --------------------------------------------------------------------------
# Values
# --------------------------------------------------------------------------


class GVar:
    """An SSA value produced by a node or fed as a graph input/constant."""

    __slots__ = ("vid", "aval", "name")

    def __init__(self, vid: int, aval: Any, name: str = ""):
        self.vid = vid
        self.aval = aval  # ShapedArray-like: has .shape and .dtype
        self.name = name or f"v{vid}"

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(getattr(self.aval, "shape", ()))

    @property
    def dtype(self):
        return getattr(self.aval, "dtype", None)

    def __repr__(self):  # pragma: no cover - debugging aid
        return f"%{self.name}:{self.dtype}{list(self.shape)}"


class GLit:
    """A literal operand frozen into the graph (paper: frozen args)."""

    __slots__ = ("val", "aval")

    def __init__(self, val: Any, aval: Any = None):
        self.val = val
        self.aval = aval

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(np.shape(self.val))

    @property
    def dtype(self):
        if self.aval is not None:
            return getattr(self.aval, "dtype", None)
        return np.asarray(self.val).dtype

    def __repr__(self):  # pragma: no cover
        return f"lit({self.val!r})"


Operand = Union[GVar, GLit]


# --------------------------------------------------------------------------
# Nodes
# --------------------------------------------------------------------------


class GNode:
    """One operation: a jax primitive application or a fused ``forge.*`` op."""

    __slots__ = ("nid", "op", "prim", "params", "invars", "outvars", "meta")

    def __init__(
        self,
        nid: int,
        op: str,
        prim: Optional[Primitive],
        params: Dict[str, Any],
        invars: List[Operand],
        outvars: List[GVar],
        meta: Optional[Dict[str, Any]] = None,
    ):
        self.nid = nid
        self.op = op
        self.prim = prim
        self.params = params
        self.invars = invars
        self.outvars = outvars
        self.meta = meta or {}

    @property
    def is_fused(self) -> bool:
        return self.op.startswith("forge.")

    def __repr__(self):  # pragma: no cover
        outs = ", ".join(map(repr, self.outvars))
        ins = ", ".join(map(repr, self.invars))
        return f"{outs} = {self.op}({ins})"


# --------------------------------------------------------------------------
# Graph
# --------------------------------------------------------------------------


class Graph:
    """Mutable, topologically ordered operation graph (the FX analogue)."""

    def __init__(self):
        self._vid = itertools.count()
        self._nid = itertools.count()
        # nid -> GNode; insertion order == topological order (maintained by
        # passes: replacements always occupy the position of the replaced
        # node's last member).
        self.nodes: Dict[int, GNode] = {}
        self.invars: List[GVar] = []
        self.constvars: List[GVar] = []
        self.consts: List[Any] = []
        self.outvars: List[Operand] = []
        # use-def chains
        self.producer_of: Dict[int, Tuple[int, int]] = {}  # vid -> (nid, out_idx)
        self.users_of: Dict[int, Set[int]] = {}  # vid -> {nid}

    # -- construction -------------------------------------------------------

    def new_var(self, aval, name: str = "") -> GVar:
        v = GVar(next(self._vid), aval, name)
        self.users_of[v.vid] = set()
        return v

    def add_input(self, aval, name: str = "") -> GVar:
        v = self.new_var(aval, name)
        self.invars.append(v)
        return v

    def add_const(self, value, aval=None, name: str = "") -> GVar:
        if aval is None:
            arr = np.asarray(value)
            aval = ShapedArray(arr.shape, arr.dtype)
        v = self.new_var(aval, name or f"c{len(self.consts)}")
        self.constvars.append(v)
        self.consts.append(value)
        return v

    def add_node(
        self,
        op: str,
        prim: Optional[Primitive],
        params: Dict[str, Any],
        invars: Sequence[Operand],
        out_avals: Sequence[Any],
        meta: Optional[Dict[str, Any]] = None,
    ) -> GNode:
        nid = next(self._nid)
        outvars = [self.new_var(a) for a in out_avals]
        node = GNode(nid, op, prim, dict(params), list(invars), outvars, meta)
        self.nodes[nid] = node
        for k, ov in enumerate(outvars):
            self.producer_of[ov.vid] = (nid, k)
        for iv in invars:
            if isinstance(iv, GVar):
                self.users_of.setdefault(iv.vid, set()).add(nid)
        return node

    # -- queries -------------------------------------------------------------

    def node_list(self) -> List[GNode]:
        return list(self.nodes.values())

    def producer(self, v: Operand) -> Optional[GNode]:
        if not isinstance(v, GVar):
            return None
        pr = self.producer_of.get(v.vid)
        return self.nodes.get(pr[0]) if pr else None

    def users(self, v: GVar) -> List[GNode]:
        return [self.nodes[n] for n in self.users_of.get(v.vid, ()) if n in self.nodes]

    def n_uses(self, v: GVar) -> int:
        """Number of *operand slots + graph outputs* referencing ``v``."""
        cnt = sum(
            1
            for nid in self.users_of.get(v.vid, ())
            if nid in self.nodes
            for iv in self.nodes[nid].invars
            if isinstance(iv, GVar) and iv.vid == v.vid
        )
        cnt += sum(1 for ov in self.outvars if isinstance(ov, GVar) and ov.vid == v.vid)
        return cnt

    def is_output(self, v: GVar) -> bool:
        return any(isinstance(ov, GVar) and ov.vid == v.vid for ov in self.outvars)

    def num_nodes(self) -> int:
        return len(self.nodes)

    # -- mutation ------------------------------------------------------------

    def replace_all_uses(self, old: GVar, new: Operand) -> None:
        """FX ``replace_all_uses_with``: rewire every consumer of ``old``."""
        for nid in list(self.users_of.get(old.vid, ())):
            node = self.nodes.get(nid)
            if node is None:
                continue
            changed = False
            for i, iv in enumerate(node.invars):
                if isinstance(iv, GVar) and iv.vid == old.vid:
                    node.invars[i] = new
                    changed = True
            if changed and isinstance(new, GVar):
                self.users_of.setdefault(new.vid, set()).add(nid)
        self.users_of[old.vid] = set()
        for i, ov in enumerate(self.outvars):
            if isinstance(ov, GVar) and ov.vid == old.vid:
                self.outvars[i] = new

    def erase_node(self, node: GNode) -> None:
        """FX ``graph.erase_node``: node outputs must be unused."""
        for ov in node.outvars:
            if self.n_uses(ov):
                raise ValueError(f"erase_node: {node.op} output {ov} still in use")
        for iv in node.invars:
            if isinstance(iv, GVar):
                s = self.users_of.get(iv.vid)
                if s is not None:
                    s.discard(node.nid)
        for ov in node.outvars:
            self.producer_of.pop(ov.vid, None)
        del self.nodes[node.nid]

    def insert_node_like(
        self,
        anchor: GNode,
        op: str,
        params: Dict[str, Any],
        invars: Sequence[Operand],
        out_avals: Sequence[Any],
        meta: Optional[Dict[str, Any]] = None,
    ) -> GNode:
        """Insert a new node occupying ``anchor``'s topological position.

        Used by fusion passes: the fused node replaces the last node of the
        matched chain, so def-before-use order is preserved.  Implemented by
        rebuilding the insertion-ordered dict once (O(n), passes call it
        rarely).
        """
        meta = dict(meta or {})
        if "scope" in anchor.meta:  # the fused node keeps the chain's scope
            meta.setdefault("scope", anchor.meta["scope"])
        node = self.add_node(op, None, params, invars, out_avals, meta)
        order: Dict[int, GNode] = {}
        for nid, n in self.nodes.items():
            if nid == node.nid:
                continue
            order[nid] = n
            if nid == anchor.nid:
                order[node.nid] = node
        if node.nid not in order:  # anchor missing => append (already there)
            order[node.nid] = node
        self.nodes = order
        return node

    # -- validation ----------------------------------------------------------

    def validate(self) -> None:
        """Check SSA & topological invariants; raise on violation."""
        defined: Set[int] = {v.vid for v in self.invars} | {v.vid for v in self.constvars}
        for node in self.nodes.values():
            for iv in node.invars:
                if isinstance(iv, GVar) and iv.vid not in defined:
                    raise AssertionError(
                        f"use before def: {iv} consumed by {node.op} (nid={node.nid})"
                    )
            for ov in node.outvars:
                if ov.vid in defined:
                    raise AssertionError(f"double definition of {ov}")
                defined.add(ov.vid)
        for ov in self.outvars:
            if isinstance(ov, GVar) and ov.vid not in defined:
                raise AssertionError(f"graph output {ov} is undefined")

    # -- structural metrics (cost model / CompilationResult inputs) ----------

    def depth(self) -> int:
        """Longest def-use chain length (graph depth, cost-model term)."""
        memo: Dict[int, int] = {}
        d = 0
        for node in self.nodes.values():
            best = 0
            for iv in node.invars:
                if isinstance(iv, GVar):
                    pr = self.producer_of.get(iv.vid)
                    if pr:
                        best = max(best, memo.get(pr[0], 0))
            memo[node.nid] = best + 1
            d = max(d, best + 1)
        return d

    def __repr__(self):  # pragma: no cover
        lines = ["graph {"]
        lines += [f"  in  {v!r}" for v in self.invars]
        lines += [f"  cst {v!r}" for v in self.constvars]
        lines += [f"  {n!r}" for n in self.nodes.values()]
        lines += [f"  out {v!r}" for v in self.outvars]
        lines.append("}")
        return "\n".join(lines)
