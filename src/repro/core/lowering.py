"""Phase 3 — lowering the optimized graph to the typed register IR (RGIR).

The JAX analogue of the paper's NPUIR (§4.4): every graph node becomes one
:class:`RGIROp` instruction carrying

* an **opcode** — ``accel.<op>`` for MXU-bound dispatches (all ``forge.*``
  fused nodes plus raw ``dot_general``), ``host.<op>`` for glue primitives
  (the paper's ``npu.module`` / ``cpu.aten.*`` split),
* **typed virtual registers** — integer IDs for inputs/outputs with
  shape/dtype metadata,
* a **device** tag consumed by the Phase-4 scheduler,
* a **pre-resolved callable** — primitive ``bind`` or the fused kernel
  dispatch — so the executor performs zero attribute lookups at runtime,
* **frozen args** — literal operands are frozen into the instruction at
  lowering time (the paper's ``_RegRef`` scheme inverted: we freeze the
  literals and register-reference everything else).

Lowering is a single topological traversal (paper Algorithm 1).  Only
constants actually referenced by live instructions are loaded into the
program's constant table.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ._jax_internal import Primitive, trace_state_clean
from .graph import Graph, GLit, GNode, GVar, Operand
from .fused_ops import fused_callable

#: opcodes routed to the accelerator (MXU-bound dispatch units).  The
#: paper's routing is name-based (``_npu_linear_`` …); ours is op-class
#: based: fused dispatches and bare matmuls.
ACCEL_OPS = ("dot_general", "conv_general_dilated")


def route_device(op: str) -> str:
    if op.startswith("forge."):
        return "accel"
    if op in ACCEL_OPS:
        return "accel"
    return "host"


class RegRef:
    """Marker: operand slot reads virtual register ``reg`` (paper _RegRef)."""

    __slots__ = ("reg",)

    def __init__(self, reg: int):
        self.reg = reg

    def __repr__(self):  # pragma: no cover
        return f"r{self.reg}"


@dataclass
class RGIROp:
    """One typed instruction (paper Listing 7's ``NPUIROp``)."""

    op_id: int
    opcode: str
    device: str  # 'accel' | 'host'
    target: Callable  # pre-resolved: bound primitive or fused kernel
    frozen_args: Tuple[Any, ...]  # RegRef | frozen literal values
    input_regs: Tuple[int, ...]
    output_regs: Tuple[int, ...]
    params: Dict[str, Any] = field(default_factory=dict)
    out_avals: Tuple[Any, ...] = ()
    flops: float = 0.0  # cost-model estimate attached at lowering

    def execute(self, read: Callable[[int], Any]) -> List[Any]:
        args = [read(a.reg) if isinstance(a, RegRef) else a for a in self.frozen_args]
        out = self.target(*args)
        return list(out) if isinstance(out, (list, tuple)) else [out]

    def __repr__(self):  # pragma: no cover
        ins = ", ".join(map(str, self.frozen_args))
        outs = ", ".join(f"r{r}" for r in self.output_regs)
        return f"[{self.device}] {outs} = {self.opcode}({ins})"


@dataclass
class RGIRProgram:
    """The flat instruction stream plus register metadata."""

    ops: List[RGIROp]
    n_vregs: int
    input_regs: List[int]
    output_regs: List[int]
    #: reg -> concrete value, pre-loaded once (paper: ``self.constants``)
    constants: Dict[int, Any]
    #: reg -> aval (shape/dtype) for every register
    reg_avals: Dict[int, Any]

    def device_transitions(self) -> int:
        """δ(I) — number of accel↔host boundaries (paper Eq. 17)."""
        return sum(
            1
            for a, b in zip(self.ops, self.ops[1:])
            if a.device != b.device
        )

    def renumber(self, order: Sequence[int]) -> "RGIRProgram":
        """Return a program with ops permuted into ``order`` (op_ids kept)."""
        return RGIRProgram(
            ops=[self.ops[i] for i in order],
            n_vregs=self.n_vregs,
            input_regs=self.input_regs,
            output_regs=self.output_regs,
            constants=self.constants,
            reg_avals=self.reg_avals,
        )


def _scoped(target: Callable, scope: str) -> Callable:
    """``target`` inside the named scope its equation was traced under,
    whenever it runs under a trace, so the ops it binds keep their
    ``op_name``.  An eager call compiles a program of its own, where the
    scope would name nothing, and skips it."""

    def call(*vals):
        if trace_state_clean():
            return target(*vals)
        with jax.named_scope(scope):
            return target(*vals)

    return call


def _node_flops(node: GNode) -> float:
    """Rough FLOP estimate used by the cost model and scheduler stats."""
    try:
        if node.op == "dot_general" or node.op.startswith("forge."):
            outs = node.outvars[0].shape
            if node.op == "forge.sdpa":
                q, k = node.invars[0], node.invars[1]
                B, H, Sq, D = q.shape
                Sk = k.shape[2]
                return 4.0 * B * H * Sq * Sk * D
            if node.op in ("forge.linear_act", "forge.swiglu"):
                x, w = node.invars[0], node.invars[1]
                m = float(np.prod(x.shape[:-1]))
                k_ = x.shape[-1]
                n_ = w.shape[-1]
                mult = 2.0 if node.op == "forge.swiglu" else 1.0
                return mult * 2.0 * m * k_ * n_
            if node.op == "dot_general":
                lhs = node.invars[0]
                (lc, _), _ = node.params["dimension_numbers"]
                k_ = float(np.prod([lhs.shape[c] for c in lc]))
                return 2.0 * float(np.prod(outs)) * k_
        return float(np.prod(node.outvars[0].shape or (1,)))
    except Exception:
        return 0.0


def lower_to_rgir(g: Graph) -> RGIRProgram:
    """FX→NPUIR lowering, Algorithm 1: one topological traversal."""
    reg_of: Dict[int, int] = {}  # GVar vid -> vreg
    reg_avals: Dict[int, Any] = {}
    next_reg = 0

    def reg_for(v: GVar) -> int:
        nonlocal next_reg
        r = reg_of.get(v.vid)
        if r is None:
            r = next_reg
            next_reg += 1
            reg_of[v.vid] = r
            reg_avals[r] = v.aval
        return r

    input_regs = [reg_for(v) for v in g.invars]

    # constants: load only those referenced by surviving nodes/outputs
    used_vids = set()
    for node in g.nodes.values():
        for iv in node.invars:
            if isinstance(iv, GVar):
                used_vids.add(iv.vid)
    for ov in g.outvars:
        if isinstance(ov, GVar):
            used_vids.add(ov.vid)
    constants: Dict[int, Any] = {}
    for cv, cval in zip(g.constvars, g.consts):
        if cv.vid in used_vids:
            constants[reg_for(cv)] = cval

    ops: List[RGIROp] = []
    for idx, node in enumerate(g.nodes.values()):
        frozen: List[Any] = []
        in_regs: List[int] = []
        for iv in node.invars:
            if isinstance(iv, GVar):
                r = reg_of.get(iv.vid)
                if r is None:
                    raise ValueError(
                        f"lowering: operand {iv} of {node.op} is undefined"
                    )
                frozen.append(RegRef(r))
                in_regs.append(r)
            else:  # literal frozen at compile time
                frozen.append(np.asarray(iv.val))
        out_regs = [reg_for(ov) for ov in node.outvars]

        if node.is_fused:
            target = fused_callable(node)
            opcode = f"accel.{node.op}"
        else:
            prim: Primitive = node.prim
            params = dict(node.params)

            def make_target(prim=prim, params=params):
                def call(*vals):
                    return prim.bind(*vals, **params)

                return call

            target = make_target()
            opcode = f"{route_device(node.op)}.{node.op}"
        if node.meta.get("scope"):
            target = _scoped(target, node.meta["scope"])

        ops.append(
            RGIROp(
                op_id=idx,
                opcode=opcode,
                device=route_device(node.op),
                target=target,
                frozen_args=tuple(frozen),
                input_regs=tuple(in_regs),
                output_regs=tuple(out_regs),
                params=dict(node.params) if not node.is_fused else dict(node.params),
                out_avals=tuple(ov.aval for ov in node.outvars),
                flops=_node_flops(node),
            )
        )

    output_regs = []
    extra_consts: Dict[int, Any] = {}
    for ov in g.outvars:
        if isinstance(ov, GVar):
            output_regs.append(reg_of[ov.vid])
        else:  # literal graph output — materialize as a constant register
            r = next_reg
            next_reg += 1
            reg_avals[r] = ov.aval
            extra_consts[r] = np.asarray(ov.val)
            output_regs.append(r)
    constants.update(extra_consts)

    return RGIRProgram(
        ops=ops,
        n_vregs=next_reg,
        input_regs=input_regs,
        output_regs=output_regs,
        constants=constants,
        reg_avals=reg_avals,
    )
