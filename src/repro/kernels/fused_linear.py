"""Pallas TPU fused linear(+bias)(+activation) kernel — the
``forge.linear_act`` dispatch target.

TPU-native adaptation of the paper's NNFactory matmul+activation graph
(Listing 6): instead of one NNFactory program per (matmul, activation)
pair, a tiled MXU matmul whose epilogue applies bias and activation *in
VMEM on the final K step* — the (M, N) intermediate never round-trips
through HBM between the linear and the activation.

Design (v5e target):

* 3-D grid ``(M/bm, N/bn, K/bk)`` with the K axis innermost and marked
  ``arbitrary`` so the fp32 accumulator scratch carries across K steps.
* Default tiles bm=256, bn=256, bk=512: VMEM working set =
  x(256×512×2B) + w(512×256×2B) + acc(256×256×4B) + out tile ≈ 0.9 MB —
  well inside the ~16 MB/core budget, leaving headroom for
  double-buffered pipelining.
* MXU alignment: all tile dims are multiples of 128 for the common
  d_model/d_ff sizes; odd shapes shrink tiles to divisors.
* Activation epilogue: relu / silu / gelu (tanh) / gelu_exact / tanh,
  computed in fp32 before the downcast store.

Backward: ``jax.custom_vjp`` with the reference-jnp gradient
(recompute-from-inputs), keeping the executor differentiable.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref as _ref

DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_K = 512


def _apply_act_f32(y, act: Optional[str]):
    if act is None or act == "none":
        return y
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act == "silu":
        return y * jax.nn.sigmoid(y)
    if act == "gelu":
        return jax.nn.gelu(y, approximate=True)
    if act == "gelu_exact":
        return jax.nn.gelu(y, approximate=False)
    if act == "tanh":
        return jnp.tanh(y)
    raise ValueError(f"unknown activation {act!r}")


def _linear_kernel(x_ref, w_ref, b_ref, o_ref, acc_scr, *, act, has_bias, nk):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ik == nk - 1)
    def _epilogue():
        y = acc_scr[...]
        if has_bias:
            y = y + b_ref[...].astype(jnp.float32)
        y = _apply_act_f32(y, act)
        o_ref[...] = y.astype(o_ref.dtype)


def _shrink(block: int, dim: int) -> int:
    b = min(block, dim)
    while dim % b:
        b //= 2
    return max(b, 1)


def _forward(x, w, b, *, act, block_m, block_n, block_k, interpret):
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    has_bias = b is not None

    bm = _shrink(block_m, M)
    bn = _shrink(block_n, N)
    bk = _shrink(block_k, K)
    grid = (M // bm, N // bn, K // bk)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda im, in_, ik: (im, ik)),
        pl.BlockSpec((bk, bn), lambda im, in_, ik: (ik, in_)),
    ]
    inputs = [x, w]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda im, in_, ik: (0, in_)))
        inputs.append(b.reshape(1, N))
    else:
        in_specs.append(pl.BlockSpec((1, bn), lambda im, in_, ik: (0, in_)))
        inputs.append(jnp.zeros((1, N), x.dtype))

    kernel = functools.partial(
        _linear_kernel, act=act, has_bias=has_bias, nk=grid[2]
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda im, in_, ik: (im, in_)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*inputs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused_linear_vjp(x, w, b, act, block_m, block_n, block_k, interpret):
    return _forward(
        x, w, b, act=act, block_m=block_m, block_n=block_n,
        block_k=block_k, interpret=interpret,
    )


def _fwd(x, w, b, act, block_m, block_n, block_k, interpret):
    out = _fused_linear_vjp(x, w, b, act, block_m, block_n, block_k, interpret)
    return out, (x, w, b)


def _bwd(act, block_m, block_n, block_k, interpret, res, g):
    x, w, b = res

    def ref_fn(x, w, b):
        return _ref.fused_linear_ref(x, w, b, act=act)

    _, vjp = jax.vjp(ref_fn, x, w, b)
    return vjp(g)


_fused_linear_vjp.defvjp(_fwd, _bwd)


def fused_linear_pallas(
    x: jax.Array,
    w: jax.Array,
    b: Optional[jax.Array] = None,
    *,
    act: Optional[str] = None,
    block_m: int = DEFAULT_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """y = act(x·w + b).  x: (M, K); w: (K, N); b: (N,) or None."""
    b_in = b if b is not None else None
    return _fused_linear_vjp(
        x, w, b_in, act, int(block_m), int(block_n), int(block_k), bool(interpret)
    )
