"""Pallas TPU flash-attention kernel — the ``forge.sdpa`` dispatch target.

TPU-native adaptation of the paper's attention fusion: instead of one
NNFactory SDPA dispatch, the fused node lowers to a blockwise
online-softmax kernel that streams K/V through VMEM (HBM→VMEM→MXU) and
never materializes the (Sq, Sk) score matrix in HBM.

Design (v5e target):

* 3-D grid ``(batch·heads, num_q_blocks, num_kv_blocks)`` with the KV axis
  innermost and marked ``arbitrary`` so the per-(bh, q-block) accumulator
  scratch carries across KV iterations (the canonical TPU "revisiting"
  pattern).
* BlockSpecs keep one ``(block_q, head_dim)`` Q tile and one
  ``(block_k, head_dim)`` K/V tile in VMEM; with the defaults
  (512×128 bf16 tiles + fp32 scratch) the working set is ≈ 1.4 MB,
  comfortably inside the ~16 MB/core VMEM budget.
* MXU alignment: ``block_q``/``block_k`` default to 512/512 and head_dim
  tiles are used whole (assigned archs have head_dim ∈ {64, 96, 112, 128,
  256}; 112 (kimi-k2) pads to 128 lanes — noted in EXPERIMENTS §Perf).
* GQA is handled in the index maps: the Q-head grid coordinate maps to its
  KV head via ``h // group``, so K/V are never physically expanded.
* Causal masking is block-level: fully-masked KV blocks are skipped via
  ``pl.when`` (≈2× fewer MXU passes at Sq == Sk), diagonal blocks get an
  elementwise iota mask.

Backward pass: the wrapper is a ``jax.custom_vjp`` whose backward is the
reference jnp implementation (recomputation; O(N²) flops but O(N·c)
memory via the chunked ref) — keeps the executor differentiable while the
forward takes the fast path.

Validated against :func:`repro.kernels.ref.sdpa_ref` in interpret mode by
``tests/test_kernels.py`` over shape/dtype/GQA/causal sweeps.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref as _ref

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_NEG_INF = float(np.finfo(np.float32).min)


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    scale_mode: str,
    causal: bool,
    block_q: int,
    block_k: int,
    sq: int,
    sk: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal block skip: q rows [q0, q0+bq) attend to keys <= row + (sk-sq)
    q0 = iq * block_q
    k0 = ik * block_k
    diag_off = sk - sq
    run = True
    if causal:
        run = k0 <= q0 + block_q - 1 + diag_off

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0].astype(jnp.float32)  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        if scale_mode == "div":
            s = s / scale
        elif scale_mode == "mul":
            s = s * scale
        if causal:
            row = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q0 + diag_off
            col = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + k0
            s = jnp.where(row >= col, s, _NEG_INF)

        m_prev = m_scr[...]  # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        p = jnp.exp(s - m_new)  # (bq, bk)
        l_new = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _flash_forward(
    q, k, v, *, scale, scale_mode, causal, groups, block_q, block_k, interpret
):
    B, H, Sq, D = q.shape
    KVH, Sk = k.shape[1], k.shape[2]
    assert H == KVH * groups, (H, KVH, groups)

    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    # shrink to divisors (assigned shapes are powers of two; generic inputs
    # fall back to smaller blocks rather than padding)
    while Sq % bq:
        bq //= 2
    while Sk % bk:
        bk //= 2
    bq, bk = max(bq, 1), max(bk, 1)
    nq, nk = Sq // bq, Sk // bk

    grid = (B * H, nq, nk)

    def q_map(bh, iq, ik):
        return (bh, iq, 0)

    def kv_map(bh, iq, ik):
        b = bh // H
        h = bh % H
        return (b * KVH + h // groups, ik, 0)

    q3 = q.reshape(B * H, Sq, D)
    k3 = k.reshape(B * KVH, Sk, D)
    v3 = v.reshape(B * KVH, Sk, D)

    kernel = functools.partial(
        _flash_kernel,
        scale=float(scale),
        scale_mode=scale_mode,
        causal=causal,
        block_q=bq,
        block_k=bk,
        sq=Sq,
        sk=Sk,
    )

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), q_map),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q3, k3, v3)
    return out.reshape(B, H, Sq, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_attention_vjp(
    q, k, v, scale, scale_mode, causal, groups, block_q, block_k, interpret
):
    return _flash_forward(
        q, k, v, scale=scale, scale_mode=scale_mode, causal=causal,
        groups=groups, block_q=block_q, block_k=block_k, interpret=interpret,
    )


def _fwd(q, k, v, scale, scale_mode, causal, groups, block_q, block_k, interpret):
    out = _flash_attention_vjp(
        q, k, v, scale, scale_mode, causal, groups, block_q, block_k, interpret
    )
    return out, (q, k, v)


def _bwd(scale, scale_mode, causal, groups, block_q, block_k, interpret, res, g):
    q, k, v = res
    eff_scale = scale if scale_mode == "mul" else (1.0 / scale)

    def ref_fn(q, k, v):
        return _ref.sdpa_ref(q, k, v, None, scale=eff_scale, causal=causal)

    _, vjp = jax.vjp(ref_fn, q, k, v)
    return vjp(g)


_flash_attention_vjp.defvjp(_fwd, _bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    scale: Optional[float] = None,
    scale_mode: str = "mul",
    causal: bool = False,
    groups: int = 1,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Blockwise online-softmax attention.  See module docstring."""
    if scale is None:
        scale, scale_mode = 1.0 / (q.shape[-1] ** 0.5), "mul"
    return _flash_attention_vjp(
        q, k, v, float(scale), scale_mode, bool(causal), int(groups),
        int(block_q), int(block_k), bool(interpret),
    )
