"""Pallas TPU kernel for the RG-LRU linear recurrence
``h_t = a_t ⊙ h_{t-1} + x_t`` — the ``forge.rg_lru`` dispatch target
(RecurrentGemma's gated linear recurrent unit; also reused by the xLSTM
cell's scan-free path).

TPU adaptation: a GPU implementation would assign one thread per channel
and walk T sequentially; on TPU we instead

* tile ``(B, T, D)`` into ``(1, bt, bd)`` VMEM blocks on a
  ``(B, D/bd, T/bt)`` grid with the **T axis innermost and sequential**
  (``arbitrary``), carrying the running state in an fp32 scratch,
* run a **Hillis–Steele inclusive scan** inside each block: log₂(bt)
  vectorized combine steps over the (bt, bd) tile — all full-tile VPU
  ops (shift = pad+slice), no per-row scalar loop,
* fold the carry in closed form:  out = scan(x) + cumprod(a) ⊙ h_in,
  then persist ``out[bt-1]`` as the next block's carry.

VMEM working set with defaults (bt=256, bd=256, bf16 in / fp32 scan):
x + a tiles 2×256×256×2B + two fp32 scan buffers 2×256×256×4B + carry
≈ 0.8 MB — far inside the ~16 MB/core budget.

Backward: ``jax.custom_vjp`` → reference associative-scan gradient.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref as _ref

DEFAULT_BLOCK_T = 256
DEFAULT_BLOCK_D = 256


def _block_scan(x_ref, a_ref, h0_ref, carry_scr, *, block_t):
    """Shared kernel body: scan one (bt, bd) tile against the carry.

    Initializes the fp32 carry scratch from ``h0`` on the first T-block,
    runs the Hillis–Steele inclusive scan over the tile, folds the carry
    in closed form, persists the tile's last row as the next block's
    carry, and returns the (bt, bd) fp32 state sequence.
    """
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        carry_scr[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)  # (bt, bd)
    a = a_ref[0].astype(jnp.float32)  # (bt, bd)

    # Hillis–Steele inclusive scan of the affine recurrence:
    # element t accumulates (A_t, X_t) s.t. h_t = A_t · h_{-1} + X_t
    A, X = a, x
    s = 1
    while s < block_t:
        A_sh = jnp.concatenate([jnp.ones((s, A.shape[1]), A.dtype), A[:-s]], 0)
        X_sh = jnp.concatenate([jnp.zeros((s, X.shape[1]), X.dtype), X[:-s]], 0)
        X = A * X_sh + X
        A = A * A_sh
        s *= 2

    h_in = carry_scr[...]  # (1, bd)
    out = X + A * h_in  # broadcast over rows
    carry_scr[...] = out[-1:, :]
    return out


def _rg_lru_kernel(x_ref, a_ref, h0_ref, o_ref, carry_scr, *, block_t):
    out = _block_scan(x_ref, a_ref, h0_ref, carry_scr, block_t=block_t)
    o_ref[0] = out.astype(o_ref.dtype)


def _rg_lru_chunk_kernel(x_ref, a_ref, h0_ref, o_ref, last_ref, carry_scr,
                         *, block_t):
    out = _block_scan(x_ref, a_ref, h0_ref, carry_scr, block_t=block_t)
    o_ref[0] = out.astype(o_ref.dtype)
    # every T-block writes the same (1, 1, bd) output block; T is the
    # innermost *sequential* grid axis, so the final block's write wins
    # and ``last_ref`` leaves the kernel holding h[T-1] — the carry the
    # caller folds into the next chunk's h0
    last_ref[0] = out[-1:, :].astype(last_ref.dtype)


def _shrink(block: int, dim: int) -> int:
    b = min(block, dim)
    while dim % b:
        b //= 2
    return max(b, 1)


def _pallas_scan(x, a, h0, *, block_t, block_d, interpret, with_last):
    """One ``pallas_call`` over the ``(B, D/bd, T/bt)`` grid.

    The carry-in ``h0`` (and the ``h_last`` output of the chunked form)
    travel as ``(B, 1, D)``: a ``(1, 1, bd)`` block then spans the full
    second-minor dim, as the TPU compiler's tiling rule requires, where
    a ``(1, bd)`` block over ``(B, D)`` would not.
    """
    B, T, D = x.shape
    bt = _shrink(block_t, T)
    bd = _shrink(block_d, D)
    grid = (B, D // bd, T // bt)

    def xa_map(b, id_, it):
        return (b, it, id_)

    def h_map(b, id_, it):
        return (b, 0, id_)

    seq_spec = pl.BlockSpec((1, bt, bd), xa_map)
    h_spec = pl.BlockSpec((1, 1, bd), h_map)
    seq_shape = jax.ShapeDtypeStruct((B, T, D), x.dtype)
    body = _rg_lru_chunk_kernel if with_last else _rg_lru_kernel
    out = pl.pallas_call(
        functools.partial(body, block_t=bt),
        grid=grid,
        in_specs=[seq_spec, seq_spec, h_spec],
        out_specs=[seq_spec, h_spec] if with_last else seq_spec,
        out_shape=(
            [seq_shape, jax.ShapeDtypeStruct((B, 1, D), x.dtype)]
            if with_last else seq_shape
        ),
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, a, h0.reshape(B, 1, D))
    if with_last:
        h, last = out
        return h, last.reshape(B, D)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rg_lru_vjp(x, a, h0, block_t, block_d, interpret):
    return _pallas_scan(x, a, h0, block_t=block_t, block_d=block_d,
                        interpret=interpret, with_last=False)


def _fwd(x, a, h0, block_t, block_d, interpret):
    out = _rg_lru_vjp(x, a, h0, block_t, block_d, interpret)
    return out, (x, a, h0)


def _bwd(block_t, block_d, interpret, res, g):
    x, a, h0 = res

    def ref_fn(x, a, h0):
        return _ref.rg_lru_ref(x, a, h0)

    _, vjp = jax.vjp(ref_fn, x, a, h0)
    return vjp(g)


_rg_lru_vjp.defvjp(_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rg_lru_chunk_vjp(x, a, h0, block_t, block_d, interpret):
    return _pallas_scan(x, a, h0, block_t=block_t, block_d=block_d,
                        interpret=interpret, with_last=True)


def _fwd_chunk(x, a, h0, block_t, block_d, interpret):
    out = _rg_lru_chunk_vjp(x, a, h0, block_t, block_d, interpret)
    return out, (x, a, h0)


def _bwd_chunk(block_t, block_d, interpret, res, g):
    x, a, h0 = res

    def ref_fn(x, a, h0):
        return _ref.rg_lru_chunk_ref(x, a, h0)

    _, vjp = jax.vjp(ref_fn, x, a, h0)
    return vjp(g)


_rg_lru_chunk_vjp.defvjp(_fwd_chunk, _bwd_chunk)


def rg_lru_pallas(
    x: jax.Array,
    a: jax.Array,
    h0: Optional[jax.Array] = None,
    *,
    block_t: int = DEFAULT_BLOCK_T,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
) -> jax.Array:
    """h_t = a_t ⊙ h_{t-1} + x_t over axis 1.  x, a: (B, T, D)."""
    if h0 is None:
        h0 = jnp.zeros((x.shape[0], x.shape[2]), x.dtype)
    return _rg_lru_vjp(
        x, a, h0, int(block_t), int(block_d), bool(interpret)
    )


def rg_lru_chunked(
    x: jax.Array,
    a: jax.Array,
    h0: Optional[jax.Array] = None,
    *,
    block_t: int = DEFAULT_BLOCK_T,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
) -> tuple:
    """Chunked-prefill scan: ``(h, h_last)`` for one prompt chunk.

    Same recurrence and tiling as :func:`rg_lru_pallas` plus a second
    (B, D) output carrying ``h[:, -1]`` off-device without slicing the
    (B, T, D) sequence — the inter-chunk carry a caller feeds into the
    next chunk's ``h0``.  Oracle: ``kernels.ref.rg_lru_chunk_ref``.
    """
    if h0 is None:
        h0 = jnp.zeros((x.shape[0], x.shape[2]), x.dtype)
    return _rg_lru_chunk_vjp(
        x, a, h0, int(block_t), int(block_d), bool(interpret)
    )
