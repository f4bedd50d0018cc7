"""Pallas TPU paged-attention decode kernel.

Extension of :mod:`repro.kernels.flash_attention`'s blockwise
online-softmax machinery to a paged KV cache: instead of streaming a
contiguous (Sk, D) cache row through VMEM, the KV BlockSpec index map is
indirected through a per-row **page table** prefetched into SMEM
(``pltpu.PrefetchScalarGridSpec``), so each grid step DMAs one physical
page ``k_pages[page_table[b, j]]`` HBM→VMEM.  The pages a row occupies
can live anywhere in the pool — including pages shared with other rows
via the prefix tree — and the kernel never materializes a gathered copy.

Page layout: ``(num_pages, KVH, page_size, D)``, head-major inside a
page, so one (KV head, page) pair is a contiguous ``(page_size, D)``
tile.  Every block below spans the full trailing two dims of its array,
which is what the TPU compiler's (8, 128) tiling rule accepts for any
``page_size`` and head width.

Design (decode step, one query token per row):

* 3-D grid ``(batch, kv_heads, max_pages)`` with the page axis innermost
  and ``arbitrary`` so the (m, l, acc) accumulator scratch carries across
  page iterations, exactly as flash_attention carries across KV blocks.
* GQA without expanding K/V: the queries are viewed as
  ``(B, KVH, groups, D)``, and one grid step scores all ``groups`` query
  heads of a KV head against one page — a ``(groups, page_size)`` tile.
* Scalar prefetch: ``page_table (B, MP)`` and ``pos (B,)`` ride in SMEM
  ahead of the grid; index maps read the table to pick the page, the
  kernel body reads ``pos`` to mask dead key slots.
* Page skip: pages strictly beyond ``pos`` (and, with a sliding window,
  pages wholly behind it) are skipped via ``pl.when``; the trash page
  (index 0) backing unallocated table entries is only ever touched by the
  DMA of skipped steps, never by live arithmetic — within a live page,
  slots beyond ``pos`` get an elementwise iota mask.

Validated against :func:`repro.kernels.ref.paged_sdpa_ref` in interpret
mode by ``tests/test_paged_kv.py`` over shape/GQA/window/pos sweeps, and
compiled for a v5e by ``tests/test_tpu_compile.py``.
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

_NEG_INF = float(np.finfo(np.float32).min)


def _paged_kernel(
    pt_ref,   # (B, MP) int32 in SMEM (scalar prefetch)
    pos_ref,  # (B,)    int32 in SMEM (scalar prefetch)
    q_ref,    # (1, 1, G, D)
    k_ref,    # (1, 1, ps, D)
    v_ref,    # (1, 1, ps, D)
    o_ref,    # (1, 1, G, D)
    m_scr,    # (G, 1) f32
    l_scr,    # (G, 1) f32
    acc_scr,  # (G, D) f32
    *,
    scale: float,
    page_size: int,
    window: Optional[int],
):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    p = pos_ref[b]
    k0 = j * page_size

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # page skip: a page is live iff it holds any key in the visible range
    # [max(0, p - window + 1), p]
    run = k0 <= p
    if window is not None:
        run = jnp.logical_and(run, k0 + page_size - 1 > p - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (ps, d)
        v = v_ref[0, 0].astype(jnp.float32)  # (ps, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (G, ps)
        s = s * scale
        col = lax.broadcasted_iota(jnp.int32, s.shape, 1) + k0
        keep = col <= p
        if window is not None:
            keep = jnp.logical_and(keep, col > p - window)
        s = jnp.where(keep, s, _NEG_INF)

        m_prev = m_scr[...]  # (G, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        prob = jnp.exp(s - m_new)  # (G, ps)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(prob, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            prob, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    pos: jax.Array,
    *,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Paged-attention decode step.  See module docstring.

    q: (B, H, D); k_pages/v_pages: (num_pages, KVH, page_size, D);
    page_table: (B, max_pages) int32; pos: (B,) int32.  Returns (B, H, D).
    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU
    validation); otherwise it is compiled for the TPU.
    """
    B, H, D = q.shape
    NP, KVH, ps, Dk = k_pages.shape
    if D != Dk or H % KVH:
        raise ValueError(
            f"q {q.shape} does not match pages {k_pages.shape}"
        )
    groups = H // KVH
    MP = page_table.shape[1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)

    def q_map(b, h, j, pt_ref, pos_ref):
        return (b, h, 0, 0)

    def kv_map(b, h, j, pt_ref, pos_ref):
        return (pt_ref[b, j], h, 0, 0)

    kernel = functools.partial(
        _paged_kernel,
        scale=float(scale),
        page_size=ps,
        window=None if window is None else int(window),
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KVH, MP),
        in_specs=[
            pl.BlockSpec((1, 1, groups, D), q_map),
            pl.BlockSpec((1, 1, ps, D), kv_map),
            pl.BlockSpec((1, 1, ps, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, groups, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((groups, 1), jnp.float32),
            pltpu.VMEM((groups, 1), jnp.float32),
            pltpu.VMEM((groups, D), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, groups, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(
        page_table.astype(jnp.int32),
        pos.astype(jnp.int32),
        q.reshape(B, KVH, groups, D),
        k_pages,
        v_pages,
    )
    return out.reshape(B, H, D)
