"""Compile every Pallas kernel for a TPU v5e at real model widths.

Nothing runs: the TPU compiler that ships with jaxlib compiles each
kernel against a *described* v5e topology, and each test asserts that
the compiled program holds the kernel as a ``tpu_custom_call``.  This
catches what interpret mode cannot see (block shapes the tiling rule
refuses, VMEM overflow) without a chip.

Widths: phi3-mini-3.8b (d_model 3072, 32 heads of 96, d_ff 8192), a GQA
head layout of 32 query / 8 KV heads of 128, and recurrentgemma-2b's
RG-LRU width 2560.

The paged decode and prefill steps are compiled whole, too, and their
HLO is read for how the layer loop treats the page store: the store
rides in the loop's carry and is written in place.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ModelConfig
from repro.models import get_model

from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_linear import fused_linear_pallas
from repro.kernels.paged_attention import paged_attention
from repro.kernels.rg_lru import rg_lru_chunked, rg_lru_pallas
from repro.kernels.rms_norm import rms_norm_pallas

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)


def _compile_text(fn, shapes, sharding):
    specs = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in shapes
    ]
    return jax.jit(fn).lower(*specs).compile().as_text()


CASES = {
    "flash_attention_phi3": (
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        [((4, 32, 512, 96), BF16)] * 3,
    ),
    "flash_attention_gqa": (
        lambda q, k, v: flash_attention(q, k, v, causal=True, groups=4),
        [((4, 32, 512, 128), BF16), ((4, 8, 512, 128), BF16),
         ((4, 8, 512, 128), BF16)],
    ),
    # 4 rows of 1024 tokens in 16-token pages, plus the trash page
    "paged_attention_phi3": (
        paged_attention,
        [((4, 32, 96), BF16), ((257, 32, 16, 96), BF16),
         ((257, 32, 16, 96), BF16), ((4, 64), jnp.int32),
         ((4,), jnp.int32)],
    ),
    "fused_linear_phi3_ffn": (
        lambda x, w: fused_linear_pallas(x, w, act="silu"),
        [((2048, 3072), BF16), ((3072, 8192), BF16)],
    ),
    "rms_norm_phi3": (
        rms_norm_pallas,
        [((2048, 3072), BF16), ((3072,), BF16)],
    ),
    "rg_lru_pallas": (
        rg_lru_pallas,
        [((4, 512, 2560), BF16)] * 2 + [((4, 2560), BF16)],
    ),
    "rg_lru_chunked": (
        rg_lru_chunked,
        [((4, 512, 2560), BF16)] * 2 + [((4, 2560), BF16)],
    ),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    text = _compile_text(fn, shapes, one_chip)
    assert "tpu_custom_call" in text, f"{name}: no Pallas kernel in the program"


# --------------------------------------------------------------------------
# the paged model steps: the store is carried through the layer loop
# --------------------------------------------------------------------------

_COMP = re.compile(r"^(ENTRY )?%([\w.\-]+) \(.*\{$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = (\w+)\[([0-9,]*)\]\{([^}]*)\} ([\w\-]+)\(")


def _computations(text):
    """HLO computations of a compiled module: name -> (is_entry, instrs),
    each instruction as (name, dtype, dims, layout, opcode, line)."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = m.group(2)
            comps[cur] = (bool(m.group(1)), [])
            continue
        m = _INSTR.match(line)
        if cur is not None and m:
            dims = tuple(int(d) for d in m.group(3).split(",") if d)
            comps[cur][1].append((m.group(1), m.group(2), dims, m.group(4),
                                  m.group(5), line))
    return comps


def _root_opcode(comps, line):
    """Opcode at the root of the computation a fusion calls."""
    callee = re.search(r"calls=%([\w.\-]+)", line).group(1)
    return [op for *_, op, ln in comps[callee][1] if "ROOT" in ln][0]


# no data moves: views, tuple plumbing, and the loop itself
_PLUMBING = {"get-tuple-element", "bitcast", "parameter", "tuple", "while"}


def _paged_cfg(head_dim):
    # 8 heads of 96 lanes make a 768-lane row; of 128, a 1024-lane row
    return ModelConfig(
        name=f"paged-compile-d{head_dim}", family="dense", n_layers=2,
        d_model=1024, n_heads=8, n_kv_heads=8, head_dim=head_dim, d_ff=2048,
        vocab=1024,
    )


@pytest.mark.parametrize("step", ["decode", "prefill"])
@pytest.mark.parametrize("head_dim", [96, 128])
def test_paged_step_carries_store_in_place(head_dim, step, one_chip):
    """In the compiled paged step, the layer loop's body writes the
    stacked store with a scatter into its carry and yields no other array
    of a layer's slab or the whole store's size; outside the loop at most
    one copy per store leaf is made (the caller's store, which is not
    donated, into the carry); and the store keeps its flat token rows, so
    at head_dim 96 nothing of it is padded to 128 lanes.

    The pool (2 layers x 4097 pages of 16 tokens) is larger than the
    chip's fast memory, as a served store is, so the compiler cannot
    stage it there and the HLO shows the placement a real store gets."""
    cfg = _paged_cfg(head_dim)
    model = get_model(cfg)
    B, max_len, ps, num_pages = 8, 1024, 16, 4097
    MP = max_len // ps

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), cfg)),
    )
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        cfg, B, max_len, num_pages=num_pages, page_size=ps))
    row = cfg.n_kv_heads * head_dim
    assert cache["k_pages"].shape == (cfg.n_layers, num_pages, ps, row)
    store = {k: sds(cache[k].shape, cache[k].dtype)
             for k in ("k_pages", "v_pages")}
    fn = model.paged_decode_step if step == "decode" else model.paged_prefill_step
    S = 1 if step == "decode" else 64

    def f(p, st, pt, tok, pos, m):
        return fn(p, dict(st, page_table=pt), tok, pos, cfg, slot_mask=m)

    text = jax.jit(f).lower(
        params, store, sds((B, MP), jnp.int32), sds((B, S), jnp.int32),
        sds((B,), jnp.int32), sds((B,), jnp.bool_),
    ).compile().as_text()

    comps = _computations(text)
    bodies = set(re.findall(r"body=%([\w.\-]+)", text))
    assert bodies, "the layer loop was unrolled"
    slab = num_pages * ps * row

    def store_sized(dims):
        # by size alone: XLA may flatten the store's leading dims; the odd
        # page count keeps any other array from this size
        n = 1
        for d in dims:
            n *= d
        return n in (slab, cfg.n_layers * slab)

    scatters, copies, others = [], [], []
    for name, (is_entry, instrs) in comps.items():
        if name not in bodies and not is_entry:
            continue
        for op, dtype, dims, layout, opcode, line in instrs:
            if not store_sized(dims) or opcode in _PLUMBING:
                continue
            # the row stays whole and flat: never split into heads
            assert dims[-1] == row, f"store re-laid out by heads: {line[:200]}"
            if opcode == "custom-call" and "AllocateBuffer" in line:
                continue
            kind = _root_opcode(comps, line) if opcode == "fusion" else opcode
            if name in bodies and kind == "scatter":
                scatters.append(op)
            elif is_entry and kind in ("copy", "copy-start"):
                copies.append(op)
            else:
                others.append((name in bodies, line.strip()[:200]))
    assert len(scatters) == 2, f"one scatter per store leaf: {scatters}"
    assert not others, f"store-sized ops besides the in-place scatters: {others}"
    assert len(copies) <= 2, f"more than one copy per store leaf: {copies}"
