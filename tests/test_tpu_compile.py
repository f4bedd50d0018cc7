"""Compile every Pallas kernel for a TPU v5e at real model widths.

Nothing runs: the TPU compiler that ships with jaxlib compiles each
kernel against a *described* v5e topology, and each test asserts that
the compiled program holds the kernel as a ``tpu_custom_call``.  This
catches what interpret mode cannot see (block shapes the tiling rule
refuses, VMEM overflow) without a chip.

Widths: phi3-mini-3.8b (d_model 3072, 32 heads of 96, d_ff 8192), a GQA
head layout of 32 query / 8 KV heads of 128, and recurrentgemma-2b's
RG-LRU width 2560.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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
