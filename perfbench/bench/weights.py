"""Seeded weights, by name, made on the device.

Each tensor of a reference's ``layout`` has its own key, folded from the run
seed, the tensor's index in the layout and its layer, so the benchmark can
make every layer at once for the program (one jitted call, in the type the
weights are served in) and the reference can make one layer again after the
window, bit for bit, without reading anything the program holds.

Inits: ``linear`` normal / sqrt(fan-in) and ``embed`` normal * 0.02, both in
the configuration's dtype; ``norm`` 1 + 0.1 * normal in float32, the type
the program keeps norm scales in.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int) -> jax.Array:
    seed = int(seed) % 2**64
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def _tensor(key, shape: Tuple[int, ...], init: str, dtype) -> jax.Array:
    z = jax.random.normal(key, shape, jnp.float32)
    if init == "norm":
        return (1.0 + 0.1 * z).astype(jnp.float32)
    if init == "embed":
        return (z * 0.02).astype(dtype)
    if init == "linear":
        return (z * (1.0 / np.sqrt(shape[0]))).astype(dtype)
    raise ValueError(f"unknown init {init!r}")


def _key(base, tid: int, layer: int):
    return jax.random.fold_in(jax.random.fold_in(base, tid), layer)


def make_all(seed: int, glob: List, per_layer: List, n_layers: int, dtype: str):
    """``(globals {name: array}, layers {name: (n_layers, ...) array})`` in
    one jitted call."""
    dt = jnp.dtype(dtype)
    n_glob = len(glob)

    def build(base):
        g = {name: _tensor(_key(base, i, 0), shape, init, dt)
             for i, (name, shape, init) in enumerate(glob)}

        def one_layer(layer):
            return {name: _tensor(_key(base, n_glob + i, layer), shape, init, dt)
                    for i, (name, shape, init) in enumerate(per_layer)}

        return g, jax.vmap(one_layer)(jnp.arange(n_layers))

    return jax.jit(build)(base_key(seed))


def layer_maker(glob: List, per_layer: List, dtype: str):
    """``(seed, layer) -> {name: float32 array}``: one layer, as served, upcast."""
    dt = jnp.dtype(dtype)
    n_glob = len(glob)

    @jax.jit
    def build(base, layer):
        return {name: _tensor(_key(base, n_glob + i, layer), shape, init, dt)
                .astype(jnp.float32)
                for i, (name, shape, init) in enumerate(per_layer)}

    return lambda seed, layer: build(base_key(seed), jnp.int32(layer))


def global_maker(glob: List, dtype: str):
    """``(seed, names) -> {name: float32 array}`` for the global tensors."""
    dt = jnp.dtype(dtype)
    index: Dict[str, int] = {name: i for i, (name, _, _) in enumerate(glob)}

    def build(seed, names):
        base = base_key(seed)
        out = {}
        for name in names:
            i = index[name]
            _, shape, init = glob[i]
            out[name] = jax.jit(
                lambda b, i=i, shape=shape, init=init:
                _tensor(_key(b, i, 0), shape, init, dt).astype(jnp.float32)
            )(base)
        return out

    return build
