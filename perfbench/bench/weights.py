"""Seeded weights, by name, made on the device.

A reference's ``layout(cfg)`` is ``(globals, groups)``: globals a list of
``(name, shape, init, path)``, groups a list of ``(count, per-layer
tensors)`` in layer order, each tensor again ``(name, shape, init,
path)``.  ``path`` is the tensor's place in the program's pytree
(``blocks/attn/wq``); a group's tensors are stacked over its layers there,
and groups that name one path are joined along the layer axis in order.

Each tensor has its own key, folded from the run seed, the tensor's index
(globals first, then each group's tensors in turn) and its global layer
index, so the benchmark can make every layer at once for the program (one
jitted call, in the type the weights are served in) and the reference can
make one layer again after the window, bit for bit, without reading
anything the program holds.

Inits: ``linear`` normal / sqrt(fan-in) and ``embed`` normal * 0.02, both in
the configuration's dtype; ``linear_f32`` as ``linear`` in float32 (a
router, kept so by the program); ``norm`` 1 + 0.1 * normal in float32, the
type the program keeps norm scales in.  Fan-in is the second-to-last axis
(an expert stack is ``(experts, in, out)``).
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
        return (z * (1.0 / np.sqrt(shape[-2]))).astype(dtype)
    if init == "linear_f32":
        return z * (1.0 / np.sqrt(shape[-2]))
    raise ValueError(f"unknown init {init!r}")


def _key(base, tid: int, layer: int):
    return jax.random.fold_in(jax.random.fold_in(base, tid), layer)


def extents(glob: List, groups: List) -> List[Tuple[int, int, int]]:
    """(first tensor id, first layer, count) of each group."""
    out, tid, first = [], len(glob), 0
    for count, tensors in groups:
        out.append((tid, first, int(count)))
        tid, first = tid + len(tensors), first + int(count)
    return out


def n_layers(groups: List) -> int:
    return sum(int(count) for count, _ in groups)


def make_all(seed: int, glob: List, groups: List, dtype: str) -> Dict[str, jax.Array]:
    """``{path: array}`` of every tensor, each group stacked over its layers
    (``(count, ...)``, joined with the groups that share its path), in one
    jitted call."""
    dt = jnp.dtype(dtype)

    def build(base):
        out = {path: _tensor(_key(base, i, 0), shape, init, dt)
               for i, (_, shape, init, path) in enumerate(glob)}
        stacked: Dict[str, List[jax.Array]] = {}
        for (tid, first, count), (_, tensors) in zip(extents(glob, groups), groups):
            def one_layer(layer, tid=tid, tensors=tensors):
                return {path: _tensor(_key(base, tid + i, layer), shape, init, dt)
                        for i, (_, shape, init, path) in enumerate(tensors)}

            for path, arr in jax.vmap(one_layer)(first + jnp.arange(count)).items():
                stacked.setdefault(path, []).append(arr)
        out.update({path: arrs[0] if len(arrs) == 1 else jnp.concatenate(arrs)
                    for path, arrs in stacked.items()})
        return out

    return jax.jit(build)(base_key(seed))


def layer_maker(glob: List, groups: List, dtype: str):
    """``(seed, layer) -> (group, {name: float32 array})``: one layer of its
    group, as served, upcast."""
    dt = jnp.dtype(dtype)
    where = extents(glob, groups)

    def group_build(tid, tensors):
        @jax.jit
        def build(base, layer):
            return {name: _tensor(_key(base, tid + i, layer), shape, init, dt)
                    .astype(jnp.float32)
                    for i, (name, shape, init, _) in enumerate(tensors)}
        return build

    builds = [group_build(tid, tensors) for (tid, _, _), (_, tensors) in zip(where, groups)]

    def make(seed, layer):
        g = next(k for k, (_, first, count) in enumerate(where) if layer < first + count)
        return g, builds[g](base_key(seed), jnp.int32(layer))

    return make


def global_maker(glob: List, dtype: str):
    """``seed -> {name: float32 array}``: the global tensors, as served, upcast."""
    dt = jnp.dtype(dtype)

    def build(seed):
        base = base_key(seed)
        return {name: jax.jit(
                    lambda b, i=i, shape=shape, init=init:
                    _tensor(_key(b, i, 0), shape, init, dt).astype(jnp.float32)
                )(base)
                for i, (name, shape, init, _) in enumerate(glob)}

    return build
