"""Pipeline parallelism over the ``pod`` axis (GPipe fill–drain).

The production mesh's ``pod`` axis is data-parallel by default; this
module repurposes it as a pipeline axis for workloads where cross-pod DCN
bandwidth can't carry FSDP/DP traffic: layers are split into
``n_stages = |pod|`` contiguous stages, microbatches stream through with
``lax.ppermute`` boundary transfers (the ONLY cross-pod communication —
one (mb, S, d) activation per tick), and the classic fill/drain bubble of
(S−1)/(M+S−1) is amortized by the microbatch count M.

Implementation: ``shard_map`` over the pod axis; stage-local parameters
arrive pre-sharded (leading stage dim, ``P('pod', …)``); the in-pod
(data, model) axes stay under GSPMD via ``auto`` axes, so TP/DP compose
inside each stage unchanged.

``gpipe_apply`` is forward-only (serving/prefill pipelines — the paper's
inference regime); training pipelines would add the 1F1B schedule on the
same skeleton (documented future work in DESIGN.md).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def split_stages(blocks: Any, n_stages: int) -> Any:
    """Reshape layer-stacked params (L, …) -> (n_stages, L/n_stages, …)."""

    def one(a):
        L = a.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return a.reshape(n_stages, L // n_stages, *a.shape[1:])

    return jax.tree_util.tree_map(one, blocks)


def gpipe_apply(
    stage_params: Any,  # (n_stages, L/S, …) sharded P('pod', …)
    microbatches: jax.Array,  # (M, mb, S, d) — replicated across pods
    stage_fn: Callable[[Any, jax.Array], jax.Array],  # layers of ONE stage
    *,
    mesh: Mesh,
    axis: str = "pod",
) -> jax.Array:
    """Run M microbatches through the stage pipeline; returns (M, mb, S, d).

    ``stage_fn(params_stage, x)`` applies one stage's layer stack.
    """
    n_stages = mesh.shape[axis]
    M = microbatches.shape[0]
    ticks = M + n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def per_pod(params_stage, mbs):
        # params_stage: (1, L/S, …) — this pod's slice; mbs: (M, mb, S, d)
        params_stage = jax.tree_util.tree_map(
            lambda a: a[0], params_stage
        )
        stage = lax.axis_index(axis)
        zero = jnp.zeros_like(mbs[0])
        outs0 = jnp.zeros_like(mbs)

        def tick(carry, t):
            prev_out, outs = carry
            # boundary transfer: stage i-1's output -> stage i
            recv = lax.ppermute(prev_out, axis, perm)
            feed_idx = jnp.clip(t, 0, M - 1)
            inp = jnp.where(stage == 0,
                            jnp.where(t < M, mbs[feed_idx], zero),
                            recv)
            out = stage_fn(params_stage, inp)
            # last stage retires microbatch t-(S-1) at tick t
            retire = t - (n_stages - 1)
            do_write = jnp.logical_and(stage == n_stages - 1, retire >= 0)
            widx = jnp.clip(retire, 0, M - 1)
            outs = lax.cond(
                do_write,
                lambda o: o.at[widx].set(out),
                lambda o: o,
                outs,
            )
            return (out, outs), None

        (_, outs), _ = lax.scan(tick, (zero, outs0), jnp.arange(ticks))
        # broadcast the last stage's results to every pod (tiny psum trick)
        owner = (lax.axis_index(axis) == n_stages - 1).astype(outs.dtype)
        return lax.psum(outs * owner, axis)

    # manual only over the pod axis; (data, model) stay under GSPMD
    # inside each stage
    fn = jax.shard_map(
        per_pod,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        axis_names={axis},
        check_vma=False,
    )
    return fn(stage_params, microbatches)


def reference_apply(stage_params, microbatches, stage_fn) -> jax.Array:
    """Sequential oracle: all stages applied in order, no pipeline."""
    n_stages = jax.tree_util.tree_leaves(stage_params)[0].shape[0]

    def one_mb(x):
        for s in range(n_stages):
            p_s = jax.tree_util.tree_map(lambda a: a[s], stage_params)
            x = stage_fn(p_s, x)
        return x

    return jax.vmap(one_mb)(microbatches)
