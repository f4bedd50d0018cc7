"""Memory of a cell's largest programs, compiled for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 perfbench/memory_plan.py <cell> [<cell> ...]

Compiles the paged decode step at the top batch rung and the paged prefill
step at the top (rung x sequence bucket) cell of each named cell, with the
cell's page store, for one chip of a described ``v5e:2x2`` topology, and
prints ``memory_analysis()`` in GB.  No chip is needed.  It compiles the
plain-jit model steps (``fuse="none"``): the Forge programs split the same
work into segments and hold at least these bytes.  Add the page store
twice more (the scheduler keeps the server's store alive beside a tick's
input and output) to reckon the peak.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))


def main(cells) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import correct, program
    from repro.models import get_model

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    confs = {c["name"]: c for c in bench["configs"]}
    works = {w["name"]: w for w in bench["workloads"]}
    for name in cells:
        w = works[name]
        cfg = json.loads((HERE.parent / confs[w["config"]]["file"]).read_text())
        cell = json.loads((HERE / "cells" / f"{name}.json").read_text())
        s = cell["server"]
        mc = program.program_config(cfg).with_(fuse="none")
        model = get_model(mc)
        ref = correct.load_reference(cfg["reference"])
        params = jax.eval_shape(lambda: program.program_params(cfg, 0, ref))
        store = jax.eval_shape(lambda: model.init_paged_cache(
            mc, 1, s["max_len"], num_pages=s["kv_pages"], page_size=s["kv_page_size"]))
        store = {k: store[k] for k in ("k_pages", "v_pages")}

        def sds(x, dtype=None):
            return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype, sharding=one)

        params = jax.tree_util.tree_map(sds, params)
        store = jax.tree_util.tree_map(sds, store)
        B = s["max_slots"]
        MP = s["max_len"] // s["kv_page_size"]
        S = s["top_seq_rung"]
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)  # noqa: E731
        mask = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one)

        def step(fn):
            def f(p, st, pt, tok, pos, m):
                return fn(p, dict(st, page_table=pt), tok, pos, mc, slot_mask=m)
            return f

        gb = 1e9
        store_gb = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(store)) / gb
        weights_gb = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params)) / gb
        print(f"{name}: weights {weights_gb:.3f} GB, page store {store_gb:.3f} GB "
              f"({s['kv_pages']} pages of {s['kv_page_size']})", flush=True)
        for label, fn, tok in (("decode", model.paged_decode_step, i32(B, 1)),
                               ("prefill", model.paged_prefill_step, i32(B, S))):
            c = jax.jit(step(fn)).lower(params, store, i32(B, MP), tok, i32(B), mask).compile()
            m = c.memory_analysis()
            print(f"  {label} B={B} S={tok.shape[1]}: "
                  f"arguments {m.argument_size_in_bytes / gb:.3f} "
                  f"outputs {m.output_size_in_bytes / gb:.3f} "
                  f"temp {m.temp_size_in_bytes / gb:.3f} GB", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
