"""Async background compilation + persistent compile cache (ISSUE 7).

The inline compiler stalls a serve tick for the full Phase 1-4 build
whenever traffic discovers a cold bucket — a p99/pmax tick-latency
cliff.  With ``--async-compile`` the scheduler submits the exact rung
to the CompileService and pads into the nearest warm dominating rung,
so a tick never blocks once any dominating program exists; the exact
program takes over when the background build lands.

Both servers warm ONLY the top decode rung, then serve the same
retire-heavy workload whose occupancy decays through the cold lower
rungs.  Reported / gated:

* ``warm_fallbacks`` (async) — ticks served by a padded dominating
  rung while the exact rung compiled in the background (gated >= 1),
* ``compile_wait_s`` split — request-visible stall seconds.  The async
  run must show (near-)zero wait: everything it discovered cold was
  dominated by the warm top rung (gated ~0).  The inline run absorbs
  every one of those builds in its ticks instead,
* background compile throughput — builds completed off the request
  path and the summed worker busy seconds,
* fidelity — the async run's tokens are asserted bitwise-equal to the
  inline run's, fallback ticks and mid-run program switches included,
* restart replay — a second server pointed at the same ``--cache-dir``
  must rebuild its whole bucket ladder from disk with ZERO full
  builds (gated == 0), inner per-block forge bodies included.
"""
from __future__ import annotations

import shutil
import tempfile
from typing import List

import jax
import numpy as np

from repro.configs import get_config
from repro.core import get_compile_cache
from repro.launch.serve import BatchedServer, Request, SlotScheduler
from repro.models import get_model
import repro.models._forge as forge_glue

from . import common
from .common import Csv

MAX_LEN = 64
MAX_SLOTS = 8
N_REQUESTS = 24
FAST_N_REQUESTS = 14
#: long enough that steady decode ticks dominate and the (few) stall
#: ticks of the inline run sit in the tail of the distribution
MAX_NEW = 12
FAST_MAX_NEW = 8


def make_workload(n: int, max_new: int, seed: int = 0) -> List[Request]:
    """One admission wave, then a retire-only decay: budgets are
    staggered so slots drain a few at a time and the live count walks
    down through every lower rung (8 -> 4 -> 2 -> 1), each discovered
    cold mid-serve."""
    rng = np.random.default_rng(seed)
    return [
        Request(
            rid=i,
            prompt=rng.integers(0, 512, (4 + i % 5,)).astype(np.int32),
            max_new=max_new + 2 * (i % MAX_SLOTS),
            arrival=0,
        )
        for i in range(n)
    ]


def _server(cfg, params, **kw) -> BatchedServer:
    return BatchedServer(
        cfg, params, max_len=MAX_LEN, mode="forge",
        backend="segment_jit", bucket_policy="pow2", **kw,
    )


def run(csv: Csv) -> None:
    fast = common.FAST
    n = FAST_N_REQUESTS if fast else N_REQUESTS
    max_new = FAST_MAX_NEW if fast else MAX_NEW

    cfg = get_config("forge-125m", smoke=True)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    reqs = make_workload(n, max_new)
    prompt_lens = sorted({len(r.prompt) for r in reqs})
    top = MAX_SLOTS  # the only warm rung: everything else is cold

    # -- inline (sync) reference: cold rungs compile inside the tick --
    sync_srv = _server(cfg, params)
    sync_srv.warmup([top], prompt_lens=prompt_lens)
    sync_sched = SlotScheduler(sync_srv, max_slots=MAX_SLOTS)
    rs = sync_sched.run(make_workload(n, max_new))
    sync_wait = sync_srv.bucketed.stats.compile_wait_s

    # -- async: cold rungs go to the service, ticks pad into the warm
    #    top rung until the exact program lands ----------------------
    async_srv = _server(cfg, params, async_compile=True,
                        compile_workers=2)
    try:
        async_srv.warmup([top], prompt_lens=prompt_lens)
        async_sched = SlotScheduler(async_srv, max_slots=MAX_SLOTS)
        ra = async_sched.run(reqs)
        async_srv.compile_service.wait_idle(120.0)
        bs = async_srv.bucketed.stats
        svc = async_srv.compile_service.stats
        async_wait = bs.compile_wait_s

        # fidelity: fallback ticks and mid-run rung switches must not
        # change a single emitted token
        assert set(rs["results"]) == set(ra["results"])
        for rid in rs["results"]:
            np.testing.assert_array_equal(
                rs["results"][rid]["tokens"], ra["results"][rid]["tokens"],
                err_msg=f"request {rid} diverged between inline and async",
            )
        assert ra["warm_fallbacks"] >= 1, (
            "workload never exercised the warm-bucket fallback"
        )
        assert async_wait <= 0.005, (
            f"async run blocked {async_wait:.3f}s on compiles despite a "
            f"warm dominating rung"
        )

        csv.row(
            "async_compile/inline",
            rs["wall_s"] * 1e6,
            f"tok_per_s={rs['tok_per_s']:.0f};"
            f"compile_wait_s={sync_wait:.3f}",
        )
        csv.row(
            "async_compile/async",
            ra["wall_s"] * 1e6,
            f"tok_per_s={ra['tok_per_s']:.0f};"
            f"warm_fallbacks={ra['warm_fallbacks']};"
            f"fallback_calls={bs.fallback_calls};"
            f"fallback_cells_padded={bs.fallback_cells_padded};"
            f"compile_wait_s={async_wait:.3f};"
            f"bg_compiles={svc.completed};"
            f"bg_busy_s={svc.busy_s:.3f};"
            f"bg_compiles_per_s="
            f"{svc.completed / svc.busy_s if svc.busy_s else 0.0:.2f}",
        )
    finally:
        async_srv.compile_service.shutdown()

    # -- restart replay: the persistent tier rebuilds the ladder ------
    g = get_compile_cache()
    store0 = g.store
    cache_dir = tempfile.mkdtemp(prefix="forge-bench-cache-")
    try:
        forge_glue.clear_cache()
        g.clear()
        g.store = None
        srv1 = _server(cfg, params, cache_dir=cache_dir)
        srv1.warmup([2, 4], prompt_lens=prompt_lens)
        writes = srv1.compile_cache.store.stats.writes
        builds1 = srv1.compile_cache.stats.misses + g.stats.misses
        # simulated restart: every in-memory tier is dropped; only the
        # cache directory survives
        forge_glue.clear_cache()
        g.clear()
        g.store = None
        srv2 = _server(cfg, params, cache_dir=cache_dir)
        srv2.warmup([2, 4], prompt_lens=prompt_lens)
        builds2 = srv2.compile_cache.stats.misses + g.stats.misses
        disk_hits = (srv2.compile_cache.stats.disk_hits
                     + g.stats.disk_hits)
        assert builds2 == 0, (
            f"restart replayed with {builds2} full builds (expected 0)"
        )
        csv.row(
            "async_compile/replay",
            0.0,
            f"builds_cold_start={builds1};entries_written={writes};"
            f"builds_post_restart={builds2};disk_hits={disk_hits};"
            f"bytes_written={srv1.compile_cache.store.stats.bytes_written}",
        )
    finally:
        forge_glue.clear_cache()
        g.clear()
        g.store = store0
        shutil.rmtree(cache_dir, ignore_errors=True)
