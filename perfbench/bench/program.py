"""The system under test, as the benchmark drives it.

``SlotScheduler.run`` in wall mode over ``BatchedServer(mode="forge",
backend="segment_jit", paged=True)``.  From the program the benchmark takes
only that entry, its counters (``BucketStats``, ``ExecutorStats``,
``PageStats``, the ``run()`` result) and, in traced runs, the device trace.
The host spans in the trace are the benchmark's own, around its calls into
the program's layers.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from . import correct, weights

ENTRY = dict(mode="forge", backend="segment_jit", paged=True)


def program_config(cfg: Dict):
    """The program's ModelConfig for a configuration file; refuses drift.

    The program's own config for ``program.arch`` with the file's depth and
    dtype and any ``program.overrides``; every field the reference's
    ``program_fields(cfg)`` names has to read as it says."""
    from repro.configs import get_config

    prog = cfg["program"]
    mc = get_config(prog["arch"], smoke=bool(prog.get("smoke", False)))
    mc = mc.with_(n_layers=int(cfg["num_hidden_layers"]), dtype=cfg["torch_dtype"],
                  **prog.get("overrides", {}))
    want = correct.load_reference(cfg["reference"]).program_fields(cfg)
    got = {k: getattr(mc, k) for k in want}
    if got != want:
        raise SystemExit(f"program config {mc.name} differs from {cfg['name']}: {got} != {want}")
    return mc


def program_params(cfg: Dict, seed: int, ref) -> Dict[str, Any]:
    """Seeded weights in the program's pytree, made in one jitted call: each
    tensor of the reference's layout at its path."""
    glob, groups = ref.layout(cfg)
    tree: Dict[str, Any] = {}
    for path, arr in weights.make_all(seed, glob, groups, cfg["torch_dtype"]).items():
        *parents, leaf = path.split("/")
        node = tree
        for name in parents:
            node = node.setdefault(name, {})
        node[leaf] = arr
    return tree


def check_params(mc, params) -> None:
    """Refuse weights whose tree, shapes or dtypes differ from the program's
    own ``init`` for ``mc``."""
    from repro.models import get_model

    def leaves(tree):
        return {jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype))
                for p, x in jax.tree_util.tree_leaves_with_path(tree)}

    got = leaves(params)
    want = leaves(jax.eval_shape(lambda: get_model(mc).init(jax.random.PRNGKey(0), mc)))
    if got != want:
        diff = {k: (got.get(k), want.get(k)) for k in sorted(set(got) | set(want))
                if got.get(k) != want.get(k)}
        raise SystemExit(f"weights differ from the program's tree for {mc.name} "
                         f"(path: (made, program)): {diff}")


def seq_rungs(policy: str) -> List[int]:
    assert policy.startswith("ladder:"), policy
    return [int(x) for x in policy[len("ladder:"):].split(",")]


class _Span:
    """Calls ``fn`` inside a profiler span named ``name``."""

    def __init__(self, fn, name: str):
        self._fn, self._name = fn, name

    def __call__(self, *a, **k):
        with jax.profiler.TraceAnnotation(self._name):
            return self._fn(*a, **k)

    def __getattr__(self, attr):
        return getattr(self._fn, attr)


class Cell:
    """One server + scheduler for a cell, its warm-up and its window."""

    def __init__(self, cfg: Dict, cell: Dict, params):
        from repro.launch.serve import BatchedServer, SlotScheduler

        self.cfg, self.cell = cfg, cell
        self.mc = program_config(cfg)
        check_params(self.mc, params)
        s = cell["server"]
        self.max_len = int(s["max_len"])
        self.srv = BatchedServer(
            self.mc, params, max_len=self.max_len, bucket_policy=s["bucket_policy"],
            seq_bucket_policy=s["seq_bucket_policy"], kv_page_size=int(s["kv_page_size"]),
            kv_pages=int(s["kv_pages"]), **ENTRY)
        self.sched = SlotScheduler(self.srv, max_slots=int(s["max_slots"]))
        self.rungs = [r for r in seq_rungs(s["seq_bucket_policy"])
                      if r <= int(s["top_seq_rung"])]
        #: prompt id -> prefix-tree skip of its last admission
        self.skips: Dict[int, int] = {}
        self._record_skips()

    def _record_skips(self) -> None:
        tree = self.srv.prefix_tree
        match = tree.match

        def recording_match(tokens, **kw):
            shared, skip = match(tokens, **kw)
            self.skips[id(tokens)] = skip
            return shared, skip

        tree.match = recording_match

    def reset(self, params) -> None:
        """New weights and an empty page pool, keeping every compiled program
        (several seeds in one process, for calibration)."""
        from repro.core.paging import PagePool, PrefixTree

        srv = self.srv
        srv.params = params
        srv.page_pool = PagePool(srv.page_pool.num_pages, srv.page_pool.page_size)
        srv.prefix_tree = PrefixTree(srv.page_pool)
        self._record_skips()

    # -- set-up -----------------------------------------------------------

    def warm(self) -> None:
        """Compile and run once every program, gather and host op the
        cell's traffic can reach: each decode rung, and each (rung, seq
        bucket) prefill cell up to ``top_seq_rung``, through ``run()``."""
        from repro.launch.serve import Request

        self.sched.warmup(prompt_lens=self.rungs)
        reqs, rid, tick = [], 0, 0
        small = max(8, self.rungs[0] // 2)
        rng = np.random.default_rng(0)  # distinct prompts: no prefix-tree hits
        for e in self.sched.rungs():
            for s in self.rungs:
                lens = [s - 4] + [min(small, s - 4)] * (e - 1)
                for P in lens:
                    prompt = rng.integers(1, int(self.cfg["vocab_size"]), P).astype(np.int32)
                    reqs.append(Request(rid=rid, prompt=prompt, max_new=4, arrival=tick))
                    rid += 1
                tick += 16
        res = self.sched.run(reqs)
        bad = {r: v["error"] for r, v in res["results"].items() if "error" in v}
        if bad:
            raise SystemExit(f"warm-up requests failed: {bad}")
        self.srv.prefix_tree.clear()

    def hold_prefixes(self, prefixes: List[np.ndarray]) -> None:
        """Pre-fill the shared prompts into the prefix tree, as a running
        server would already hold them."""
        from repro.launch.serve import Request

        self.srv.prefix_tree.clear()
        if prefixes:
            self.sched.run([Request(rid=i, prompt=p, max_new=1)
                            for i, p in enumerate(prefixes)])

    # -- the window ---------------------------------------------------------

    def counters(self) -> Dict[str, Any]:
        srv = self.srv
        dec, pre = srv.bucketed, srv.prefill_bucketed
        ps = srv.page_pool.stats
        ex = [(m.stats.total_calls, m.stats.total_segments_executed, m.stats.n_host)
              for m in dec.programs.values()]
        return {
            "decode_calls": sum(c for c, _, _ in ex),
            "decode_dispatches": sum(s + h * c for c, s, h in ex),
            "prefill_cells": pre.stats.rows_real + pre.stats.rows_padded,
            "tokens_prefilled": ps.tokens_prefilled,
            "tokens_reused": ps.tokens_reused,
            "prefix_hits": ps.prefix_hits,
            "prefix_misses": ps.prefix_misses,
        }

    def run(self, turns, *, trace: bool) -> Dict[str, Any]:
        """Serve ``turns`` in wall mode; returns the run() result plus the
        window's counter differences and the requests sent."""
        from repro.launch.serve import Request

        reqs = [Request(rid=i, prompt=t.prompt, max_new=t.max_new, arrival_s=t.due_s)
                for i, t in enumerate(turns)]
        spans = self._spans() if trace else contextlib.nullcontext()
        before = self.counters()
        with spans:
            res = self.sched.run(reqs)
        after = self.counters()
        res["window_counters"] = {k: after[k] - before[k] for k in before}
        res["requests"] = reqs
        res["skips"] = {r.rid: self.skips.get(id(r.prompt), 0) for r in reqs}
        return res

    @contextlib.contextmanager
    def _spans(self):
        """Profiler spans around the benchmark's calls into each layer."""
        srv, sched = self.srv, self.sched
        saved = (srv.bucketed.program_for, srv.prefill_bucketed.program_for,
                 sched._admit_paged)

        def wrap_front(program_for, name):
            def program_for_spanned(*a, **k):
                mod, key, rest = program_for(*a, **k)
                return _Span(mod, name), key, rest
            return program_for_spanned

        srv.bucketed.program_for = wrap_front(saved[0], "forge.decode")
        srv.prefill_bucketed.program_for = wrap_front(saved[1], "forge.prefill")
        sched._admit_paged = _Span(saved[2], "sched.admit")
        try:
            yield
        finally:
            del srv.bucketed.program_for, srv.prefill_bucketed.program_for
            del sched._admit_paged

    def close(self) -> None:
        """Drop every device array the program holds."""
        self.srv.params = None
        self.srv.page_store = None
        self.srv = self.sched = None


def peak_bytes(dev) -> Optional[int]:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")
