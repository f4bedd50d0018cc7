"""Program spans, device scopes and the serve counters that time work.

A tiny paged ``SlotScheduler`` run under ``jax.profiler`` on the CPU: every
``serve.*`` span, ``forge.segment`` and the set-up spans are in the trace
and nest as ``repro.runtime.trace`` says; ``serve.admit`` carries the
admitted request ids; each request's queue wait lies within its TTFT; the
compile split is part of the compile time; and the tokens are the same
with and without a profiler.
"""
import gc
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core.capture import trace_to_graph
from repro.launch.serve import BatchedServer, Request, SlotScheduler
from repro.models import get_model
from repro.runtime import trace as rt


def _requests(vocab):
    rng = np.random.default_rng(3)
    due = [0.0, 0.0, 0.05, 1.5]  # the last one arrives with nothing in flight
    return [Request(rid=i, prompt=rng.integers(1, vocab, 5 + 3 * i).astype(np.int32),
                    max_new=4 + i, arrival_s=t) for i, t in enumerate(due)]


def _host_events(log_dir):
    """{span name: [(line, start, end, stats)]} of the program's spans."""
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name in rt.SPANS:
                    out.setdefault(e.name, []).append(
                        (ln.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cfg = get_config("phi3-mini-3.8b", smoke=True)
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    srv = BatchedServer(cfg, params, max_len=32, mode="forge", backend="segment_jit",
                        seq_bucket_policy="ladder:8,16,32", paged=True, kv_page_size=8)
    sched = SlotScheduler(srv, max_slots=2)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(log_dir):
        sched.warmup(prompt_lens=[8, 16])
        traced = sched.run(_requests(cfg.vocab))
        with rt.gc_spans():
            gc.collect()
    srv.prefix_tree.clear()
    plain = sched.run(_requests(cfg.vocab))
    return {"srv": srv, "traced": traced, "plain": plain, "spans": _host_events(log_dir)}


@pytest.mark.parametrize("name", rt.SPANS)
def test_span_in_trace(served, name):
    assert served["spans"].get(name), f"no {name} span in the trace"


@pytest.mark.parametrize("child,parents", [
    ("serve.admit", ("serve.tick",)),
    ("serve.prefill", ("serve.admit",)),
    ("serve.resize", ("serve.tick",)),
    ("serve.dispatch", ("serve.tick",)),
    ("serve.pool_check", ("serve.tick",)),
    ("serve.harvest", ("serve.tick",)),
    ("serve.wait_arrival", ("serve.tick",)),
    ("forge.segment", ("serve.dispatch", "serve.prefill")),
    ("xla.compile", ("forge.backend",)),
])
def test_spans_nest(served, child, parents):
    spans = served["spans"]

    def inside(line, s, e, name):
        return any(pl == line and ps <= s and e <= pe for pl, ps, pe, _ in spans[name])

    for line, s, e, _ in spans[child]:
        if child == "forge.segment" and not inside(line, s, e, "serve.tick"):
            continue  # a program call of the warm-up, outside the loop
        assert any(inside(line, s, e, p) for p in parents), (
            f"{child} at {s} lies outside every {parents}")


def test_admit_span_carries_request_ids(served):
    rids = set()
    for _, _, _, stats in served["spans"]["serve.admit"]:
        rids.update(int(r) for r in str(stats["rids"]).split(";"))
    assert set(served["traced"]["results"]) <= rids


@pytest.mark.parametrize("run", ["traced", "plain"])
def test_queue_wait_within_ttft(served, run):
    results = served[run]["results"]
    assert len(results) == 4
    for rid, r in results.items():
        assert "error" not in r
        assert 0.0 <= r["queue_wait_s"] <= r["ttft_s"], rid


@pytest.mark.parametrize("front", ["bucketed", "prefill_bucketed"])
def test_compile_split_within_compile_time(served, front):
    st = getattr(served["srv"], front).stats
    assert st.compiles > 0
    assert st.forge_phases_s > 0.0 and st.xla_compile_s > 0.0
    assert st.forge_phases_s + st.xla_compile_s <= st.compile_s


def test_tokens_equal_with_and_without_profiler(served):
    traced, plain = served["traced"]["results"], served["plain"]["results"]
    assert set(traced) == set(plain)
    for rid in traced:
        np.testing.assert_array_equal(traced[rid]["tokens"], plain[rid]["tokens"])


def test_capture_keeps_named_scopes():
    """Phase 1 records each equation's scope, through inlined calls."""

    @jax.jit
    def inner(x):
        with jax.named_scope("kv.write"):
            return x * 2.0

    def fn(x):
        with jax.named_scope("attn"):
            y = inner(x) + 1.0
        return y.sum()

    g = trace_to_graph(fn, np.ones((4,), np.float32)).graph
    scopes = {n.op: n.meta.get("scope", "") for n in g.nodes.values()}
    assert scopes["mul"].split("/")[-1] == "kv.write"
    assert scopes["mul"].split("/")[0] == "attn"
    assert scopes["add"] == "attn"
    assert scopes["reduce_sum"] == ""
