"""The reduction against the program's spans and scopes, on a small recorded
trace with known answers."""
from __future__ import annotations

import pytest
from jax.profiler import ProfileData

import tiny  # noqa: F401  (puts the benchmark on the import path)
from bench import spans

MS = 10**9  # picoseconds per millisecond
US = 10**6


def _ev(meta: int, start_ms: float, dur_ms: float) -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_ms * MS)} "
            f"duration_ps: {int(dur_ms * MS)} }}")


def _op(meta: int, name: str, op_name: str = "") -> str:
    """Event metadata; a TPU trace keeps an op's path in its ``tf_op`` stat."""
    stat = f' stats {{ metadata_id: 9 str_value: "{op_name}" }}' if op_name else ""
    return f'event_metadata {{ key: {meta} value {{ id: {meta} name: "{name}"{stat} }} }}'


BODY = "jit(scan)/while/body/closed_call"
OPS = [  # (metadata id, start ms, duration ms, HLO text, op_name)
    (1, 1, 8, "%while.3 = (s32[], bf16[8,1,96]) while(%t)", ""),
    (2, 2, 2, "%scatter.1 = bf16[225,32,16,96] scatter(%a)", BODY + "/attn/kv.write/scatter:"),
    (3, 4, 1, "%fusion.2 = bf16[8,1,96] fusion(%b)", BODY + "/attn/dot_general:"),
    (4, 5, 2, "%copy.55 = bf16[1,225,32,16,96] copy(%c)", ""),
    (5, 12, 1, "%fusion.7 = bf16[8,1,8192] fusion(%d)", "jit(seg_fn)/mlp/dot_general:"),
    (6, 15, 1, "%fusion.9 = bf16[8,1,32064] fusion(%e)", "jit(seg_fn)/logits/dot_general:"),
]


def xspace() -> str:
    """One TPU.  The layer scan's ``while`` [1, 9] ms holds three body ops:
    a kv.write scatter, an attn dot and a copy XLA put in with no scope;
    then an mlp op [12, 13] and a logits op [15, 16] in another program.
    The host thread: the window [0, 20]; three ticks; a dispatch around the
    scan; an admission with its prefill; a harvest holding a full
    collection; a sleep toward the next arrival in the last tick."""
    ops = "\n    ".join(_ev(m, s, d) for m, s, d, _, _ in OPS)
    metas = "\n  ".join(_op(m, t, p) for m, _, _, t, p in OPS)
    return f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{
    id: 1
    name: "XLA Ops"
    timestamp_ns: 0
    {ops}
  }}
  lines {{
    id: 2
    name: "XLA Modules"
    timestamp_ns: 0
    {_ev(7, 0.5, 9)}
    {_ev(8, 11.5, 5)}
  }}
  {metas}
  {_op(7, "jit_scan(3)")}
  {_op(8, "jit_seg_fn(4)")}
  stat_metadata {{ key: 9 value {{ id: 9 name: "tf_op" }} }}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{
    id: 1
    name: "python3"
    timestamp_ns: 0
    {_ev(1, 0, 20)}
    {_ev(2, 0.5, 9.5)}
    {_ev(3, 0.8, 8.8)}
    {_ev(2, 10, 7)}
    {_ev(4, 10, 2)}
    {_ev(5, 10.5, 1.4)}
    {_ev(6, 13, 1.5)}
    {_ev(7, 13.2, 0.4)}
    {_ev(2, 17, 3)}
    {_ev(8, 17.2, 2.6)}
  }}
  {_op(1, "bench.window")}
  {_op(2, "serve.tick")}
  {_op(3, "serve.dispatch")}
  {_op(4, "serve.admit")}
  {_op(5, "serve.prefill")}
  {_op(6, "serve.harvest")}
  {_op(7, "py.gc")}
  {_op(8, "serve.wait_arrival")}
}}
"""


#: the server held no request in the last 3 ms of the window
QUIET = [(17e-3, 20e-3)]


def _xplane(tmp_path_factory) -> str:
    path = tmp_path_factory.mktemp("xplane") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(xspace()))
    return str(path)


@pytest.fixture(scope="module")
def red(tmp_path_factory):
    return spans.reduce(_xplane(tmp_path_factory), quiet=QUIET)


def test_nested_ops_count_once(red):
    assert red["busy_s"] == pytest.approx(10e-3)  # [1, 9], [12, 13], [15, 16]
    ops = dict(map(tuple, red["op_self_s"]))
    # the while's own time is 8 ms less the 5 ms of its body ops
    assert ops["jit_scan/while.3 bf16[8,1,96]"] == pytest.approx(3e-3)
    assert ops["jit_scan/copy.55 bf16[1,225,32,16,96]"] == pytest.approx(2e-3)
    assert sum(ops.values()) == pytest.approx(red["busy_s"])


def test_scoped_and_unscoped_ops(red):
    assert red["scope_s"] == pytest.approx({
        "kv.write": 2e-3, "attn": 1e-3, "mlp": 1e-3, "logits": 1e-3,
        spans.UNSCOPED: 5e-3,
    })
    assert sum(red["scope_s"].values()) == pytest.approx(red["busy_s"])
    unscoped = dict(map(tuple, red["unscoped_ops"]))
    assert unscoped == pytest.approx({"jit_scan/while.3 bf16[8,1,96]": 3e-3,
                                      "jit_scan/copy.55 bf16[1,225,32,16,96]": 2e-3})


def test_idle_goes_to_the_innermost_span(red):
    # idle with a request in flight: [0, 1], [9, 12], [13, 15], [16, 17] ms
    assert red["idle_in_flight_s"] == pytest.approx(7e-3)
    idle = dict(map(tuple, red["idle_by_span"]))
    assert idle == pytest.approx({
        spans.UNCOVERED: 0.5e-3,  # [0, 0.5]: the window alone
        "serve.tick": 2.2e-3,  # [0.5, 0.8], [9.6, 10], [14.5, 15], [16, 17]
        "serve.dispatch": 0.8e-3,  # [0.8, 1], [9, 9.6]
        "serve.admit": 0.6e-3,  # [10, 10.5], [11.9, 12]
        "serve.prefill": 1.4e-3,
        "serve.harvest": 1.1e-3,  # [13, 15] less the collection
        "py.gc": 0.4e-3,
    })
    assert red["idle_covered_share"] == pytest.approx(6.5 / 7)


def test_loop_idle_leaves_out_the_wait_for_arrivals(red):
    # idle inside the ticks less [17.2, 19.8]: 0.5 + 3 + 2 + 1.2 + 0.2 ms
    assert red["loop_idle_s"] == pytest.approx(6.9e-3)
    assert red["gc_count"] == 1 and red["gc_s"] == pytest.approx(0.4e-3)


def test_a_program_without_span_names_leaves_idle_uncovered(tmp_path_factory):
    red = spans.reduce(_xplane(tmp_path_factory), quiet=QUIET, spans=(), scopes=())
    assert dict(map(tuple, red["idle_by_span"])) == pytest.approx(
        {spans.UNCOVERED: 7e-3})
    assert red["scope_s"] == pytest.approx({spans.UNSCOPED: 10e-3})
    assert red["loop_idle_s"] == 0.0


@pytest.mark.parametrize("path,scope", [
    (BODY + "/checkpoint/attn/kv.write/scatter:", "kv.write"),
    (BODY + "/checkpoint/attn/kv.gather/gather:", "kv.gather"),
    (BODY + "/checkpoint/attn/...k,kn->...n/dot_general:", "attn"),
    ("jit(seg_fn)/logits/argmax:", "logits"),
    ("jit(scan)/while/body/dynamic_slice:", None),
    ("", None),
])
def test_op_scope_is_the_innermost_program_scope(path, scope):
    assert spans.op_scope(path) == scope


@pytest.mark.parametrize("ops,lo,want", [
    # (start, end) in us; a parent with two children, then a sibling
    ([(0, 10), (1, 3), (4, 6), (12, 13)], 0, [6, 2, 2, 1]),
    # a child that runs past its parent's end counts its overlap once
    ([(0, 4), (3, 6)], 0, [3, 3]),
    # ops clipped to a window that starts at 2 us
    ([(0, 5), (1, 3)], 2, [2, 1]),
])
def test_self_times(ops, lo, want):
    got = spans.self_times([(f"op{k}", "", s * US, e * US) for k, (s, e) in enumerate(ops)],
                           lo * US, 20 * US)
    assert [t / US for _, _, t in got] == pytest.approx(want)


def test_loop_idle_share_reads_the_span_split(red):
    from bench import readers

    assert readers.loop_idle_share({"spans": red}) == pytest.approx(100 * 6.9 / 20)
    assert readers.loop_idle_share({"spans": None}) is None


def test_a_traced_run_hands_readers_the_span_split():
    """``run_cell`` reduces its trace against the program's names too; the
    CPU has no device plane, so there is nothing to split and the reader
    returns nothing."""
    from bench import readers

    run = tiny.R.run_cell(tiny.spec(), 2**31 + 99, 2.0, True, require_chip=False)
    ctx = run["ctx"]
    assert ctx["setup_s"] > 0
    assert ctx["spans"]["devices"] == 0 and ctx["spans"]["window_s"] == 0.0
    assert readers.loop_idle_share(ctx) is None
