"""Trace reduction on small recorded traces with known answers."""
from __future__ import annotations

import glob
import os

import pytest
from jax.profiler import ProfileData

import tiny  # noqa: F401  (puts the benchmark on the import path)
from bench import trace

MS = 10**9  # picoseconds per millisecond

# one TPU with three ops (two overlap, inside one program) and a host thread
# with the window span and one benchmark span; all times in ms from the trace
# start.  Op and program events carry their names as a TPU trace does.
XSPACE = f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{
    id: 1
    name: "XLA Ops"
    timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: {1 * MS} duration_ps: {2 * MS} }}
    events {{ metadata_id: 2 offset_ps: {2 * MS} duration_ps: {2 * MS} }}
    events {{ metadata_id: 1 offset_ps: {7 * MS} duration_ps: {1 * MS} }}
  }}
  lines {{
    id: 2
    name: "XLA Modules"
    timestamp_ns: 0
    events {{ metadata_id: 3 offset_ps: {MS // 2} duration_ps: {4 * MS} }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = bf16[8,128]{{1,0}} fusion(%p0)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%dot.2 = f32[8]{{0}} dot(%a, %b)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "jit_step(7)" }} }}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{
    id: 1
    name: "python3"
    timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: {10 * MS} }}
    events {{ metadata_id: 2 offset_ps: {4 * MS} duration_ps: {3 * MS} }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "sched.admit" }} }}
}}
"""


def test_busy_idle_ops_and_gaps():
    red = trace.reduce(ProfileData.from_text_proto(XSPACE), ("sched.admit",))
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(10e-3)
    assert red["busy_s"] == pytest.approx(4e-3)  # [1, 4] and [7, 8] ms
    assert trace.idle_share(red) == pytest.approx(0.6)
    # named program/op, where a program runs around the op
    assert red["device_ops"] == [["jit_step/fusion.1 bf16[8,128]", pytest.approx(2e-3)],
                                 ["jit_step/dot.2 f32[8]", pytest.approx(2e-3)],
                                 ["fusion.1 bf16[8,128]", pytest.approx(1e-3)]]
    gaps = dict(map(tuple, red["idle_gaps"]))
    assert gaps["sched.admit"] == pytest.approx(3e-3)  # [4, 7] ms
    assert gaps["host code"] == pytest.approx(3e-3)  # [0, 1] and [8, 10] ms
    assert gaps["longest gap: sched.admit"] == pytest.approx(3e-3)


def test_gaps_with_no_request_in_flight_are_named_so():
    # the server held no request in the first 0.9 ms of the window
    red = trace.reduce(ProfileData.from_text_proto(XSPACE), ("sched.admit",),
                       quiet=[(0.0, 0.9e-3)])
    gaps = dict(map(tuple, red["idle_gaps"]))
    assert gaps[trace.QUIET] == pytest.approx(1e-3)  # [0, 1] ms
    assert gaps["host code"] == pytest.approx(2e-3)  # [8, 10] ms
    assert gaps["sched.admit"] == pytest.approx(3e-3)


def test_union_merges_overlaps():
    import numpy as np

    u = trace._union(np.array([[5.0, 6.0], [0.0, 2.0], [1.0, 3.0], [3.0, 4.0]]))
    assert u.tolist() == [[0.0, 4.0], [5.0, 6.0]]


def test_a_recorded_cpu_trace_has_no_device_to_read(tmp_path):
    import jax
    import jax.numpy as jnp

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            jnp.ones((64, 64)).sum().block_until_ready()
    path = trace.find_xplane(str(tmp_path))
    assert os.path.getsize(path) > 0
    red = trace.reduce(path)
    assert red["devices"] == 0 and red["busy_s"] == 0.0
    assert trace.idle_share(red) is None
    assert glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
