"""Reduction of a profiler trace against the program's own span and scope names.

The program (``repro.runtime.trace``) emits host spans (``serve.*``,
``forge.*``, ``py.gc``) and names the device ops of its model step with
scopes (``kv.write``, ``kv.gather``, ``attn``, ``mlp``, ``logits``).  This
module reads them from the ``.xplane.pb`` that ``jax.profiler`` writes:

- self time per device op: an op's duration less the time of the ops
  nested inside it (a TPU trace lists a ``while`` and the ops of its body
  on one line), so no time is counted twice and the ops sum to busy time;
- device self time by scope: an op belongs to the innermost program scope
  in its ``op_name`` path, else it is ``unscoped`` and listed by name;
- each idle gap of ``SHORT_GAP_NS`` or more while a request is in flight,
  put down to the innermost program span covering it;
- device idle inside ``serve.tick`` less ``serve.wait_arrival``.

A program without these names (one older than them) gives empty splits.
"""
from __future__ import annotations

import bisect
import heapq
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import trace as tr

try:
    from repro.runtime.trace import SCOPES, SPANS
except ImportError:  # a program that emits no spans of its own
    SCOPES: Tuple[str, ...] = ()
    SPANS: Tuple[str, ...] = ()

UNSCOPED = "unscoped"
#: idle gaps no program span covers
UNCOVERED = "no program span"
#: the stat of a TPU op's event metadata that holds its ``op_name`` path
#: (``jit(scan)/while/body/closed_call/checkpoint/attn/kv.gather/gather:``)
OP_NAME_STAT = "tf_op"


def op_scope(path: str, scopes: Sequence[str] = SCOPES) -> Optional[str]:
    """The innermost of ``scopes`` among the components of an ``op_name``
    path (``jit(scan)/while/body/attn/kv.write/scatter`` -> ``kv.write``)."""
    for part in reversed(path.split("/")):
        if part in scopes:
            return part
    return None


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """(field number, wire type, value) of a protobuf message; a
    length-delimited value is returned as bytes, unparsed."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wt}")
        yield f, wt, v


def metadata_paths(xplane: bytes) -> Dict[str, Dict[str, str]]:
    """{device plane name: {op event name: op_name path}}.  A TPU trace
    keeps an op's path on the op's event metadata, which ``ProfileData``
    does not show; this reads XSpace.planes, XPlane.name / event_metadata /
    stat_metadata and XEventMetadata.name / stats, and skips the rest
    unparsed."""
    out: Dict[str, Dict[str, str]] = {}
    for f, _, plane in _fields(xplane):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pf, _, v in _fields(plane):
            if pf == 2:
                name = v.decode()
            elif pf == 4:
                metas.append(v)
            elif pf == 5:
                for ef, _, ev in _fields(v):
                    if ef == 2:
                        d = {sf: sv for sf, _, sv in _fields(ev)}
                        stat_names[d.get(1, 0)] = d.get(2, b"").decode()
        if not tr._is_device(name):
            continue
        want = {k for k, nm in stat_names.items() if nm == OP_NAME_STAT}
        paths = {}
        for entry in metas:
            for ef, _, ev in _fields(entry):
                if ef != 2:
                    continue
                op = path = ""
                for mf, _, mv in _fields(ev):
                    if mf == 2:
                        op = mv.decode()
                    elif mf == 5:
                        st = {sf: sv for sf, _, sv in _fields(mv)}
                        if st.get(1) in want and isinstance(st.get(5), bytes):
                            path = st[5].decode()
                if path:
                    paths[op] = path
        out[name] = paths
    return out


def device_ops(plane, paths: Dict[str, str]) -> List[Tuple[str, str, float, float]]:
    """(name, op_name path, start, end) of each op event of a device plane,
    named as ``bench.trace`` names them (its ``_device_ops``, with the path
    from ``paths``, :func:`metadata_paths` of the plane)."""
    lines = {ln.name: ln for ln in plane.lines}
    want = next((w for w in tr.OP_LINES if w in lines), None)
    if want is None:
        return []
    mods = []
    if want == "XLA Ops" and "XLA Modules" in lines:
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns, tr._short(e.name))
                      for e in lines["XLA Modules"].events)
    starts = [m[0] for m in mods]
    out = []
    for e in lines[want].events:
        name = tr._short(e.name)
        i = bisect.bisect_right(starts, e.start_ns) - 1
        if i >= 0 and e.start_ns < mods[i][1]:
            name = f"{mods[i][2]}/{name}"
        out.append((name, paths.get(e.name, ""), e.start_ns, e.start_ns + e.duration_ns))
    return out


def self_times(ops: Sequence[Tuple[str, str, float, float]], lo: float,
               hi: float) -> List[Tuple[str, str, float]]:
    """(name, path, self time) of each op clipped to ``[lo, hi]``: its time
    less the time of the ops nested in it.  Over a line whose ops nest or
    follow one another, the self times sum to the union of the ops."""
    iv = sorted(((max(s, lo), min(e, hi), name, path) for name, path, s, e in ops
                 if min(e, hi) > max(s, lo)), key=lambda o: (o[0], -o[1]))
    child = [0.0] * len(iv)
    stack: List[int] = []
    for k, (s, e, _, _) in enumerate(iv):
        while stack and iv[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            child[p] += min(e, iv[p][1]) - s
        stack.append(k)
    return [(name, path, (e - s) - c) for (s, e, name, path), c in zip(iv, child)]


def innermost(spans: Sequence[Tuple[float, float, str]]) -> Tuple[np.ndarray, List[Optional[str]]]:
    """Partition time by the innermost covering span (the covering span
    that started last; of two that started together, the one that ends
    first).  Returns the edges and the label of each
    ``[edges[i], edges[i + 1])`` (None where no span covers it)."""
    events = sorted([(s, 1, k) for k, (s, _, _) in enumerate(spans)]
                    + [(e, 0, k) for k, (_, e, _) in enumerate(spans)])
    edges: List[float] = []
    labels: List[Optional[str]] = []
    heap: List[Tuple[float, float, int]] = []
    ended = set()
    for t, is_start, k in events:
        if is_start:
            heapq.heappush(heap, (-spans[k][0], spans[k][1], k))
        else:
            ended.add(k)
        while heap and heap[0][2] in ended:
            heapq.heappop(heap)
        label = spans[heap[0][2]][2] if heap else None
        if edges and edges[-1] == t:
            labels[-1] = label
        else:
            edges.append(t)
            labels.append(label)
    return np.asarray(edges, np.float64), labels


def _split(gaps: np.ndarray, edges: np.ndarray,
           labels: List[Optional[str]]) -> Dict[Optional[str], float]:
    """Length of ``gaps`` under each label of a partition."""
    out: Dict[Optional[str], float] = defaultdict(float)
    for g0, g1 in gaps:
        i = int(np.searchsorted(edges, g0, side="right")) - 1
        t = g0
        while t < g1:
            end = min(edges[i + 1] if i + 1 < len(edges) else g1, g1)
            out[labels[i] if i >= 0 else None] += end - t
            t = end
            i += 1
    return out


def _minus(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Disjoint sorted intervals ``a`` less the union of ``b``."""
    b = tr._union(b) if len(b) else b.reshape(0, 2)
    out = []
    for s, e in a:
        t = s
        i = max(int(np.searchsorted(b[:, 1], s, side="right")), 0) if len(b) else 0
        while t < e and i < len(b) and b[i, 0] < e:
            if b[i, 0] > t:
                out.append((t, b[i, 0]))
            t = max(t, b[i, 1])
            i += 1
        if t < e:
            out.append((t, e))
    return np.asarray(out, np.float64).reshape(-1, 2)


def _intersect_len(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the intersection of two unions of intervals."""
    if not len(a) or not len(b):
        return 0.0
    a, b = tr._union(a), tr._union(b)
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            total += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total


def reduce(profile: str, quiet: Sequence[Tuple[float, float]] = (), top: int = 10,
           spans: Sequence[str] = SPANS, scopes: Sequence[str] = SCOPES) -> Dict[str, object]:
    """The trace reduced against the program's names; times in seconds.

    ``profile`` is the path of an ``.xplane.pb``; the window is the benchmark's ``bench.window`` span, else the extent of the
    device ops.  ``quiet`` holds the stretches, in seconds from the
    window's start, in which the server held no request."""
    from jax.profiler import ProfileData

    with open(profile, "rb") as fh:
        raw = fh.read()
    meta = metadata_paths(raw)
    planes = list(ProfileData.from_serialized_xspace(raw).planes)
    per_dev = [ops for ops in (device_ops(p, meta.get(p.name, {})) for p in planes
                               if tr._is_device(p.name)) if ops]
    empty = {"busy_s": 0.0, "window_s": 0.0, "devices": 0, "op_self_s": [], "scope_s": {},
             "unscoped_ops": [], "idle_in_flight_s": 0.0, "idle_by_span": [],
             "idle_covered_share": None, "loop_idle_s": 0.0, "gc_s": 0.0, "gc_count": 0}
    if not per_dev:
        return empty
    host = tr.host_spans(planes, tuple(spans))
    if tr.WINDOW_SPAN in host and len(host[tr.WINDOW_SPAN]):
        w = host[tr.WINDOW_SPAN]
        lo, hi = float(w[:, 0].min()), float(w[:, 1].max())
    else:
        lo = min(s for ops in per_dev for _, _, s, _ in ops)
        hi = max(e for ops in per_dev for _, _, _, e in ops)
    named = [(float(s), float(e), name) for name, iv in host.items()
             if name != tr.WINDOW_SPAN for s, e in iv if e > lo and s < hi]
    edges, labels = innermost(named)
    quiet_ns = lo + 1e9 * np.asarray(quiet, np.float64).reshape(-1, 2)
    tick = host.get("serve.tick", np.zeros((0, 2)))
    wait = host.get("serve.wait_arrival", np.zeros((0, 2)))
    loop = _minus(tr._union(tr._clip(tick, lo, hi)) if len(tick) else tick.reshape(0, 2), wait)

    n = len(per_dev)
    busy = 0.0
    by_op: Dict[str, float] = defaultdict(float)
    by_scope: Dict[str, float] = defaultdict(float)
    unscoped: Dict[str, float] = defaultdict(float)
    idle_split: Dict[Optional[str], float] = defaultdict(float)
    in_flight_idle = loop_idle = 0.0
    for ops in per_dev:
        for name, path, t in self_times(ops, lo, hi):
            by_op[name] += t
            sc = op_scope(path, scopes)
            by_scope[sc or UNSCOPED] += t
            if sc is None:
                unscoped[name] += t
        u = tr._union(np.asarray([(max(s, lo), min(e, hi)) for _, _, s, e in ops
                                  if min(e, hi) > max(s, lo)], np.float64).reshape(-1, 2))
        busy += float(np.sum(u[:, 1] - u[:, 0]))
        idle = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
        idle = idle[idle[:, 1] > idle[:, 0]]
        loop_idle += _intersect_len(idle, loop)
        long_gaps = idle[idle[:, 1] - idle[:, 0] >= tr.SHORT_GAP_NS]
        flying = _minus(long_gaps, quiet_ns)
        in_flight_idle += float(np.sum(flying[:, 1] - flying[:, 0])) if len(flying) else 0.0
        for label, t in _split(flying, edges, labels).items():
            idle_split[label] += t
    covered = in_flight_idle - idle_split.get(None, 0.0)
    gc_iv = host.get("py.gc", np.zeros((0, 2)))
    gc_iv = tr._clip(gc_iv, lo, hi) if len(gc_iv) else gc_iv.reshape(0, 2)

    def top_s(d: Dict, k: int = top) -> List[List]:
        return [[name, float(v) / n * 1e-9] for name, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:k]]

    return {
        "busy_s": busy / n * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "devices": n,
        "op_self_s": top_s(by_op),
        "scope_s": {k: float(v) / n * 1e-9 for k, v in by_scope.items()},
        "unscoped_ops": top_s(unscoped),
        "idle_in_flight_s": in_flight_idle / n * 1e-9,
        "idle_by_span": top_s({(UNCOVERED if k is None else k): v
                               for k, v in idle_split.items()}, len(idle_split)),
        "idle_covered_share": covered / in_flight_idle if in_flight_idle else None,
        "loop_idle_s": loop_idle / n * 1e-9,
        "gc_s": float(np.sum(gc_iv[:, 1] - gc_iv[:, 0])) * 1e-9,
        "gc_count": len(gc_iv),
    }
