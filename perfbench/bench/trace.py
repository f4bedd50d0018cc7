"""Reduction of a profiler trace to device busy time, top ops and idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` alone.  Device planes are those named
``/device:<PLATFORM>:<n>``; an operation is an event on their ``XLA Ops``
line (``XLA Modules`` where a plane has no op line).  Busy time is the union
of those intervals inside the window, averaged over the devices; idle gaps
are the stretches of the window with no operation, each named by the
benchmark's host span that covers most of it (``host code`` where none does),
or as time in which the server held no request.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

OP_LINES = ("XLA Ops", "XLA Modules")
WINDOW_SPAN = "bench.window"
#: gaps this short sit between the ops of one program; they are summed, not named
SHORT_GAP_NS = 20_000.0
SHORT_GAP = "between ops (<20 us)"
#: label of the stretches in which the server held no request
QUIET = "no request in flight"
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9][0-9,]*\]")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and name.count(":") == 2 and name.rsplit(":", 1)[1].isdigit()


def _short(name: str) -> str:
    """An op's or a program's name in a few words.  A TPU trace names an op
    event by its whole HLO instruction (``%while.3 = (s32[], bf16[8,1,3072]
    ...) while(...)``): that becomes the instruction's name and the first
    array shape of its result (``while.3 bf16[8,1,3072]``), which tells a
    decode rung from a prefill bucket.  A program (``jit_step(12)``) loses
    its id."""
    if " = " not in name:
        return name.split("(", 1)[0]
    head, result = name.split(" = ", 1)
    shape = _SHAPE.search(result)
    return head.lstrip("%") + (f" {shape.group(0)}" if shape else "")


def _device_ops(plane) -> List[Tuple[str, float, float]]:
    """(name, start, duration) of each op; the name is ``program/op`` where
    the plane's ``XLA Modules`` line has a program around the op."""
    lines = {ln.name: ln for ln in plane.lines}
    want = next((w for w in OP_LINES if w in lines), None)
    if want is None:
        return []
    mods = []
    if want == "XLA Ops" and "XLA Modules" in lines:
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns, _short(e.name))
                      for e in lines["XLA Modules"].events)
    starts = [m[0] for m in mods]
    out = []
    for e in lines[want].events:
        name = _short(e.name)
        i = bisect.bisect_right(starts, e.start_ns) - 1
        if i >= 0 and e.start_ns < mods[i][1]:
            name = f"{mods[i][2]}/{name}"
        out.append((name, e.start_ns, e.duration_ns))
    return out


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (start, end) rows into disjoint sorted intervals."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    end = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > end[:-1]
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    ends = np.maximum.reduceat(end, idx)
    return np.stack([starts, ends], 1)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def host_spans(planes: Iterable, names: Sequence[str]) -> Dict[str, np.ndarray]:
    """(start, end) intervals of the named host spans, by name."""
    out: Dict[str, list] = defaultdict(list)
    want = set(names) | {WINDOW_SPAN}
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name in want:
                    out[e.name].append((e.start_ns, e.start_ns + e.duration_ns))
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


def reduce(profile, span_names: Sequence[str] = (), top: int = 10,
           quiet: Sequence[Tuple[float, float]] = ()) -> Dict[str, object]:
    """``busy_s``, ``window_s``, ``device_ops`` and ``idle_gaps`` of a trace.

    ``profile`` is a ``ProfileData`` or a path to an ``.xplane.pb``.  The
    window is the benchmark's ``bench.window`` host span where the trace
    has one, else the extent of the device operations.  ``quiet`` holds the
    stretches, in seconds from the window's start, in which the server held
    no request; they name the idle gaps they cover like a host span.
    """
    if isinstance(profile, str):
        from jax.profiler import ProfileData

        profile = ProfileData.from_file(profile)
    planes = list(profile.planes)
    spans = host_spans(planes, span_names)
    devices = [p for p in planes if _is_device(p.name)]
    per_dev = [_device_ops(p) for p in devices]
    per_dev = [ops for ops in per_dev if ops]
    if not per_dev:
        return {"busy_s": 0.0, "window_s": 0.0, "devices": 0,
                "device_ops": [], "idle_gaps": []}
    if WINDOW_SPAN in spans and len(spans[WINDOW_SPAN]):
        w = spans[WINDOW_SPAN]
        lo, hi = float(w[:, 0].min()), float(w[:, 1].max())
    else:
        allv = np.concatenate([np.asarray([(s, s + d) for _, s, d in ops]) for ops in per_dev])
        lo, hi = float(allv[:, 0].min()), float(allv[:, 1].max())
    if len(quiet):
        spans[QUIET] = lo + 1e9 * np.asarray(quiet, np.float64).reshape(-1, 2)
    busy, by_name, gaps = [], defaultdict(float), []
    for ops in per_dev:
        iv = np.asarray([(s, s + d) for _, s, d in ops], np.float64)
        u = _union(_clip(iv, lo, hi))
        busy.append(float(np.sum(u[:, 1] - u[:, 0])))
        for (name, s, d) in ops:
            if s >= lo and s + d <= hi:
                by_name[name] += d
        edges = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
        gaps.extend(g for g in edges if g[1] > g[0])
    n = len(per_dev)
    labelled: Dict[str, float] = defaultdict(float)
    longest: List[Tuple[float, str]] = []
    for g0, g1 in gaps:
        if g1 - g0 < SHORT_GAP_NS:
            labelled[SHORT_GAP] += (g1 - g0) / n
            continue
        label, best = "host code", 0.0
        for name, iv in spans.items():
            if name == WINDOW_SPAN or not len(iv):
                continue
            cover = np.clip(np.minimum(iv[:, 1], g1) - np.maximum(iv[:, 0], g0), 0, None).sum()
            if cover > best:
                label, best = name, cover
        labelled[label] += (g1 - g0) / n
        longest.append(((g1 - g0) / n, label))
    longest.sort(reverse=True)
    idle = sorted(([k, v * 1e-9] for k, v in labelled.items()), key=lambda kv: -kv[1])
    idle += [[f"longest gap: {lab}", s * 1e-9] for s, lab in longest[:3]]
    ops_top = sorted(([k, v / n * 1e-9] for k, v in by_name.items()), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy) / n * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "devices": n,
        "device_ops": ops_top[:top],
        "idle_gaps": idle[:top],
    }


def idle_share(red: Dict[str, object]) -> Optional[float]:
    w = float(red.get("window_s") or 0.0)
    return None if w <= 0 or not red.get("devices") else 1.0 - float(red["busy_s"]) / w
