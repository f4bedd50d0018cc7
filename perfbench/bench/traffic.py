"""The one traffic generator: reads a traffic file and a cell's load, returns requests.

A traffic file (``perfbench/traffic/<name>.json``) holds parameters only:

* ``shared_prefixes`` — ``count`` system/tool prompts, their ``tokens`` (a
  distribution), and ``zipf_s`` for how often each is chosen (count 0: none);
* ``turns`` — turns per session (a distribution of whole numbers);
* ``user_tokens`` / ``output_tokens`` — new prompt text and output budget per turn;
* ``think_s`` — seconds from one turn's due time to the next turn's;
* ``arrivals.process`` — ``poisson`` (sessions at the cell's ``sessions_per_s``)
  or ``at_once`` (a closed job: enough sessions, all due at 0, to fill
  ``--seconds`` at the cell's recorded ``tok_s``).

Every seed gets the same schedule: the sizes, due times and order come from
the file's ``shape_seed``, and ``--seed`` draws every token id (and, in the
harness, the weights).  So runs with different seeds do the same work at the
same times, on different data.  A turn's prompt is the
shared prefix, then the session's earlier turns verbatim (user text and the
answer drawn at the length that turn asked for), then new user text.  A
session ends before prompt + output budget would pass ``max_len``; turns due
at or after ``seconds`` are not sent.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np


@dataclass
class Turn:
    session: int
    turn: int
    due_s: float
    prompt: np.ndarray  # int32
    max_new: int
    prefix: int  # index of the shared prefix, -1 for none


def _draw(rng: np.random.Generator, spec: Dict, n: Optional[int] = None):
    kind = spec["dist"]
    if kind == "uniform":
        out = rng.integers(spec["low"], spec["high"] + 1, size=n)
    elif kind == "lognormal":
        out = np.rint(rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n))
        out = np.clip(out, spec["low"], spec["high"]).astype(np.int64)
    elif kind == "exponential":
        out = rng.exponential(spec["mean"], size=n)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return out


def _session_shapes(traffic: Dict, rng: np.random.Generator, n: int, max_len: int):
    """Per session: (prefix index, prefix length, [(user, output, think_s), ...])."""
    sp = traffic.get("shared_prefixes", {"count": 0})
    n_pre = int(sp.get("count", 0))
    pre_len = [int(x) for x in _draw(rng, sp["tokens"], n_pre)] if n_pre else []
    zipf = None
    if n_pre:
        w = 1.0 / np.arange(1, n_pre + 1) ** float(sp.get("zipf_s", 1.0))
        zipf = w / w.sum()
    shapes = []
    for _ in range(n):
        p = int(rng.choice(n_pre, p=zipf)) if n_pre else -1
        n_turns = int(_draw(rng, traffic["turns"]))
        turns = []
        length = pre_len[p] if p >= 0 else 0
        for _t in range(n_turns):
            user = int(_draw(rng, traffic["user_tokens"]))
            out = int(_draw(rng, traffic["output_tokens"]))
            think = float(_draw(rng, traffic["think_s"])) if "think_s" in traffic else 0.0
            if length + user + out > max_len:
                break
            turns.append((user, out, think))
            length += user + out
        shapes.append((p, turns))
    return pre_len, shapes


def mean_output_tokens(traffic: Dict) -> float:
    """Mean output budget per request, from the file's own size stream."""
    rng = np.random.default_rng(int(traffic["shape_seed"]) + 1)
    return float(np.mean(_draw(rng, traffic["output_tokens"], 4096)))


def generate(traffic: Dict, load: Dict, *, seed: int, seconds: float,
             max_len: int, vocab: int):
    """``(turns, prefixes)``: the request list of one run, sorted by due
    time, and the shared prompts a running server would already hold."""
    # two streams, so that session k has the same shape at every rate and a
    # higher rate runs the same sessions closer together (a knee sweep)
    shape_seed = int(traffic["shape_seed"])
    process = traffic["arrivals"]["process"]
    if process == "poisson":
        gap_rng = np.random.default_rng([shape_seed, 0])
        rate = float(load["sessions_per_s"])
        gaps: List[float] = []
        while sum(gaps) < seconds:
            gaps.append(float(gap_rng.exponential()) / rate)
        gaps = gaps[:-1]  # the kept gaps sum to less than ``seconds``
        n = len(gaps)
    elif process == "at_once":
        n = max(1, int(round(float(load["tok_s"]) * seconds / mean_output_tokens(traffic))))
        gaps = [0.0] * n
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    pre_len, shapes = _session_shapes(traffic, np.random.default_rng([shape_seed, 1]),
                                      n, max_len)

    run = np.random.default_rng(int(seed) % 2**64)
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) if n else []
    prefixes = [run.integers(0, vocab, L, dtype=np.int64).astype(np.int32) for L in pre_len]

    turns: List[Turn] = []
    for j, (p, shp) in enumerate(shapes):
        history = [prefixes[p]] if p >= 0 else []
        due = float(starts[j])
        for t, (user, out, think) in enumerate(shp):
            if t:
                due += shp[t - 1][2]
            text = run.integers(0, vocab, user, dtype=np.int64).astype(np.int32)
            answer = run.integers(0, vocab, out, dtype=np.int64).astype(np.int32)
            if due >= seconds and process == "poisson":
                break
            history.append(text)
            turns.append(Turn(session=int(j), turn=t, due_s=due,
                              prompt=np.concatenate(history).astype(np.int32),
                              max_new=out, prefix=p))
            history.append(answer)
    turns.sort(key=lambda r: (r.due_s, r.session, r.turn))
    return turns, prefixes
