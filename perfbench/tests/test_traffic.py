"""The traffic generator: same sizes for every seed, prompts built as stated."""
from __future__ import annotations

import json
from collections import Counter

import numpy as np
import pytest

import tiny
from bench import traffic

TRAFFIC = tiny.HERE.parent / "traffic"


def load(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def sizes(turns):
    return Counter((len(t.prompt), t.max_new) for t in turns)


@pytest.mark.parametrize("seed", [1, 2**31 + 77])
def test_agent_sessions(seed):
    tr = load("agent")
    turns, prefixes = traffic.generate(tr, {"sessions_per_s": 1.0}, seed=seed, seconds=40,
                                       max_len=1024, vocab=32064)
    assert len(prefixes) == 4 and all(384 <= len(p) <= 768 for p in prefixes)
    assert turns and all(0 <= t.due_s < 40 for t in turns)
    assert all(len(t.prompt) + t.max_new <= 1024 for t in turns)
    assert all(8 <= t.max_new <= 256 for t in turns)
    by_session = {}
    for t in turns:
        by_session.setdefault(t.session, []).append(t)
    for ts in by_session.values():
        ts.sort(key=lambda t: t.turn)
        assert np.array_equal(ts[0].prompt[: len(prefixes[ts[0].prefix])], prefixes[ts[0].prefix])
        for a, b in zip(ts, ts[1:]):
            # each turn replays the previous prompt verbatim, then its answer
            assert np.array_equal(b.prompt[: len(a.prompt)], a.prompt)
            assert len(b.prompt) > len(a.prompt) + a.max_new
            assert b.due_s > a.due_s


def test_agent_seeds_share_sizes_not_tokens():
    tr = load("agent")
    a, _ = traffic.generate(tr, {"sessions_per_s": 1.0}, seed=5, seconds=200,
                            max_len=1024, vocab=32064)
    b, _ = traffic.generate(tr, {"sessions_per_s": 1.0}, seed=6, seconds=200,
                            max_len=1024, vocab=32064)
    # the same schedule: sizes, due times and order; only the token ids differ
    assert [(len(t.prompt), t.max_new, t.due_s, t.session, t.turn) for t in a] == \
        [(len(t.prompt), t.max_new, t.due_s, t.session, t.turn) for t in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)


def test_batch_job_fills_the_window():
    tr = load("batch")
    mean = traffic.mean_output_tokens(tr)
    assert mean == pytest.approx(384, abs=5)
    turns, prefixes = traffic.generate(tr, {"tok_s": 200.0}, seed=3, seconds=40,
                                       max_len=1024, vocab=102400)
    assert prefixes == []
    assert len(turns) == round(200.0 * 40 / mean)
    assert all(t.due_s == 0.0 for t in turns)
    assert all(64 <= len(t.prompt) <= 512 and 256 <= t.max_new <= 512 for t in turns)
    again, _ = traffic.generate(tr, {"tok_s": 200.0}, seed=4, seconds=40,
                                max_len=1024, vocab=102400)
    assert sizes(again) == sizes(turns)
