"""A tiny cell on the CPU: the benchmark's whole run path at smoke size."""
from __future__ import annotations

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import run as R  # noqa: E402

DATA = HERE / "data"


def spec():
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    return {"bench": bench, "workload": {"name": "phi3-mini-3.8b.agent", "chips": 1},
            "config": json.loads((DATA / "tiny.json").read_text()),
            "traffic": json.loads((DATA / "tiny_agent.json").read_text()),
            "cell": json.loads((DATA / "tiny_cell.json").read_text())}


def run_tiny(seed: int, *, plant=None, control: bool = False, seconds: float = 3.0):
    """(result object, check) of one tiny run, the chip check skipped."""
    from bench import correct

    sp = spec()
    run = R.run_cell(sp, seed, seconds, False, require_chip=False, plant=plant)
    check = correct.check(sp["config"], sp["cell"], run["ctx"]["res"], seed, control=control)
    return R.finish(run, check), check
