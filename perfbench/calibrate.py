"""Readings that set a cell's load and its correctness limit, in one process.

    python3 perfbench/calibrate.py --workload <cell> --seconds <s> --seeds 1,2,3 \
        [--loads 0.5,1.0,1.5] [--control 1] [--out chiprun_out/<file>.jsonl]

Set-up (weights, warm-up) is paid once; every seed then gets new weights and
an empty page pool on the same compiled programs, and every load in
``--loads`` (sessions/s for an open loop, tokens/s for a closed job) is
served for ``--seconds``.  Per window it prints the end-to-end numbers and
how the queue grew (the knee sweep).  After the last window the program is
freed and the sample of each seed's first window goes through the reference:
the program's widest logit gap (the lower reading) and, with ``--control 1``,
the widest gap of the fp8 control (the upper reading).  The benchmark's own
runs never run the control.  Needs the chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import gc
import json
import time

import run as R  # sets the compile cache and the import path  # noqa: I001

import numpy as np


def queue_growth(res, reqs) -> float:
    """Median TTFT of the last third of arrivals over that of the first third."""
    order = sorted(reqs, key=lambda r: r.arrival_s)
    k = max(1, len(order) // 3)
    t = lambda rs: np.median([res["results"][r.rid]["ttft_s"] for r in rs  # noqa: E731
                              if res["results"][r.rid].get("ttft_s") is not None])
    return float(t(order[-k:]) / max(t(order[:k]), 1e-9))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--loads", default="")
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    loads = [float(x) for x in args.loads.split(",")] if args.loads else [None]
    spec = R.load_spec(args.workload)

    import jax

    from bench import correct, program
    from bench import traffic as gen

    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec, default=float)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    dev = R.require_chips(int(spec["workload"]["chips"]))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cfg, cellspec = spec["config"], spec["cell"]
    ref = correct.load_reference(cfg["reference"])
    t0 = time.perf_counter()
    cell = None
    samples = []
    key = "sessions_per_s" if "sessions_per_s" in cellspec["load"] else "tok_s"
    for seed in seeds:
        if cell is not None:
            cell.srv.params = None
            gc.collect()
        params = jax.block_until_ready(program.program_params(cfg, seed, ref))
        if cell is None:
            cell = program.Cell(cfg, cellspec, params)
            cell.warm()
            emit({"setup_s": time.perf_counter() - t0, "device": dev.device_kind})
        else:
            cell.reset(params)
        del params
        for i, load in enumerate(loads):
            ld = dict(cellspec["load"])
            if load is not None:
                ld[key] = load
            turns, prefixes = gen.generate(spec["traffic"], ld, seed=seed, seconds=args.seconds,
                                           max_len=cell.max_len, vocab=cfg["vocab_size"])
            cell.hold_prefixes(prefixes)
            res = cell.run(turns, trace=False)
            e2e = R.end_to_end(res, 0.0, program.peak_bytes(dev))
            reqs = res["requests"]
            emit({"seed": seed, "load": ld, "requests": len(reqs),
                  "failed": sum("error" in res["results"][r.rid] for r in reqs),
                  "wall_s": res["wall_s"], "real_tokens": res["real_tokens"],
                  "drain_s": res["wall_s"] - max(r.arrival_s for r in reqs),
                  "queue_growth": queue_growth(res, reqs), "deferrals": res["deferrals"],
                  "kv_peak_pages": res["kv_peak_pages_in_use"], "swaps": res["swaps"],
                  "counters": res["window_counters"], **e2e})
            if i == 0:
                picks = correct.sample(res, seed, int(cellspec["check"]["min_tokens"]))
                by_rid = {r.rid: r for r in reqs}
                samples.append((seed, [np.asarray(by_rid[r].prompt) for r in picks],
                                [np.asarray(res["results"][r]["tokens"]) for r in picks]))
    emit({"peak_hbm_gb": program.peak_bytes(dev) / 1e9})
    cell.close()
    del cell
    gc.collect()
    for seed, prompts, served in samples:
        t = time.perf_counter()
        g = correct.reference_gaps(cfg, seed, prompts, served, control=bool(args.control))
        rec = {"seed": seed, "tokens": int(sum(len(s) for s in served)),
               "max_logit_gap": float(np.max(g["gap"])),
               "gap_p99": float(np.percentile(g["gap"], 99)),
               "gaps_over_0.1": int(np.sum(g["gap"] > 0.1)), "ref_s": time.perf_counter() - t}
        if args.control:
            rec["control_max_logit_gap"] = float(np.max(g["control_gap"]))
            rec["control_gap_p99"] = float(np.percentile(g["control_gap"], 99))
        emit(rec)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
