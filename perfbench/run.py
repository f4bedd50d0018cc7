"""Chip benchmark of the Forge serve path: one run of one cell.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell is an entry of ``BENCHMARK.json``;
its configuration, traffic mix and server options are the files named after
it (``perfbench/configs/<config>.json``, ``perfbench/traffic/<traffic>.json``,
``perfbench/cells/<cell>.json``); the configuration names its plain
reference module (``perfbench/reference/<reference>.py``), which also
states the program's config, the weights' layout and the counted work; and
each per-layer metric is read by ``perfbench/metrics/<metric>.py``.  A run
makes its weights and requests from ``--seed``, warms every program the
traffic can reach (set-up), serves the requests in an open loop for
``--seconds`` plus the drain, reads peak device memory, frees the program
and compares a sample of the served tokens with the plain reference.  The
last line of standard output is one JSON object; the last lines of
standard error are the numbers compared, each with its limit.  Without a
TPU, or with fewer chips than the cell asks for, it exits 1 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# JAX's persistent compilation cache lives at one fixed path in the checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
# no size limit, so no eviction: an entry another writer left without its
# access-time file would otherwise make every write of this run fail
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for p in (str(ROOT / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

SPANS = ("forge.decode", "forge.prefill", "sched.admit")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> Dict:
    return json.loads(path.read_text())


def load_spec(workload: str) -> Dict:
    """The cell's entry, configuration, traffic and server files, by name."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return {
        "bench": bench,
        "workload": w,
        "config": load_json(ROOT / conf["file"]),
        "traffic": load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        "cell": load_json(HERE / "cells" / f"{workload}.json"),
    }


def require_chips(n: int):
    """The first device, when JAX finds ``n`` or more TPU chips; else exit 1."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        log(f"perfbench: needs {n} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform!r} device(s). No CPU fallback.")
        raise SystemExit(1)
    return devs[0]


def percentile(xs: List[float], q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else None


def in_flight(res: Dict) -> List[List[float]]:
    """Disjoint stretches, in seconds from the window's start, in which the
    server held at least one request (from its due arrival to its end)."""
    out: List[List[float]] = []
    for a, b in sorted((r.arrival_s, r.arrival_s + res["results"][r.rid]["latency_s"])
                       for r in res["requests"]
                       if res["results"][r.rid].get("latency_s") is not None):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def quiet(busy: List[List[float]], end: float) -> List[List[float]]:
    """The stretches of ``[0, end]`` outside ``busy``."""
    edges = [0.0] + [t for iv in busy for t in iv] + [end]
    return [[a, b] for a, b in zip(edges[::2], edges[1::2]) if b > a]


def end_to_end(res: Dict, setup_s: float, peak: Optional[int]) -> Dict[str, float]:
    results = res["results"].values()
    ttft = [r["ttft_s"] for r in results if r.get("ttft_s") is not None]
    tpot = [1e3 * (r["latency_s"] - r["ttft_s"]) / (len(r["tokens"]) - 1)
            for r in results
            if "error" not in r and r.get("ttft_s") is not None and len(r["tokens"]) > 1]
    return {
        "ttft_p90_s": percentile(ttft, 90),
        "tpot_p90_ms": percentile(tpot, 90),
        "output_tok_s": res["real_tokens"] / res["wall_s"],
        "peak_hbm_gb": None if peak is None else peak / 1e9,
        "setup_s": setup_s,
    }


def reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"perfbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: Dict, workload: str, trace: bool) -> List[Dict]:
    e2e = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload] if m["moves"] in reported else [])]


def run_cell(spec: Dict, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, t_start: float = T_START,
             plant=None) -> Dict:
    """One run; returns the result object and the numbers compared.

    ``plant`` (tests only) is called with the cell after set-up, to break
    the timed path underneath.
    """
    import jax

    from bench import correct, flops, program, spans
    from bench import trace as tr
    from bench import traffic as gen

    w, cfg, cellspec = spec["workload"], spec["config"], spec["cell"]
    if require_chip:
        dev = require_chips(int(w["chips"]))
    else:
        dev = jax.devices()[0]
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = {"window": 0, "on": False, "names": []}

    def on_event(event, duration, **kw):
        if event == BACKEND_COMPILE and compiles["on"]:
            compiles["window"] += 1
            compiles["names"].append(str(kw.get("fun_name")))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    full_gc: List[float] = []  # seconds of each full collection in the window
    gc_start = [0.0]

    def on_gc(phase, info):
        if info["generation"] == 2 and compiles["on"]:
            if phase == "start":
                gc_start[0] = time.perf_counter()
            else:
                full_gc.append(time.perf_counter() - gc_start[0])

    gc.callbacks.append(on_gc)

    ref = correct.load_reference(cfg["reference"])
    params = jax.block_until_ready(program.program_params(cfg, seed, ref))
    cell = program.Cell(cfg, cellspec, params)
    del params
    cell.warm()
    turns, prefixes = gen.generate(spec["traffic"], cellspec["load"], seed=seed,
                                   seconds=seconds, max_len=cell.max_len,
                                   vocab=cfg["vocab_size"])
    cell.hold_prefixes(prefixes)
    if plant is not None:
        plant(cell)
    jax.effects_barrier()

    log_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    compiles["on"] = True
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        res = cell.run(turns, trace=trace)
    compiles["on"] = False
    gc.callbacks.remove(on_gc)
    flight = in_flight(res)
    reduction = split = None
    trace_cost: Dict[str, float] = {}  # seconds after the window, by step
    if trace:
        t = time.perf_counter()
        jax.profiler.stop_trace()
        trace_cost["stop_s"] = time.perf_counter() - t
        xplane, idle = tr.find_xplane(log_dir), quiet(flight, res["wall_s"])
        t = time.perf_counter()
        reduction = tr.reduce(xplane, SPANS, quiet=idle)
        trace_cost["trace_reduce_s"] = time.perf_counter() - t
        t = time.perf_counter()
        split = spans.reduce(xplane, quiet=idle)
        trace_cost["spans_reduce_s"] = time.perf_counter() - t
        import shutil

        shutil.rmtree(log_dir, ignore_errors=True)
    peak = program.peak_bytes(dev)
    e2e = end_to_end(res, setup_s, peak)

    # per-layer context: counters, counted work, trace and the program's
    # span and scope split of it, set-up time
    reqs = res["requests"]
    per_req = [(len(r.prompt), res["skips"][r.rid], len(res["results"][r.rid]["tokens"]))
               for r in reqs if "error" not in res["results"][r.rid]]
    work = flops.window_work(ref.counted_work(cfg), per_req, res["prefill_dispatches"],
                             res["decode_dispatches"])
    pk = flops.peaks(dev.device_kind) if require_chip else None
    in_flight_s = sum(b - a for a, b in flight)
    ctx = {"res": res, "counters": res["window_counters"], "trace": reduction,
           "spans": split, "setup_s": setup_s, "work": work, "peak": pk,
           "in_flight_s": in_flight_s,
           "roofline_s": flops.roofline_seconds(work, pk) if pk else None}

    failed = sum("error" in res["results"][r.rid] for r in reqs)
    diag = {k: res[k] for k in ("wall_s", "real_tokens", "decode_dispatches",
                                "prefill_dispatches", "swaps", "resizes", "deferrals",
                                "compiles", "kv_peak_pages_in_use", "kv_pages_capacity")}
    diag.update(window_compiles=compiles["window"], window_compiled=compiles["names"][:10],
                window_full_gc_s=full_gc, trace_cost=trace_cost,
                counters=res["window_counters"],
                prefilled_by_count=sum(p - s for p, s, _ in per_req), in_flight_s=in_flight_s,
                work=work, e2e=e2e)
    if reduction:
        diag["trace"] = {k: reduction[k] for k in ("busy_s", "window_s", "devices")}
    if split:
        diag["spans"] = {k: split[k] for k in ("scope_s", "idle_by_span", "loop_idle_s",
                                               "idle_covered_share", "gc_count")}

    # free the program before the reference runs
    cell.close()
    del cell
    gc.collect()
    return {"spec": spec, "seed": seed, "trace": trace, "dev": dev, "e2e": e2e, "ctx": ctx,
            "reduction": reduction, "failed": failed, "attempted": len(reqs), "diag": diag,
            "peak": peak}


def finish(run: Dict, check: Dict) -> Dict:
    """The result object of a run, and the compared numbers on stderr."""
    import jax

    spec, w = run["spec"], run["spec"]["workload"]
    limit = spec["cell"]["check"]["limits"]["max_logit_gap"]
    gap = check.get("max_logit_gap")
    checks = {
        "failed_requests": {"value": run["failed"], "limit": 0},
        "max_logit_gap": {"value": gap, "limit": limit},
    }
    correct = (run["failed"] == 0 and gap is not None and gap <= limit
               and check.get("tokens", 0) > 0)
    metrics = {}
    for m in cell_metrics(spec["bench"], w["name"], run["trace"]):
        if run["trace"]:
            v = reader(m["name"])(run["ctx"])
        else:
            v = run["e2e"].get(m["name"])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = run["dev"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": run["peak"]}
    out = {"correct": bool(correct), "attempted": run["attempted"], "failed": run["failed"],
           "metrics": metrics, "device": device}
    red = run["reduction"]
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
    run["diag"]["check"] = check
    log("perfbench diag " + json.dumps(run["diag"], default=float))
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec(args.workload)

    from bench import correct

    run = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    t_ref = time.perf_counter()
    check = correct.check(spec["config"], spec["cell"], run["ctx"]["res"], args.seed)
    check["ref_s"] = time.perf_counter() - t_ref
    print(json.dumps(finish(run, check)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
