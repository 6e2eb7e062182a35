"""Tools that fix a cell's rate, size, limits and bounds on the chip.

    python bench/tools.py sweep --workload fem_mesh.serve --rates 2,4,6 \
        --seconds 20 --seed 5 [--set config.graph.side=96]
        One run per offered rate, ascending until one does not hold:
        latency, and whether the backlog grew
        (the mean latency of the last third of arrivals against the
        first).  The last line gives the knee, the highest rate with no
        failure whose backlog does not grow (last third at most 1.25x the
        first), and 4/5 of it.  ``--set`` changes one key of the cell's
        configuration or traffic for this process only.
    python bench/tools.py control --workload fem_mesh.serve \
        --seeds 1,2,3 --seconds 30 [--control]
        Runs in one process (set-up once per run, programs cached), each
        printing its compared numbers; ``--control`` runs the program's
        own lower-precision path (no float64 refinement).
    python bench/tools.py spreads LOG [LOG ...]
        Reads result lines (the last stdout line of each run) from logs
        holding two sets of runs in order, and prints each metric's
        quartile spread per set and the bound five times the wider one
        would give.  Needs no chip.

Each line printed is one JSON object.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import run as bench_run
from benchkit import spec, stats


def _override(cell, settings):
    """Apply ``config.a.b=VALUE`` / ``traffic.a=VALUE`` (VALUE as JSON)."""
    for item in settings or ():
        key, value = item.split("=", 1)
        path = key.split(".")
        node = {"config": cell.config, "traffic": cell.traffic}[path[0]]
        for part in path[1:-1]:
            node = node[part]
        node[path[-1]] = json.loads(value)


def _setup(args):
    cell = spec.resolve(args.workload)
    _override(cell, args.set)
    bench_run.configure_jax(bench_run.os.path.join(bench_run.CACHE, "jax"))
    device, reason = bench_run.find_device(cell.chips)
    if device is None:
        sys.exit(f"tools: {reason}")
    from benchkit.roofline import peaks

    return cell, device, peaks(device["kind"])


def sweep(args):
    cell, device, peaks = _setup(args)
    held = []
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic["rate_hz"] = rate
        run, _, _ = bench_run.measure(cell, args.seed, args.seconds, False,
                                      peaks, t_start=time.perf_counter())
        lat = run.ctx["latencies"]
        third = max(1, len(lat) // 3)
        first, last = sum(lat[:third]) / third, sum(lat[-third:]) / third
        ok = run.failed == 0 and run.correct and last <= 1.25 * first
        if ok:
            held.append(rate)
        print(json.dumps({
            "rate_hz": rate, "requests": run.attempted,
            "failed": run.failed, "correct": run.correct,
            "p50_ms": stats.percentile(lat, 50),
            "p95_ms": stats.percentile(lat, 95),
            "first_third_mean_ms": first, "last_third_mean_ms": last,
            "solves_per_s": run.e2e["solves_per_s"],
            "compared": run.compared,
            "setup_s": run.setup_s, "notes": run.notes}), flush=True)
        if not ok:
            break       # rates ascend: past the knee
    knee = max(held, default=None)
    print(json.dumps({"knee_hz": knee,
                      "four_fifths_hz": 0.8 * knee if knee else None}),
          flush=True)


def control(args):
    cell, device, peaks = _setup(args)
    kwargs = {"max_refine": 0} if args.control else None
    for seed in [int(s) for s in args.seeds.split(",")]:
        run, metrics, _ = bench_run.measure(
            cell, seed, args.seconds, False, peaks,
            t_start=time.perf_counter(), service_kwargs=kwargs)
        print(json.dumps({"seed": seed, "control": bool(args.control),
                          "correct": run.correct, "attempted": run.attempted,
                          "failed": run.failed, "compared": run.compared,
                          "metrics": metrics, "notes": run.notes}),
              flush=True)


def spreads(args):
    runs = []
    for path in args.logs:
        with open(path) as f:
            for line in f:
                if line.startswith("{") and '"metrics"' in line:
                    runs.append(json.loads(line))
    half = len(runs) // 2
    sets = [runs[:half], runs[half:]]
    for name in sorted({k for r in runs for k in r["metrics"]}):
        vals = [[r["metrics"][name]["value"] for r in s
                 if name in r["metrics"]] for s in sets]
        sp = [stats.spread(v) if len(v) >= 2 else None for v in vals]
        med = [statistics.median(v) if v else None for v in vals]
        known = [x for x in sp if x is not None]
        print(json.dumps({"metric": name, "values": vals, "medians": med,
                          "spreads": sp,
                          "bound_5x": 5 * max(known) if known else None,
                          "correct": [r["correct"] for r in runs]}))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="tool", required=True)
    p = sub.add_parser("sweep")
    p.add_argument("--rates", required=True)
    p.add_argument("--seed", type=int, default=5)
    p = sub.add_parser("control")
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", action="store_true")
    for p in sub.choices.values():
        p.add_argument("--workload", required=True)
        p.add_argument("--seconds", type=float, required=True)
        p.add_argument("--set", action="append", metavar="KEY=VALUE")
    p = sub.add_parser("spreads")
    p.add_argument("logs", nargs="+")
    args = ap.parse_args()
    {"sweep": sweep, "control": control, "spreads": spreads}[args.tool](args)


if __name__ == "__main__":
    main()
