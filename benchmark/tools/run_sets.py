#!/usr/bin/env python
"""Run one cell as the contract's measurement asks: sets of runs with the same
seeds in each set, every run a process of its own, then each metric's median
and spread (distance between the quartiles of ``statistics.quantiles(n=4)``
as a share of the median) per set. The bounds in BENCHMARK.json were set from
this tool's output (PERF.md). This parent never touches JAX: the chip belongs
to the run.

    python benchmark/tools/run_sets.py --workload <cell> --seeds 1,2,3,4,5,6 \\
        [--sets 2] [--traced-seed 7] [--out chiprun_out/<file>.jsonl]

The ``--out`` file also keeps, under ``said``, the last lines each run printed
before its result line.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one(workload, seed, seconds, trace):
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace)],
        capture_output=True, text=True, cwd=ROOT)
    lines = p.stdout.strip().splitlines()
    try:
        line = json.loads(lines[-1])
    except (IndexError, ValueError):
        line = None
    if p.returncode or line is None:
        print(p.stdout[-3000:], p.stderr[-3000:], sep="\n", flush=True)
    return p.returncode, line, lines[:-1]


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced-seed", type=int)
    ap.add_argument("--out")
    a = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    seeds = [int(s) for s in a.seeds.split(",")]
    out = open(os.path.join(ROOT, a.out), "a") if a.out else None
    summary = {}
    for k in range(a.sets):
        per_metric = {}
        for seed in seeds:
            code, line, earlier = one(a.workload, seed, seconds, 0)
            row = {"set": k, "seed": seed, "code": code, "line": line}
            print(json.dumps(row), flush=True)
            if out:
                # with what the run said before its line: a run that reads
                # far off says there where (its longest iterations)
                out.write(json.dumps(dict(row, said=earlier[-24:])) + "\n")
                out.flush()
            if line:
                for name, m in line["metrics"].items():
                    per_metric.setdefault(name, []).append(m["value"])
        summary[k] = {
            name: {"median": statistics.median(v), "spread": spread(v),
                   "n": len(v), "first": v[0]}
            for name, v in per_metric.items() if len(v) >= 2}
    print(json.dumps({"summary": summary}), flush=True)
    if a.traced_seed is not None:
        code, line, earlier = one(a.workload, a.traced_seed, seconds, 1)
        print("\n".join(earlier[-14:]))
        print(json.dumps({"traced": line, "code": code}), flush=True)
        if out:
            out.write(json.dumps({"traced": line}) + "\n")
    if out:
        out.close()


if __name__ == "__main__":
    main()
