#!/usr/bin/env python
"""Read, in one process on the chip and at the cell's own size, the numbers
``correct`` compares: for each seed what the sound program gives, and for the
control seeds what the reference gives when it is computed in fp8's precision
in the program's place. The limits in ``workloads/<cell>.json`` are set from
these readings (PERF.md lists them); the benchmark's own runs never run this.

    python benchmark/tools/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3 [--seconds 25]
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import registry, runtime  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=25.0)
    a = ap.parse_args()
    seeds = [int(s) for s in a.seeds.split(",")]
    control = {int(s) for s in a.control_seeds.split(",") if s}

    import jax

    bench_run.compile_cache_dir()
    bench = registry.load_benchmark()
    cell = registry.cell_entry(bench, a.workload)
    workload = registry.load_json("workloads", cell["name"])
    devices = jax.devices()[:cell["chips"]]
    run = runtime.Run(
        t_process=time.perf_counter(),
        args=argparse.Namespace(seed=seeds[0], seconds=a.seconds, trace=0),
        cell=cell, workload=workload,
        config=registry.load_config(bench, cell["config"]),
        peaks=registry.load_peaks(devices[0].device_kind), devices=devices,
        scratch=os.path.join(ROOT, ".bench_scratch"))
    driver = registry.load_module("drivers", workload["driver"])
    for reading in driver.calibrate(run, seeds, control):
        print(json.dumps(reading), flush=True)


if __name__ == "__main__":
    main()
