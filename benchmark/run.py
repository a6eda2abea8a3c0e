#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line last.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data found by name (harness/registry.py): this file
has no branch on a workload, a configuration or a metric. It refuses to
measure off a TPU or on fewer chips than the cell asks for (non-zero exit, no
result line), and it never prints a line that harness/line.py rejects.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import line as line_mod  # noqa: E402
from benchmark.harness import registry, runtime  # noqa: E402

EXIT_NO_CHIP = 2
EXIT_BAD_LINE = 3
EXIT_FAILED = 4


def say(*a):
    print(*a, flush=True)


def compile_cache_dir():
    """JAX's persistent cache, at a fixed place inside the checkout unless
    the environment already names one (JAX reads that itself)."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def read_metrics(declared, facts):
    values = {}
    for m in declared:
        reader = registry.load_module("metrics", m["name"])
        values[m["name"]] = reader.read(facts)
    return values


def measure(args, bench, cell, workload, config, devices, peaks):
    """Everything after the look for a chip: drive the cell's driver, read
    its metrics, build the line. Returns (exit code, line or None). Tests
    call this on the CPU with toy sizes in ``workload`` and ``config``."""
    declared = line_mod.declared(bench, cell["name"], args.trace)
    driver = registry.load_module("drivers", workload["driver"])
    run = runtime.Run(t_process=T_PROCESS, args=args, cell=cell,
                      workload=workload, config=config, peaks=peaks,
                      devices=devices,
                      scratch=os.path.join(ROOT, ".bench_scratch"))
    outcome = driver.run(run)       # set-up, window, trace, comparison
    facts = outcome["facts"]
    facts["setup_s"] = run.setup_s
    for c in outcome["checks"]:
        say(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['ok'] else 'NOT OK'}")
    correct = all(c["ok"] for c in outcome["checks"])
    say("setup phases: " + ", ".join(
        f"{n} {e - s:.1f} s" for n, s, e in run.spans.rows
        if n.startswith("setup.")))
    say(f"setup_s {run.setup_s:.3f} reference_s {run.reference_s:.3f} "
        f"window_s {facts['window_s']:.3f} programs_lowered_in_window "
        f"{run.compiles_in_window()}")

    device = dict(runtime.device_description(devices),
                  memory_peak_bytes=outcome["memory_peak_bytes"])
    breakdown = None
    if args.trace:
        for name, runs in sorted(run.trace["module_runs_s"].items()):
            if sum(runs) > 0.01 * run.trace["busy_s"]:
                say(f"trace module {name}: runs {len(runs)} median "
                    f"{sorted(runs)[len(runs) // 2]:.6f} s, in the window "
                    f"{run.trace['module_busy_s'][name]:.6f} s")
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        breakdown = {"device_ops": run.trace["device_ops"],
                     "idle_gaps": run.trace["idle_gaps"]}
    try:
        text = line_mod.build(
            correct=correct, attempted=outcome["attempted"],
            failed=outcome["failed"], values=read_metrics(declared, facts),
            metrics_declared=declared, device=device, trace=bool(args.trace),
            breakdown=breakdown, checks=outcome["checks"])
    except line_mod.LineError as e:
        say(f"benchmark: no result line: {e}")
        return EXIT_BAD_LINE, None
    # every number compared beside its limit: the last lines on standard
    # error, as under the line's last key
    for c in outcome["checks"]:
        print(f"compared {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'NOT OK'}", file=sys.stderr)
    return 0, text


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = registry.load_benchmark()
    cell = registry.cell_entry(bench, args.workload)
    workload = registry.load_json("workloads", cell["name"])
    config = registry.load_config(bench, cell["config"])

    import jax

    cache = compile_cache_dir()
    devices = jax.devices()
    found = runtime.device_description(devices)
    if found["platform"] != "tpu" or len(devices) < cell["chips"]:
        print(f"benchmark: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX found platform {found['platform']!r} "
              f"({found['kind']}) with {len(devices)} device(s)",
              file=sys.stderr)
        return EXIT_NO_CHIP
    devices = devices[:cell["chips"]]
    peaks = registry.load_peaks(devices[0].device_kind)
    say(f"cell {cell['name']} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace} devices {len(devices)} x "
        f"{devices[0].device_kind} compile_cache {cache}")
    code, text = measure(args, bench, cell, workload, config, devices, peaks)
    if text is not None:
        sys.stderr.flush()
        say(text)
    return code


if __name__ == "__main__":
    try:
        code = main()
    except Exception:
        traceback.print_exc()
        print("benchmark: the run failed; no result line", flush=True)
        code = EXIT_FAILED
    sys.stdout.flush()
    sys.exit(code)
