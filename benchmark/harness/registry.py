"""Finding a cell's files by the names ``BENCHMARK.json`` gives.

A configuration is ``configs/<config>.json``, a cell's traffic and limits
``workloads/<cell>.json``, its driver ``drivers/<driver>.py``, a metric's
reader ``metrics/<metric>.py``, a count ``counts/<name>.py``: a later PR adds
files and entries and edits nothing here.
"""
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class RegistryError(LookupError):
    """A name in BENCHMARK.json has no file, or a file no such name."""


def load_benchmark(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_entry(bench, name):
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise RegistryError(
        f"no workload {name!r} in BENCHMARK.json; it has "
        f"{[w['name'] for w in bench['workloads']]}")


def config_entry(bench, name):
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise RegistryError(f"no configuration {name!r} in BENCHMARK.json")


def load_json(kind, name, bench_dir=BENCH_DIR):
    path = os.path.join(bench_dir, kind, name + ".json")
    if not os.path.isfile(path):
        raise RegistryError(f"{kind[:-1]} {name!r} has no file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(kind, name, bench_dir=BENCH_DIR):
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.isfile(path):
        raise RegistryError(f"{kind[:-1]} {name!r} has no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(bench, name, root=ROOT):
    """The configuration's file, at the path its BENCHMARK.json entry gives."""
    entry = config_entry(bench, name)
    path = os.path.join(root, entry["file"])
    if not os.path.isfile(path):
        raise RegistryError(f"configuration {name!r} has no file {path}")
    with open(path) as f:
        return json.load(f)


def load_peaks(device_kind, bench_dir=BENCH_DIR):
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise RegistryError(
            f"device kind {device_kind!r} is not in peaks.json "
            f"({sorted(peaks)}): add its published peaks, with their source")
    return peaks[device_kind]
