"""BENCHMARK.json against the contract's limits and against the files it
names, and the data-driven discovery: a configuration, a cell and a per-layer
metric added as files are found with no edit to a file that was there."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import line as line_mod
from benchmark.harness import registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return registry.load_benchmark()


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert bench["paths"][0] == "benchmark"
    assert os.path.getsize(os.path.join(registry.ROOT,
                                        "BENCHMARK.json")) < 64 * 1024
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])


def test_names_units_and_whys(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_every_config_is_used_and_its_file_states_the_source(bench):
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("benchmark/configs/")
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        data = registry.load_config(bench, c["name"])
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]
        for key in ("published", "as_run", "departures", "assumed", "padded"):
            assert key in data


def test_no_width_differs_from_the_published_one(bench):
    gpt2 = registry.load_config(bench, "gpt2-medium")
    pub, run = gpt2["published"], gpt2["as_run"]
    assert (run["d_model"], run["n_heads"], run["n_layers"], run["d_ff"],
            run["max_len"]) == (pub["n_embd"], pub["n_head"], pub["n_layer"],
                                pub["n_inner"], pub["n_positions"])
    assert run["vocab"] >= pub["vocab_size"] and "vocab" in gpt2["padded"]
    sc2 = registry.load_config(bench, "starcoder2-3b")
    pub, run = sc2["published"], sc2["as_run"]
    assert (run["d_model"], run["n_heads"], run["n_kv_heads"],
            run["n_layers"], run["d_ff"], run["vocab"],
            run["rope_theta"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["num_hidden_layers"],
        pub["intermediate_size"], pub["vocab_size"], pub["rope_theta"])


def test_every_cell_has_its_files(bench):
    for w in bench["workloads"]:
        data = registry.load_json("workloads", w["name"])
        driver = registry.load_module("drivers", data["driver"])
        assert callable(driver.run)
        assert {"traffic", "check", "trace"} <= set(data)


@pytest.mark.parametrize("group", ["end_to_end", "per_layer"])
def test_every_metric_has_a_reader_that_agrees_with_its_entry(bench, group):
    layers = set()
    for m in bench[group]:
        reader = registry.load_module("metrics", m["name"])
        assert reader.UNIT == m["unit"] and reader.SOURCE == m["source"]
        assert callable(reader.read)
        if group == "per_layer":
            assert reader.LAYER == m["layer"] and reader.MOVES == m["moves"]
            layers.add(m["layer"])
            assert set(m) <= {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
    files = {f[:-3] for f in os.listdir(os.path.join(registry.BENCH_DIR,
                                                     "metrics"))
             if f.endswith(".py")}
    assert {m["name"] for m in bench[group]} <= files


def test_readers_return_nothing_when_there_is_nothing_to_read(bench):
    facts = {"kind": "train", "trace": None, "chips": 1}
    for m in bench["per_layer"]:
        if m["source"] == "device_trace":
            reader = registry.load_module("metrics", m["name"])
            assert reader.read(facts) is None


def test_unknown_names_are_errors_not_defaults(bench):
    with pytest.raises(registry.RegistryError):
        registry.cell_entry(bench, "no-such-cell")
    with pytest.raises(registry.RegistryError):
        registry.load_json("workloads", "no-such-cell")
    with pytest.raises(registry.RegistryError):
        registry.load_module("metrics", "no_such_metric")
    with pytest.raises(registry.RegistryError):
        registry.load_peaks("TPU v9 imaginary")
    assert registry.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


@pytest.fixture(scope="module")
def grown(tmp_path_factory, bench):
    """A copy of the benchmark with one configuration, one cell and one
    per-layer metric ADDED as files and entries; no copied file is edited."""
    root = tmp_path_factory.mktemp("grown")
    shutil.copytree(registry.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("fixtures", "__pycache__"))
    b = root / "benchmark"
    cfg = registry.load_config(bench, "gpt2-medium")
    cfg["name"], cfg["source"] = "gpt2-large", "https://example.org/gpt2-large"
    (b / "configs" / "gpt2-large.json").write_text(json.dumps(cfg))
    cell = registry.load_json("workloads", "gpt2m-train-dp1")
    cell["traffic"]["batch_per_chip"] = 4
    (b / "workloads" / "gpt2l-train-dp1.json").write_text(json.dumps(cell))
    (b / "metrics" / "train_steps_in_window.py").write_text(
        'LAYER = "trainer loop"\nMOVES = "train_tokens_per_s_per_chip"\n'
        'UNIT = "steps"\nSOURCE = "program_counter"\n\n\n'
        'def read(facts):\n    return facts["steps"]\n')
    grown = json.loads(json.dumps(bench))
    grown["configs"].append({
        "name": "gpt2-large", "source": cfg["source"],
        "file": "benchmark/configs/gpt2-large.json", "reduced": [],
        "why": "a later PR's configuration"})
    grown["workloads"].append({
        "name": "gpt2l-train-dp1", "config": "gpt2-large",
        "traffic": "train-dp1-b4", "chips": 1, "why": "a later PR's cell"})
    grown["per_layer"].append({
        "name": "train_steps_in_window", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "trainer loop",
        "moves": "train_tokens_per_s_per_chip",
        "workloads": ["gpt2l-train-dp1"]})
    for m in grown["end_to_end"]:
        if m["name"] == "train_tokens_per_s_per_chip":
            m["workloads"].append("gpt2l-train-dp1")
    (root / "BENCHMARK.json").write_text(json.dumps(grown))
    return root


def test_added_files_are_found_with_no_edit(grown):
    bench = registry.load_benchmark(str(grown))
    bdir = str(grown / "benchmark")
    cell = registry.cell_entry(bench, "gpt2l-train-dp1")
    assert registry.load_config(bench, cell["config"],
                                str(grown))["name"] == "gpt2-large"
    work = registry.load_json("workloads", cell["name"], bdir)
    assert work["traffic"]["batch_per_chip"] == 4
    assert callable(registry.load_module("drivers", work["driver"], bdir).run)
    names = [m["name"] for m in line_mod.declared(bench, cell["name"], 1)]
    assert "train_steps_in_window" in names
    assert "train_collective_exposed_pct" not in names
    reader = registry.load_module("metrics", "train_steps_in_window", bdir)
    assert reader.read({"steps": 12}) == 12
    old = [m["name"] for m in line_mod.declared(bench, "gpt2m-train-dp1", 1)]
    assert "train_steps_in_window" not in old


@pytest.mark.parametrize("cell,code", [("gpt2l-train-dp1", 2),
                                       ("no-such-cell", 4)])
def test_run_off_a_tpu_prints_no_line_and_exits_non_zero(grown, cell, code):
    """The copy's own run.py finds the added cell's files (it gets as far as
    the look for a chip: exit 2), fails on a name that has none (exit 4), and
    in neither case prints a result line. The copy holds only BENCHMARK.json
    and the benchmark's directory: the program is found through PYTHONPATH."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=registry.ROOT)
    p = subprocess.run(
        [sys.executable, str(grown / "benchmark" / "run.py"), "--workload",
         cell, "--seed", str(2 ** 31 + 11), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=str(grown), timeout=300)
    assert p.returncode == code, p.stderr[-2000:]
    last = (p.stdout.strip().splitlines() or [""])[-1]
    assert not last.startswith("{")
    if code == 2:
        assert "needs 1 TPU chip" in p.stderr
