"""The last line: what harness/line.py lets through and what it refuses."""
import copy
import json

import pytest

from benchmark.harness import line as line_mod

DECL = [{"name": "serve_tokens_per_s", "unit": "tokens/s"},
        {"name": "setup_s", "unit": "s"}]


def good(trace=False):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 123}
    if trace:
        device.update(busy_s=1.5, window_s=3.0)
    return {"correct": True, "attempted": 10, "failed": 0,
            "metrics": {"serve_tokens_per_s": {"value": 500.25,
                                               "unit": "tokens/s"},
                        "setup_s": {"value": 40.0, "unit": "s"}},
            "device": device}


@pytest.mark.parametrize("trace", [False, True])
def test_good_line_passes(trace):
    line_mod.check(good(trace), DECL, trace)


def _set(path, value):
    def f(line):
        node = line
        for k in path[:-1]:
            node = node[k]
        if value is KeyError:
            del node[path[-1]]
        else:
            node[path[-1]] = value
    return f


BAD = {
    "nan": _set(("metrics", "setup_s", "value"), float("nan")),
    "infinity": _set(("metrics", "setup_s", "value"), float("inf")),
    "null": _set(("metrics", "setup_s", "value"), None),
    "missing_metric": _set(("metrics", "setup_s"), KeyError),
    "wrong_unit": _set(("metrics", "serve_tokens_per_s", "unit"), "tok/s"),
    "bare_number": _set(("metrics", "setup_s"), 40.0),
    "undeclared_metric": _set(("metrics", "extra"),
                              {"value": 1.0, "unit": "s"}),
    "no_device_kind": _set(("device", "kind"), KeyError),
    "no_memory_peak": _set(("device", "memory_peak_bytes"), KeyError),
    "memory_peak_zero": _set(("device", "memory_peak_bytes"), 0),
    "correct_not_bool": _set(("correct",), "yes"),
    "failed_over_attempted": _set(("failed",), 11),
    "attempted_negative": _set(("attempted",), -1),
    "no_metrics_key": _set(("metrics",), KeyError),
    "busy_zero": _set(("device", "busy_s"), 0.0),
    "busy_over_window": _set(("device", "busy_s"), 3.5),
    "busy_missing": _set(("device", "busy_s"), KeyError),
    "window_nan": _set(("device", "window_s"), float("nan")),
    "breakdown_too_long": _set(("breakdown",), {
        "device_ops": [["op", 0.1]] * 11, "idle_gaps": []}),
    "breakdown_nan": _set(("breakdown",), {
        "device_ops": [["op", float("nan")]], "idle_gaps": []}),
}


@pytest.mark.parametrize("case", sorted(BAD))
def test_bad_line_is_refused(case):
    line = copy.deepcopy(good(trace=True))
    BAD[case](line)
    with pytest.raises(line_mod.LineError):
        line_mod.check(line, DECL, True)


def test_build_gives_strict_json_with_units_from_the_declaration():
    text = line_mod.build(
        correct=True, attempted=3, failed=0,
        values={"serve_tokens_per_s": 10.5, "setup_s": 2.0},
        metrics_declared=DECL,
        device=good()["device"], trace=False)
    line = json.loads(text)
    assert line["metrics"]["serve_tokens_per_s"] == {"value": 10.5,
                                                     "unit": "tokens/s"}
    assert list(line)[:5] == list(line_mod.KEYS)


def test_build_refuses_a_reader_that_found_nothing_where_declared():
    with pytest.raises(line_mod.LineError, match="setup_s"):
        line_mod.build(correct=True, attempted=3, failed=0,
                       values={"serve_tokens_per_s": 10.5, "setup_s": None},
                       metrics_declared=DECL, device=good()["device"],
                       trace=False)


def test_declared_metrics_follow_benchmark_json():
    from benchmark.harness import registry

    bench = registry.load_benchmark()
    for cell in bench["workloads"]:
        e2e = {m["name"] for m in line_mod.declared(bench, cell["name"], 0)}
        assert "setup_s" in e2e and len(e2e) >= 2
        per_layer = line_mod.declared(bench, cell["name"], 1)
        assert per_layer
        assert all(m["moves"] in e2e for m in per_layer)
    dp1 = {m["name"] for m in line_mod.declared(bench, "gpt2m-train-dp1", 1)}
    dp4 = {m["name"] for m in line_mod.declared(bench, "gpt2m-train-dp4", 1)}
    assert dp4 - dp1 == {"train_collective_exposed_pct"}


def test_every_number_compared_stands_beside_its_limit_under_the_last_key():
    checks = [
        {"name": "served_logit_gap", "value": 0.07, "limit": 0.2, "ok": True},
        {"name": "state_logit_rms", "value": float("inf"), "limit": 0.28,
         "ok": False},
        {"name": "served_tokens_compared", "value": 900, "limit": ">= 256",
         "ok": True},
        {"name": "cursors_off", "value": [(3, 17, 18)], "limit": [],
         "ok": False}]
    g = good(True)
    text = line_mod.build(
        correct=False, attempted=10, failed=0,
        values={k: v["value"] for k, v in g["metrics"].items()},
        metrics_declared=DECL, device=g["device"], trace=True,
        breakdown={"device_ops": [["fusion", 1.0]], "idle_gaps": []},
        checks=checks)
    line = json.loads(text)
    assert list(line)[-1] == "compared"
    assert line["compared"] == {
        "served_logit_gap": {"value": 0.07, "limit": 0.2},
        "state_logit_rms": {"value": "inf", "limit": 0.28},
        "served_tokens_compared": {"value": 900, "limit": ">= 256"},
        "cursors_off": {"value": [[3, 17, 18]], "limit": []}}
    # a line without checks is as it was
    assert "compared" not in json.loads(line_mod.build(
        correct=True, attempted=10, failed=0,
        values={k: v["value"] for k, v in g["metrics"].items()},
        metrics_declared=DECL, device=g["device"], trace=True))
