"""Median device duration of one run of the prefill program in the traced
sub-window."""
LAYER = "serving programs"
MOVES = 'serve_tokens_per_s'
UNIT = "ms"
SOURCE = "device_trace"

from benchmark.harness import stats


def read(facts):
    trace = facts.get("trace")
    if not trace or facts["kind"] != "serve":
        return None
    runs = trace["module_runs_s"].get(
        facts["workload"]["trace"]["modules"]["prefill"])
    return 1e3 * stats.median(runs) if runs else None
