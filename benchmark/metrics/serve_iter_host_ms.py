"""Median, over the Engine.step() spans of the traced sub-window, of the
span's wall time not covered by device-busy time."""
LAYER = "engine scheduler"
MOVES = 'serve_tokens_per_s'
UNIT = "ms"
SOURCE = "device_trace"

from benchmark.harness import stats


def read(facts):
    trace = facts.get("trace")
    if not trace or facts["kind"] != "serve":
        return None
    xs = trace["span_uncovered_s"].get("engine.step")
    return 1e3 * stats.median(xs) if xs else None
