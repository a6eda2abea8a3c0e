"""Least time for one decode step by the chip's memory bandwidth (the bytes
of the weights a step reads plus the bytes of the cache filled at the time,
counts/decode.py; memory-bound) over the decode program's device time per
step (its median run over decode_k)."""
LAYER = "decode attention and weights"
MOVES = 'serve_tpot_p95_ms'
UNIT = "%"
SOURCE = "device_trace"

from benchmark.harness import registry, stats


def read(facts):
    trace = facts.get("trace")
    if not trace or facts["kind"] != "serve" or not facts["trace_span"]:
        return None
    runs = trace["module_runs_s"].get(
        facts["workload"]["trace"]["modules"]["decode"])
    lo, hi = facts["trace_span"]
    filled = [n for t, n in facts["filled"] if lo <= t <= hi]
    if not runs or not filled:
        return None
    count = registry.load_module("counts", "decode")
    cfg = facts["config"]["as_run"]
    bytes_ = (count.weight_bytes(cfg)
              + stats.mean(filled) * count.cache_bytes_per_token(cfg))
    least = bytes_ / facts["peaks"]["hbm_bytes_per_s"]
    step = stats.median(runs) / facts["workload"]["engine"]["decode_k"]
    return 100.0 * least / step
