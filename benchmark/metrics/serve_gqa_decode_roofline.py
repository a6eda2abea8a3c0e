"""Least time for one decode step of the model of K/V pages and rings by the
chip's memory bandwidth (counts/gqa_decode.py: the weights outside the
experts, the kernels of the experts the step's pairs reached —
`experts_touched` of the `engine.decode.enqueue` spans over the steps
dispatched — the FILLED columns of the live rows' pages, their
`attn_fill_columns`, a window of ring columns for every live row whose rings
have wrapped, `attn_rows_wrapped` (the fewer columns of the others left out),
and the rows written) over the decode program's device time per step (its
median run over decode_k)."""
LAYER = "decode state and expert weights"
MOVES = 'serve_tokens_per_s'
UNIT = "%"
SOURCE = "device_trace"

from benchmark.harness import phases, registry, stats

SPAN = ("engine.step", "engine.decode.enqueue")


def read(facts):
    trace = facts.get("trace")
    if not trace or facts["kind"] != "serve":
        return None
    runs = trace["module_runs_s"].get(
        facts["workload"]["trace"]["modules"]["decode"])
    got = {a: phases.per_iteration(facts, *SPAN, attr=a) for a in (
        "experts_touched", "attn_fill_columns", "attn_rows_wrapped",
        "attn_rows_live")}
    if not runs or not got["attn_rows_live"] or not sum(got["attn_rows_live"]):
        return None
    cfg = facts["config"]["as_run"]
    k = facts["workload"]["engine"]["decode_k"]
    steps = k * sum(1 for x in got["attn_rows_live"] if x)
    per_step = {a: sum(xs) / steps for a, xs in got.items()}
    count = registry.load_module("counts", "gqa_decode")
    bytes_ = count.decode_step_bytes(
        cfg, per_step["experts_touched"], per_step["attn_fill_columns"],
        per_step["attn_rows_wrapped"] * cfg["window"],
        per_step["attn_rows_live"])
    least = bytes_ / facts["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (stats.median(runs) / k)
