"""Least time for one decode step of the hybrid model by the chip's memory
bandwidth (counts/hybrid_decode.py: the weights outside the experts, the
kernels of the held experts the step's pairs reached — `experts_touched` of
the `engine.decode.enqueue` spans over the steps dispatched — the live slots'
recurrent state read and written, and the latent page filled at the time)
over the decode program's device time per step (its median run over
decode_k)."""
LAYER = "decode state and expert weights"
MOVES = 'serve_tokens_per_s'
UNIT = "%"
SOURCE = "device_trace"

from benchmark.harness import phases, registry, stats


def read(facts):
    trace = facts.get("trace")
    if not trace or facts["kind"] != "serve" or not facts["trace_span"]:
        return None
    runs = trace["module_runs_s"].get(
        facts["workload"]["trace"]["modules"]["decode"])
    lo, hi = facts["trace_span"]
    filled = [n for t, n in facts["filled"] if lo <= t <= hi]
    touched = phases.per_iteration(facts, "engine.step",
                                   "engine.decode.enqueue",
                                   attr="experts_touched")
    if not runs or not filled or not touched or not sum(touched):
        return None
    live = phases.per_iteration(facts, "engine.step",
                                "engine.decode.enqueue", attr="live")
    k = facts["workload"]["engine"]["decode_k"]
    dispatches = sum(1 for x in touched if x)
    count = registry.load_module("counts", "hybrid_decode")
    bytes_ = count.decode_step_bytes(
        facts["config"]["as_run"], sum(touched) / (dispatches * k),
        sum(live) / dispatches, stats.mean(filled))
    least = bytes_ / facts["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (stats.median(runs) / k)
