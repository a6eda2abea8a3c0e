"""Least time by the chip's peaks for the decode-tile calls of the grouped
expert product (`grouped_swiglu_narrow`, ops/grouped_swiglu.py) in the traced
sub-window over their summed device time. A call's bytes are the three
kernels of each expert it touched — the mean of `experts_touched` per step
and expert layer over the `engine.decode.enqueue` spans — and its rows in and
out (counts/hybrid_decode.py); at a few rows per expert the calls are
memory-bound."""
LAYER = "kernels"
MOVES = 'serve_tokens_per_s'
UNIT = "%"
SOURCE = "device_trace"

from benchmark.harness import phases, registry

KERNEL = "grouped_swiglu_narrow"


def read(facts):
    trace = facts.get("trace")
    if not trace or facts["kind"] != "serve":
        return None
    fams = [f for f in trace["op_family_s"] if KERNEL in f]
    touched = phases.per_iteration(facts, "engine.step",
                                   "engine.decode.enqueue",
                                   attr="experts_touched")
    if not fams or not touched or not sum(touched):
        return None
    held = phases.per_iteration(facts, "engine.step",
                                "engine.decode.enqueue", attr="pairs_held")
    cfg = facts["config"]["as_run"]
    k = facts["workload"]["engine"]["decode_k"]
    layers = sum(1 for _, f in cfg["pattern"] if f == "moe")
    calls_spanned = sum(1 for x in touched if x) * k * layers
    count = registry.load_module("counts", "hybrid_decode")
    cost = count.grouped_swiglu(sum(held) / calls_spanned,
                                sum(touched) / calls_spanned, cfg)
    calls = sum(trace["op_family_calls"][f] for f in fams)
    spent = sum(trace["op_family_s"][f] for f in fams)
    least = calls * count.least_seconds(*cost, facts["peaks"])[0]
    return 100.0 * least / spent if spent else None
