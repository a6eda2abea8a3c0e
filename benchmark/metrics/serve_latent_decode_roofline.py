"""Least time for one decode step of the latent-attention model by the
chip's memory bandwidth (counts/latent_decode.py: the weights outside the
experts, the kernels of the experts the step's pairs reached —
`experts_touched` of the `engine.decode.enqueue` spans over the steps
dispatched — and the FILLED columns of the live slots' latent pages, their
`filled_columns`) over the decode program's device time per step (its median
run over decode_k)."""
LAYER = "decode state and expert weights"
MOVES = 'serve_tokens_per_s'
UNIT = "%"
SOURCE = "device_trace"

from benchmark.harness import phases, registry, stats


def read(facts):
    trace = facts.get("trace")
    if not trace or facts["kind"] != "serve":
        return None
    runs = trace["module_runs_s"].get(
        facts["workload"]["trace"]["modules"]["decode"])
    touched = phases.per_iteration(facts, "engine.step",
                                   "engine.decode.enqueue",
                                   attr="experts_touched")
    if not runs or not touched or not sum(touched):
        return None
    filled = phases.per_iteration(facts, "engine.step",
                                  "engine.decode.enqueue",
                                  attr="filled_columns")
    k = facts["workload"]["engine"]["decode_k"]
    dispatches = sum(1 for x in touched if x)
    count = registry.load_module("counts", "latent_decode")
    # a slot's page grows by a column a step: the mean over the dispatch
    # lies (k - 1) / 2 columns a live slot above its start, left out
    bytes_ = count.decode_step_bytes(
        facts["config"]["as_run"], sum(touched) / (dispatches * k),
        sum(filled) / dispatches)
    least = bytes_ / facts["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (stats.median(runs) / k)
