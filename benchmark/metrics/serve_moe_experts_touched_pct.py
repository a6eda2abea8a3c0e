"""Share of the held experts that at least one pair reached, per decode step
and expert layer: the summed `experts_touched` of the `engine.decode.enqueue`
spans of the traced sub-window over steps x expert layers x held experts.
It sets how many expert kernels a step reads."""
LAYER = "expert routing"
MOVES = 'serve_tokens_per_s'
UNIT = "%"
SOURCE = "program_counter"

from benchmark.harness import phases


def read(facts):
    touched = phases.per_iteration(facts, "engine.step",
                                   "engine.decode.enqueue",
                                   attr="experts_touched")
    if not touched or not sum(touched):
        return None
    cfg = facts["config"]["as_run"]
    layers = sum(1 for _, f in cfg["pattern"] if f == "moe")
    slots = (sum(1 for x in touched if x)
             * facts["workload"]["engine"]["decode_k"] * layers
             * (cfg["held_hi"] - cfg["held_lo"]))
    return 100.0 * sum(touched) / slots
