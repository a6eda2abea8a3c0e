"""Imbalance over the held experts: the summed `expert_load_max` (the busiest
held expert's pairs, per step and expert layer) over the summed
`expert_load_mean` (held pairs over held experts) of the
`engine.decode.enqueue` spans of the traced sub-window. 1 is an even spread;
the grouped product keeps every pair whatever this reads."""
LAYER = "expert routing"
MOVES = 'serve_tokens_per_s'
UNIT = "ratio"
SOURCE = "program_counter"

from benchmark.harness import phases


def read(facts):
    top = phases.per_iteration(facts, "engine.step", "engine.decode.enqueue",
                               attr="expert_load_max")
    if not top or not sum(top):
        return None
    mean = phases.per_iteration(facts, "engine.step",
                                "engine.decode.enqueue",
                                attr="expert_load_mean")
    return sum(top) / sum(mean) if sum(mean) else None
