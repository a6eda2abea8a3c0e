"""Megabytes of per-slot state the prefill programs installed per
Engine.step() iteration of the traced sub-window: the mean of the summed
`state_bytes` attribute of its `engine.admit` spans (requests admitted x the
bytes one slot holds over all its leaves)."""
LAYER = "cache manager"
MOVES = 'serve_tokens_per_s'
UNIT = "MB"
SOURCE = "program_counter"

from benchmark.harness import phases, stats


def read(facts):
    xs = phases.per_iteration(facts, "engine.step", "engine.admit",
                              attr="state_bytes")
    if not xs or not sum(xs):
        return None
    return stats.mean(xs) / 1e6
