"""Median, over the Engine.step() iterations of the traced sub-window, of the
summed `engine.admit` spans of the iteration: cohort selection, `_install`,
the fill of the token arrays and the prefill enqueue; 0 for an iteration that
admitted nothing."""
LAYER = "engine scheduler"
MOVES = 'serve_tokens_per_s'
UNIT = "ms"
SOURCE = "program_span"

from benchmark.harness import phases


def read(facts):
    return phases.median_ms(facts, "engine.step", "engine.admit")
