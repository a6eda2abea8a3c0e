"""Median, over the Engine.step() iterations of the traced sub-window, of the
summed `engine.emit` spans of the iteration: the replay loops after each pull
(tokens appended, requests retired)."""
LAYER = "engine scheduler"
MOVES = 'serve_tokens_per_s'
UNIT = "ms"
SOURCE = "program_span"

from benchmark.harness import phases


def read(facts):
    return phases.median_ms(facts, "engine.step", "engine.emit")
