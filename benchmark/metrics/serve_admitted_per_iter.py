"""Mean, over the Engine.step() iterations of the traced sub-window, of the
requests that left the queue in the iteration: the summed `admitted`
attribute of its `engine.admit` spans."""
LAYER = "engine scheduler"
MOVES = 'serve_tokens_per_s'
UNIT = "requests"
SOURCE = "program_counter"

from benchmark.harness import phases, stats


def read(facts):
    xs = phases.per_iteration(facts, "engine.step", "engine.admit",
                              attr="admitted")
    return phases.NOT_INSTRUMENTED if xs is None else stats.mean(xs)
