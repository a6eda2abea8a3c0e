"""Mean, over the Engine.step() iterations of the traced sub-window, of the
prompt tokens written into the pages in the iteration: the summed
`prompt_tokens` of its `engine.admit` spans (one chunk cohort each in
chunked mode)."""
LAYER = "engine scheduler"
MOVES = 'serve_tokens_per_s'
UNIT = "tokens"
SOURCE = "program_counter"

from benchmark.harness import phases, stats


def read(facts):
    xs = phases.per_iteration(facts, "engine.step", "engine.admit",
                              attr="prompt_tokens")
    return phases.NOT_INSTRUMENTED if xs is None else stats.mean(xs)
