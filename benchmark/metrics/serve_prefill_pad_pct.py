"""Share of the prefill positions dispatched in the traced sub-window that
were padding: 100 x sum of `padded_tokens` / sum of (`prompt_tokens` +
`padded_tokens`), which is rows x bucket, over the `engine.admit` spans;
0.0 when no prefill was dispatched."""
LAYER = "serving programs"
MOVES = 'serve_tokens_per_s'
UNIT = "%"
SOURCE = "program_counter"

from benchmark.harness import phases


def read(facts):
    padded = phases.per_iteration(facts, "engine.step", "engine.admit",
                                  attr="padded_tokens")
    if padded is None:
        return phases.NOT_INSTRUMENTED
    filled = phases.per_iteration(facts, "engine.step", "engine.admit",
                                  attr="prompt_tokens")
    positions = sum(padded) + sum(filled)
    return 100.0 * sum(padded) / positions if positions else 0.0
