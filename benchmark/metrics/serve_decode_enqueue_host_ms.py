"""Median, over the Engine.step() iterations of the traced sub-window, of the
`engine.decode.enqueue` span: from the entry of `_decode` until the decode
dispatch has returned; 0 for an iteration with nothing to decode."""
LAYER = "engine scheduler"
MOVES = 'serve_tokens_per_s'
UNIT = "ms"
SOURCE = "program_span"

from benchmark.harness import phases


def read(facts):
    return phases.median_ms(facts, "engine.step", "engine.decode.enqueue")
