"""Median, over the StandardUpdater.update() calls of the traced sub-window, of
the `updater.dispatch` span: the call of the compiled step (the enqueue; the
device runs it later)."""
LAYER = "trainer loop"
MOVES = 'train_tokens_per_s_per_chip'
UNIT = "ms"
SOURCE = "program_span"

from benchmark.harness import phases


def read(facts):
    return phases.median_ms(facts, "updater.update", "updater.dispatch")
