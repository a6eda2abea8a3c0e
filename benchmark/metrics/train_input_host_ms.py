"""Median, over the StandardUpdater.update() calls of the traced sub-window, of
the `updater.input` span: `next(iterator)`, the converter and `shard_batch`
(the `device_put` over the chips)."""
LAYER = "trainer loop"
MOVES = 'train_tokens_per_s_per_chip'
UNIT = "ms"
SOURCE = "program_span"

from benchmark.harness import phases


def read(facts):
    return phases.median_ms(facts, "updater.update", "updater.input")
