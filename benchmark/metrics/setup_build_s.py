"""Seconds of set-up spent building the program's state: its `engine.build`
(pages and per-slot state allocated, parameters placed) and `step.build` spans
that ended inside a `setup.*` span, each less the compile rows that ended
inside it."""
LAYER = "start-up"
MOVES = 'setup_s'
UNIT = "s"
SOURCE = "program_span"

from benchmark.harness import startup


def read(facts):
    return startup.read(facts, "build_s")
