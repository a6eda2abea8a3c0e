"""Seconds of set-up spent in the first run of each compiled program key: the
program's `program.first_call` spans that ended inside a `setup.*` span, each
less the compile rows that ended inside it. The span blocks on the result, so
what is left is the executable's first execution and its arguments' way to
the device."""
LAYER = "start-up"
MOVES = 'setup_s'
UNIT = "s"
SOURCE = "program_span"

from benchmark.harness import startup


def read(facts):
    return startup.read(facts, "first_run_s")
