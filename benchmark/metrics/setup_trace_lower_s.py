"""Seconds of set-up spent in Python turning functions into programs: the
summed `trace_s + lower_s` (function to jaxpr, jaxpr to MLIR module) of the
compile rows that ended inside a `setup.*` span. Paid on every start, cache
or not; what a scanned layer stack would cut."""
LAYER = "start-up"
MOVES = 'setup_s'
UNIT = "s"
SOURCE = "program_counter"

from benchmark.harness import startup


def read(facts):
    return startup.read(facts, "trace_lower_s")
