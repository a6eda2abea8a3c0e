"""Seconds of set-up spent getting executables: the summed `backend_s` of the
compile rows that ended inside a `setup.*` span: XLA's compile on a miss; the
cache's read, deserialisation and the executable's load on a hit."""
LAYER = "start-up"
MOVES = 'setup_s'
UNIT = "s"
SOURCE = "program_counter"

from benchmark.harness import startup


def read(facts):
    return startup.read(facts, "backend_compile_s")
