"""Of the programs compiled during set-up (`setup_programs_lowered`), those
whose executable did not come from JAX's persistent cache: compile rows with
`cache` other than `"hit"`. With a warm cache what is left are the compiles
the cache's own thresholds keep out (under a second each: `"none"` on the
`compile_table` line) and any real miss (`"miss"`: compiled and written),
which that line names."""
LAYER = "start-up"
MOVES = 'setup_s'
UNIT = "programs"
SOURCE = "program_counter"

from benchmark.harness import startup


def read(facts):
    return startup.read(facts, "cache_misses")
