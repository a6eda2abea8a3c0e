"""The programs JAX lowered and compiled (or fetched from its persistent cache)
during set-up: the rows of the program's compile log
(`chainermn_tpu.tracing.compiles`) that ended inside a `setup.*` span of the
run. The benchmark's own jits there (the weights made on the device) count,
since `setup_s` pays for them; the plain reference's, outside those spans, do
not. The `compile_table` line names them."""
LAYER = "start-up"
MOVES = 'setup_s'
UNIT = "programs"
SOURCE = "program_counter"

from benchmark.harness import startup


def read(facts):
    return startup.read(facts, "programs_lowered")
