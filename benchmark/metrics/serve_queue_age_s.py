"""Median, over the Engine.step() iterations of the traced sub-window, of the
`oldest_wait_s` attribute of `engine.step`: the age of the head of the queue
when the iteration began, 0 with an empty queue."""
LAYER = "engine scheduler"
MOVES = 'serve_tokens_per_s'
UNIT = "s"
SOURCE = "program_span"

from benchmark.harness import phases, stats


def read(facts):
    rows = phases.rows_in_window(facts)
    if rows is None:
        return phases.NOT_INSTRUMENTED
    return stats.median([float(step.attrs["oldest_wait_s"]) for step, _ in
                         phases.iterations(rows, "engine.step")])
