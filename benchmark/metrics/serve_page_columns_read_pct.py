"""Columns the decode steps of the traced sub-window READ from the K/V leaves
of all layers — `attn_page_columns` from the full layers' pages and
`attn_ring_columns` from the window layers' rings, as the program counts what
its loops visit — over what a capacity-long page on EVERY layer, read up to
each live row's fill, would give: `attn_fill_columns` (one layer's) times the
layers. (2 + 3 x 512 / fill) / 5 at its floor for 2 full and 3 window layers;
a program that reads the capacity, dead rows' pages or a ring past the window
reads higher."""
LAYER = "cache manager"
MOVES = 'serve_tokens_per_s'
UNIT = "%"
SOURCE = "program_counter"

from benchmark.harness import phases

SPAN = ("engine.step", "engine.decode.enqueue")


def read(facts):
    fill = phases.per_iteration(facts, *SPAN, attr="attn_fill_columns")
    if not fill or not sum(fill):
        return None
    read_ = sum(sum(phases.per_iteration(facts, *SPAN, attr=a))
                for a in ("attn_page_columns", "attn_ring_columns"))
    layers = sum(1 for m, _ in facts["config"]["as_run"]["pattern"]
                 if m in ("gqa", "swa"))
    return 100.0 * read_ / (layers * sum(fill))
