"""Of the live row-steps of the decode dispatches in the traced sub-window,
the share whose cursor had passed the window, so that the row's rings had
wrapped: the summed `attn_rows_wrapped` over the summed `attn_rows_live` of
the `engine.decode.enqueue` spans. It says that the traffic works the wrap
(long prompts arrive wrapped; a short one wraps while it decodes)."""
LAYER = "cache manager"
MOVES = 'serve_tokens_per_s'
UNIT = "%"
SOURCE = "program_counter"

from benchmark.harness import phases

SPAN = ("engine.step", "engine.decode.enqueue")


def read(facts):
    live = phases.per_iteration(facts, *SPAN, attr="attn_rows_live")
    if not live or not sum(live):
        return None
    wrapped = phases.per_iteration(facts, *SPAN, attr="attn_rows_wrapped")
    return 100.0 * sum(wrapped) / sum(live)
