"""Share of the routed (token, expert) pairs that landed on an expert held
here: summed `pairs_held` over summed `pairs_routed` of the
`engine.decode.enqueue` spans of the traced sub-window. A chip holding 128 of
512 experts under even routing reads about 25."""
LAYER = "expert routing"
MOVES = 'serve_tokens_per_s'
UNIT = "%"
SOURCE = "program_counter"

from benchmark.harness import phases


def read(facts):
    routed = phases.per_iteration(facts, "engine.step",
                                  "engine.decode.enqueue",
                                  attr="pairs_routed")
    if not routed or not sum(routed):
        return None
    held = phases.per_iteration(facts, "engine.step",
                                "engine.decode.enqueue", attr="pairs_held")
    return 100.0 * sum(held) / sum(routed)
