"""Accepted drafts over verified drafts: summed `drafts_accepted` over summed
`drafts_verified` of the `engine.decode.enqueue` spans of the traced
sub-window, all live rows together, greedy and sampled. A draft is verified
where the round's first token left its row alive (serving/state_cache.py::
state_self_draft_k_apply counts both on the device)."""
LAYER = "serving programs"
MOVES = 'serve_tokens_per_s'
UNIT = "%"
SOURCE = "program_counter"

from benchmark.harness import phases


def read(facts):
    verified = phases.per_iteration(facts, "engine.step",
                                    "engine.decode.enqueue",
                                    attr="drafts_verified")
    if not verified or not sum(verified):
        return None
    accepted = phases.per_iteration(facts, "engine.step",
                                    "engine.decode.enqueue",
                                    attr="drafts_accepted")
    return 100.0 * sum(accepted) / sum(verified)
