"""Mean of the engine's own occupancy samples (ServingReport.record_step:
slots decoding or prefilling over n_slots), one a scheduler iteration of the
window."""
LAYER = "engine scheduler"
MOVES = 'serve_tokens_per_s'
UNIT = "%"
SOURCE = "program_counter"

from benchmark.harness import stats


def read(facts):
    if facts["kind"] != "serve" or not facts["occupancy"]:
        return None
    return 100.0 * stats.mean(facts["occupancy"])
