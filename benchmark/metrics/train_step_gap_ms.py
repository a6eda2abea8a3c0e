"""Mean device-idle gap between consecutive runs of the step program in the
traced sub-window."""
LAYER = "trainer loop"
MOVES = 'train_tokens_per_s_per_chip'
UNIT = "ms"
SOURCE = "device_trace"

from benchmark.harness import stats


def read(facts):
    trace = facts.get("trace")
    if not trace or facts["kind"] != "train":
        return None
    gaps = trace["module_gaps_s"].get(facts["workload"]["trace"]["modules"]["step"])
    return 1e3 * stats.mean(gaps) if gaps else None
