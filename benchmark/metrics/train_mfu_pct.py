"""Model FLOP/s utilization: operations the forward and backward passes
require per token (counts/train_model.py, published sizes, causal attention
once, no recomputation) x tokens/s over (chips x the chip's bf16 peak). The
rate is read off the device trace: the step program's time inside the traced
sub-window over the median length of one whole run is the steps done there
(starting and stopping the profiler stalls the host, so the traced run's own
window is not a fair clock)."""
LAYER = "model"
MOVES = 'train_tokens_per_s_per_chip'
UNIT = "%"
SOURCE = "device_trace"

from benchmark.harness import registry, stats


def read(facts):
    trace = facts.get("trace")
    if not trace or facts["kind"] != "train":
        return None
    module = facts["workload"]["trace"]["modules"]["step"]
    runs = trace["module_runs_s"].get(module)
    if not runs:
        return None
    count = registry.load_module("counts", "train_model")
    per_token = count.flops_per_token(facts["config"]["published"],
                                      facts["seq_len"])
    steps = trace["module_busy_s"][module] / stats.median(runs)
    rate = (steps * facts["global_batch"] * facts["seq_len"]
            / trace["window_s"])
    return 100.0 * per_token * rate / (
        facts["chips"] * facts["peaks"]["bf16_flops_per_s"])
