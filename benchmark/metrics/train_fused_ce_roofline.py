"""Least time by the chip's peaks for the fused head + cross-entropy
kernels' calls in the traced sub-window (counts/fused_ce.py; compute-bound)
over their summed device time."""
LAYER = "kernels"
MOVES = 'train_tokens_per_s_per_chip'
UNIT = "%"
SOURCE = "device_trace"

from benchmark.harness import registry


def read(facts):
    trace = facts.get("trace")
    if not trace or facts["kind"] != "train":
        return None
    count = registry.load_module("counts", "fused_ce")
    flash = registry.load_module("counts", "flash")
    cfg = facts["config"]["as_run"]
    rows = facts["global_batch"] // facts["chips"] * facts["seq_len"]
    least = spent = 0.0
    for fam, seconds in trace["op_family_s"].items():
        kernel = next((k for k in count.MATMULS if k in fam), None)
        if kernel is None:
            continue
        cost = count.call(kernel, rows, cfg["d_model"], cfg["vocab"])
        least += (trace["op_family_calls"][fam]
                  * flash.least_seconds(*cost, facts["peaks"])[0])
        spent += seconds
    return 100.0 * least / spent if spent else None
