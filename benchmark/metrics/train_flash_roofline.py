"""Least time by the chip's peaks for the flash kernels' calls in the traced
sub-window (counts/flash.py; compute-bound at these shapes) over their
summed device time."""
LAYER = "kernels"
MOVES = 'train_tokens_per_s_per_chip'
UNIT = "%"
SOURCE = "device_trace"

from benchmark.harness import registry


def read(facts):
    trace = facts.get("trace")
    if not trace or facts["kind"] != "train":
        return None
    count = registry.load_module("counts", "flash")
    cfg = facts["config"]["as_run"]
    shape = (facts["global_batch"] // facts["chips"], cfg["n_heads"],
             cfg["n_kv_heads"], facts["seq_len"], facts["seq_len"],
             cfg["d_model"] // cfg["n_heads"])
    least = spent = 0.0
    for fam, seconds in trace["op_family_s"].items():
        if "flash_fwd" in fam:
            cost = count.forward(*shape)
        elif "flash_bwd_fused" in fam:
            cost = count.backward(*shape)
        elif "flash_bwd" in fam:        # split dq / dkv: half the work each
            cost = tuple(x / 2 for x in count.backward(*shape))
        else:
            continue
        calls = trace["op_family_calls"][fam]
        least += calls * count.least_seconds(*cost, facts["peaks"])[0]
        spent += seconds
    return 100.0 * least / spent if spent else None
