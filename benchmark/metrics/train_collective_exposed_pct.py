"""Share of the traced sub-window in which a collective runs on the device
and no compute operation does (mean over the chips)."""
LAYER = "step factory"
MOVES = 'train_tokens_per_s_per_chip'
UNIT = "%"
SOURCE = "device_trace"


def read(facts):
    trace = facts.get("trace")
    if not trace or facts["kind"] != "train" or facts["chips"] < 2:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
