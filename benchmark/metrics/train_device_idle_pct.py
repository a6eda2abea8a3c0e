"""1 - busy / window of the traced sub-window: busy is the union of the
operations' intervals on a device, the mean over the chips."""
LAYER = "device"
MOVES = 'train_tokens_per_s_per_chip'
UNIT = "%"
SOURCE = "device_trace"


def read(facts):
    trace = facts.get("trace")
    if not trace or facts["kind"] != "train":
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
