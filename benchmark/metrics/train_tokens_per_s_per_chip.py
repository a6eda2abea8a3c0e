"""Steps completed in the window x global batch x sequence length over
(window seconds x chips). The window ends in block_until_ready."""
LAYER = "end to end"
MOVES = None
UNIT = "tokens/s/chip"
SOURCE = "host_clock"


def read(facts):
    if facts["kind"] != "train":
        return None
    tokens = facts["steps"] * facts["global_batch"] * facts["seq_len"]
    return tokens / (facts["window_s"] * facts["chips"])
