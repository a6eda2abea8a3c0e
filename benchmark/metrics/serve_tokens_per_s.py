"""Output tokens the benchmark saw emitted inside the window, per second."""
LAYER = "end to end"
MOVES = None
UNIT = "tokens/s"
SOURCE = "host_clock"


def read(facts):
    if facts["kind"] != "serve":
        return None
    return facts["tokens"] / facts["window_s"]
