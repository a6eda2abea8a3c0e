"""95th percentile over the requests completed in the window of the
request's mean gap between output tokens, (t_last - t_first) / (n - 1)."""
LAYER = "end to end"
MOVES = None
UNIT = "ms"
SOURCE = "host_clock"

from benchmark.harness import stats


def read(facts):
    if facts["kind"] != "serve" or not facts["tpot_s"]:
        return None
    return 1e3 * stats.percentile(facts["tpot_s"], 95)
