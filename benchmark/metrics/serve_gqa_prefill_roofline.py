"""Least time by the chip's bf16 peak for the grouped-query attention of the
prompt chunks dispatched in the traced sub-window — 4 x 128 flop a query head
for every (query, key) pair that is causal, filled and, on a window layer,
inside the window (counts/gqa_prefill.py; the chunks are the `start_tokens`
and `prompt_tokens` of the `engine.admit` spans, one chunk row a dispatch) —
over the device time under the `gqa_chunk` and `swa_chunk` scopes there (the
page's and the ring's writes and the blocked attention)."""
LAYER = "kernels"
MOVES = 'serve_tokens_per_s'
UNIT = "%"
SOURCE = "device_trace"

from benchmark.harness import phases, registry

SCOPES = ("gqa_chunk", "swa_chunk")


def scope_seconds(facts, scopes):
    """Device seconds of the sub-window under any of ``scopes`` (alone or
    nested), from the driver's reading of the trace; None without one."""
    by_scope = facts.get("scopes_s")
    if not by_scope:
        return None
    return sum(v for k, v in by_scope.items()
               if set(scopes) & set(k.split("/")))


def read(facts):
    spent = scope_seconds(facts, SCOPES)
    if not spent or facts["kind"] != "serve":
        return None
    starts = phases.per_iteration(facts, "engine.step", "engine.admit",
                                  attr="start_tokens")
    valid = phases.per_iteration(facts, "engine.step", "engine.admit",
                                 attr="prompt_tokens")
    if not valid or not sum(valid):
        return None
    if facts["workload"]["engine"]["prefill_cohort"] != 1:
        return None     # a span sums its rows: only one row a span is a chunk
    count = registry.load_module("counts", "gqa_prefill")
    flops = count.attention_flops(
        [(s, v) for s, v in zip(starts, valid) if v],
        facts["config"]["as_run"])
    return 100.0 * flops / facts["peaks"]["bf16_flops_per_s"] / spent
