"""Least time by the chip's bf16 peak for the latent attention of the prompt
chunks dispatched in the traced sub-window — the scores' and values' flops of
their query-column pairs in the expanded form, 640 a pair and head, the
re-expansion of the page not counted (counts/latent_prefill.py; the pairs are
the summed `attended_pairs` of the `engine.admit` spans) — over the device
time under the `mla_chunk` scope there (the page write, the re-expansion and
the blocked attention)."""
LAYER = "kernels"
MOVES = 'serve_tokens_per_s'
UNIT = "%"
SOURCE = "device_trace"

from benchmark.harness import phases, registry

SCOPE = "mla_chunk"


def scope_seconds(facts, scope):
    """Device seconds of the sub-window under ``scope`` (alone or nested),
    from the driver's reading of the trace; None without one."""
    by_scope = facts.get("scopes_s")
    if not by_scope:
        return None
    return sum(v for k, v in by_scope.items() if scope in k.split("/"))


def read(facts):
    spent = scope_seconds(facts, SCOPE)
    if not spent or facts["kind"] != "serve":
        return None
    pairs = phases.per_iteration(facts, "engine.step", "engine.admit",
                                 attr="attended_pairs")
    if not pairs or not sum(pairs):
        return None
    count = registry.load_module("counts", "latent_prefill")
    flops = count.attention_flops(sum(pairs), facts["config"]["as_run"])
    return 100.0 * flops / facts["peaks"]["bf16_flops_per_s"] / spent
