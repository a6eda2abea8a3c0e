"""Device time under the `mhc_mix` scope (the hyper-connection maps of every
sub-layer: the norm of the 4-stream state, its product with Phi, the
Sinkhorn rounds, the mixing of the streams before and after the sub-layer)
over the device's busy time, in the traced sub-window. A fusion carries its
root's scope alone, so a map that XLA fuses into a neighbour outside the
scope is missed, and a neighbour fused into a map is counted."""
LAYER = "model"
MOVES = 'serve_tokens_per_s'
UNIT = "%"
SOURCE = "device_trace"

from benchmark.harness import registry


def read(facts):
    trace = facts.get("trace")
    if not trace or facts["kind"] != "serve":
        return None
    spent = registry.load_module(
        "metrics", "serve_latent_prefill_roofline").scope_seconds(
            facts, "mhc_mix")
    return None if spent is None else 100.0 * spent / trace["busy_s"]
