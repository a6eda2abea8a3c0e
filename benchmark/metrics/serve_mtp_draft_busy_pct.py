"""Device time under the `mtp_draft` scope (the module's projection, its
block — latent attention over its own page, router, experts —, its norm, the
shared head on its output, the draft's sampling) and the `mtp_accept` scope
(the acceptance scan: the target's two samples a row and the comparison)
over the device's busy time, in the traced sub-window: what drafting costs,
beside what `serve_mtp_tokens_per_round` says it buys. A fusion carries its
root's scope alone."""
LAYER = "model"
MOVES = 'serve_tokens_per_s'
UNIT = "%"
SOURCE = "device_trace"


SCOPES = ("mtp_draft", "mtp_accept")


def read(facts):
    trace = facts.get("trace")
    by_scope = facts.get("scopes_s")
    if not trace or not by_scope or facts["kind"] != "serve":
        return None
    spent = [v for k, v in by_scope.items()
             if any(s in k.split("/") for s in SCOPES)]
    return 100.0 * sum(spent) / trace["busy_s"] if spent else None
