"""Least time of one self-drafted round by the chip's peaks
(counts/mtp_round.py: the greater of its bytes — weights outside the experts,
the kernels of the held experts reached, the FILLED page columns of the live
rows — over the memory bandwidth and its operations — the absorbed attention
of the positions run and their matrices — over the bf16 peak) over the
decode program's device time a round (its median run over `decode_k`
rounds). What a round did comes from the `engine.decode.enqueue` spans of the
traced sub-window: `live`, `filled_columns` (at the dispatch's start: the
columns the rounds add are left out), `experts_touched` and
`mtp_experts_touched`, `pairs_held` and `mtp_pairs_held`, `tokens_emitted`,
`rounds`."""
LAYER = "decode state and expert weights"
MOVES = 'serve_tokens_per_s'
UNIT = "%"
SOURCE = "device_trace"

from benchmark.harness import phases, registry, stats


def read(facts):
    trace = facts.get("trace")
    if not trace or facts["kind"] != "serve":
        return None
    runs = trace["module_runs_s"].get(
        facts["workload"]["trace"]["modules"]["decode"])
    rows = phases.rows_in_window(facts)
    if not runs or rows is None:
        return None
    spans = [r.attrs for r in rows if r.name == "engine.decode.enqueue"
             and r.attrs.get("rounds")]
    rounds = sum(a["rounds"] for a in spans)
    if not rounds:
        return None
    total = lambda *names: sum(a.get(n, 0) for a in spans for n in names)
    per_round = lambda *names: total(*names) / rounds
    # live and filled_columns are read once a dispatch: weigh by its rounds
    live = sum(a["live"] * a["rounds"] for a in spans) / rounds
    filled = sum(a["filled_columns"] * a["rounds"] for a in spans) / rounds
    cfg = facts["config"]["as_run"]
    count = registry.load_module("counts", "mtp_round")
    bytes_ = count.round_bytes(
        cfg, per_round("experts_touched", "mtp_experts_touched"), filled)
    flops = count.round_flops(
        cfg, live, per_round("tokens_emitted"), filled,
        per_round("pairs_held", "mtp_pairs_held"))
    least, _ = count.least_seconds(flops, bytes_, facts["peaks"])
    k = facts["workload"]["engine"]["decode_k"]
    return 100.0 * least / (stats.median(runs) / k)
