"""Tokens a live row gets from a self-drafted round: summed `tokens_emitted`
of the `engine.decode.enqueue` spans of the traced sub-window over their
summed live rows x `rounds`. Under 1 + acceptance where EOS and budgets cut
a row's dispatch short (a row that ends in a dispatch's second round still
counts as live for all of them)."""
LAYER = "serving programs"
MOVES = 'serve_tokens_per_s'
UNIT = "tokens"
SOURCE = "program_counter"

from benchmark.harness import phases


def read(facts):
    rows = phases.rows_in_window(facts)
    if rows is None:
        return None
    spans = [r for r in rows if r.name == "engine.decode.enqueue"
             and r.attrs.get("rounds")]
    slots = sum(r.attrs["live"] * r.attrs["rounds"] for r in spans)
    if not slots:
        return None
    return sum(r.attrs["tokens_emitted"] for r in spans) / slots
