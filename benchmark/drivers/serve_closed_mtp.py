"""Driver: one ``serving.Engine`` over a latent-attention model with held
experts that DRAFTS ITS OWN NEXT TOKEN with its multi-token-prediction module
(``deepseek-v3``), under ``serve_closed``'s closed loop: every decode dispatch
is ``decode_k`` self-drafted rounds of one or two tokens a slot.

The loop, the traffic, the window and the sampling of finished requests are
``serve_closed``'s own, the seeded leaves' rules ``serve_closed_hybrid``'s,
the reading of the trace by program scope ``serve_closed_longdoc``'s
(imported, not copied). What is this driver's:

* ``build_engine`` — ``HybridLM`` from the configuration's ``as_run`` sizes
  with ``n_mtp`` 1 and an engine with ``self_draft`` on (the workload file's
  ``engine.self_draft``; off, the same weights are served one token a step:
  the builder's comparison run): one decode program, one prefill program a
  bucket.
* the module's leaves — the hybrid driver's plain rules, as a main block's
  (``make_module``): no leaf of the model, the module's included, is shaped
  for drafting. Acceptance is what the shared key row gives independent
  logits; it is never forced and the draft's sampling rule is the target's.
* ``balanced_biases`` — every expert layer's choice bias, the module's too,
  is what ``noaux_tc``'s balancing rule comes to rest at, run by the
  REFERENCE on a seeded probe (as ``serve_closed_longdoc`` does and why):
  program and reference are handed the same arrays.
* ``reference_gaps`` — what the timed path served against
  ``references/deepseek_mtp.py``'s full teacher-forced forward on prompt +
  served tokens, one sequence at a time padded to the page's capacity. Three
  readings, each held twice, by ``serve_closed_hybrid``'s scheme (the mean
  and the lower quartile carry the tight limits, between the sound program's
  largest reading over the seeds and the fp8 control's smallest; the largest
  a loose one, against a fault that hits some rows only): (1) the gap by
  which a served greedy token's reference logit lies below the reference's
  best (``served_logit_gap``, ``served_logit_gap_largest``); (2) the live
  slots' MAIN logits of the window's last dispatch — after a prefill and
  hundreds of self-drafted rounds through the pages, rejected drafts' rows
  overwritten on the way — against the reference's at that position, as the
  root-mean-square difference over the reference's standard deviation there
  (``state_logit_rms`` the lower quartile over the rows,
  ``state_logit_rms_largest``); (3) the same rows' DRAFT logits — the module
  over its own page, from the main hidden state and the last emitted token —
  against the reference's module logits (``draft_logit_rms``,
  ``draft_logit_rms_largest``): a wrong module would otherwise only lower
  the acceptance and pass.
* what a round's bookkeeping must come to: every stream within its budget,
  every live slot's cursor at prompt + emitted - 1.

After the window the engine's parameters and pages are dropped before the
reference runs: a float32 layer and the program's 14 GB do not fit one chip
together.
"""
import collections
import functools
import gc
import time

import numpy as np

from benchmark.drivers import serve_closed as base
from benchmark.drivers import serve_closed_hybrid as hybrid
from benchmark.drivers import serve_closed_longdoc as longdoc
from benchmark.harness import runtime, weights
from benchmark.references import deepseek_mtp as ref
# a program from before ISSUE 33 has no such name: its run of a cell of this
# driver ends here, before any work on the device
from chainermn_tpu.models.hybrid import HybridLM, MTPModule  # noqa: F401

MODEL_KEYS = longdoc.MODEL_KEYS + ("n_mtp",)
REF_KEYS = ("n_heads", "d_head", "d_nope", "d_rope", "kv_rank", "rope_theta",
            "rope_scaling", "n_group", "topk_group", "top_k", "routed_scale",
            "held_lo", "norm_eps", "pattern")
MTP = "mtp_0"


# -- weights and engine ------------------------------------------------------
def model_and_spec(cfg, dtype):
    import jax

    model = HybridLM(pattern=tuple(tuple(p) for p in cfg["pattern"]),
                     dtype=dtype, **{k: cfg[k] for k in MODEL_KEYS})
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 8), np.int32))["params"])
    return model, weights.spec_of(shapes)


def module_spec(spec, n_layers):
    """The module's leaves as ``hybrid.make_block`` takes a block's: the
    layer after the last."""
    return {(f"block_{n_layers}",) + p[1:]: s for p, s in spec.items()
            if p[0] == MTP}


def make_module(seed, spec, cfg, dtype, sharding=None):
    """``mtp_0``'s tree: one stacked expert kernel's float32 normals at a
    time, as a block's."""
    n = cfg["n_layers"]
    return hybrid.make_block(seed, module_spec(spec, n), n, n, dtype,
                             sharding)


def main_spec(spec):
    return {p: s for p, s in spec.items() if p[0] != MTP}


def make_params(seed, spec, cfg, dtype, sharding=None):
    tree = hybrid.make_params(seed, main_spec(spec), cfg["n_layers"], dtype,
                              sharding)
    tree[MTP] = make_module(seed, spec, cfg, dtype, sharding)
    return tree


class Leaves(collections.namedtuple("Leaves", "spec biases")):
    """What regenerates the model's weights from the seed: the tree's
    ``{path: shape}`` and, beside the rules, the one leaf kind that is
    computed — ``biases[i]``, layer ``i``'s balanced router bias, the
    module's under ``n_layers``."""


def layer_maker(run, leaves, layer):
    """``(seed, i, bias) -> the layer's leaves`` by the rules (``i`` traced:
    a main layer of block ``layer``'s kind; ``layer == n_layers``: the
    module, ``i`` then ``n_layers``), the router bias handed in."""
    import jax.numpy as jnp

    cfg = run.config["as_run"]
    dtype, n = jnp.dtype(cfg["param_dtype"]), cfg["n_layers"]
    is_module = layer == n
    spec = module_spec(leaves.spec, n) if is_module else leaves.spec
    inner = tuple((p[1:], spec[p]) for p in hybrid.block_paths(spec, layer))

    def make(seed, i, bias=None):
        flat = {sub: hybrid.make_leaf(seed, i, hybrid.leaf_id(sub, n),
                                      ("block_0",) + sub, shape, dtype)
                for sub, shape in inner}
        at = ("block", "moe", "router_bias") if is_module else (
            "moe", "router_bias")
        if bias is not None and at in flat:
            flat[at] = bias
        return weights.unflatten(flat)

    return make


def ref_cfg(run):
    cfg = run.config["as_run"]
    return dict({k: cfg[k] for k in REF_KEYS},
                q_block=run.workload["check"]["q_block"])


def balanced_biases(run, spec):
    """{expert layer: its router bias [E] float32}, the module's under
    ``n_layers``: ``noaux_tc``'s balancing run to rest by the REFERENCE,
    layer after layer, on a seeded probe of ``check.balance_tokens`` tokens
    in ``check.balance_sequences`` sequences (each layer balanced on what
    the balanced layers before it pass on; the module on the main hidden
    states and the probe's following tokens). MANY sequences, as a decode
    round holds: a sequence's positions share a direction of their own (the
    attention's mean over one context), a router reading it prefers the
    same experts all along that sequence, and a bias run to rest on ONE
    sequence undoes that sequence's preference for every other one — some
    held expert is then reached in hardly any round, which ones by the seed,
    and the bytes a round reads move with the seed (PERF.md section 6, PR
    33)."""
    import jax
    import jax.numpy as jnp

    cfg, rcfg = run.config["as_run"], ref_cfg(run)
    chk, n = run.workload["check"], cfg["n_layers"]
    n_seq = chk["balance_sequences"]
    seed = weights.seed_word(run.seed)
    toks = np.random.RandomState(seed ^ 0xBA1A7CE).randint(
        0, cfg["vocab"], (n_seq, chk["balance_tokens"] // n_seq + 1),
        np.int32)
    bare = Leaves(spec, {})
    fns, out = {}, {}

    def balance(x, p):
        y = ref.ffn_input(x, p, rcfg)
        bias = ref.balance_bias(y.reshape(-1, y.shape[-1]), p, rcfg)
        return dict(p, router_bias=bias), bias

    def layer_fn(kind, layer):
        make = layer_maker(run, bare, layer)

        @jax.jit
        def f(seed, i, x):
            p = ref.canonical_layer(make(seed, i), upcast_experts=False)
            bias = jnp.zeros((0,), jnp.float32)
            if kind[1] == "moe":
                p, bias = balance(x, p)
            return ref.block(x, p, kind, rcfg), bias

        return f

    @jax.jit
    def module_fn(seed, x, nxt, rest):
        p = ref.canonical_mtp(layer_maker(run, bare, n)(seed, jnp.int32(n)),
                              upcast_experts=False)
        u = ref.mtp_input(x, ref.embed(nxt, ref.canonical_rest(rest)), p,
                          rcfg)
        return balance(u, p["block"])[1]

    with jax.default_matmul_precision("highest"):
        rest = hybrid.make_rest(run.seed, main_spec(spec), n,
                                jnp.dtype(cfg["param_dtype"]))
        x = jax.jit(lambda t, rest: ref.embed(
            t, ref.canonical_rest(rest)))(jnp.asarray(toks[:, :-1]), rest)
        for i, kind in enumerate(tuple(k) for k in cfg["pattern"]):
            if kind not in fns:
                fns[kind] = layer_fn(kind, i)
            x, bias = fns[kind](seed, jnp.int32(i), x)
            if bias.size:
                out[i] = bias
        out[n] = module_fn(seed, x, jnp.asarray(toks[:, 1:]), rest)
    return out


def build_engine(run):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from chainermn_tpu.serving import Engine, EngineConfig

    cfg, eng = run.config["as_run"], run.workload["engine"]
    mesh = Mesh(np.array(run.devices[:1]), ("serve",))
    sharding = NamedSharding(mesh, P())
    model, spec = model_and_spec(cfg, jnp.dtype(cfg["compute_dtype"]))
    leaves = Leaves(spec, balanced_biases(run, spec))
    params = make_params(run.seed, spec, cfg, jnp.dtype(cfg["param_dtype"]),
                         sharding)
    n = cfg["n_layers"]
    for i, bias in leaves.biases.items():
        blk = params[MTP]["block"] if i == n else params[f"block_{i}"]
        blk["moe"]["router_bias"] = jax.device_put(bias, sharding)
    engine = Engine(model, params, EngineConfig(
        n_slots=eng["n_slots"], capacity=eng["capacity"],
        buckets=tuple(eng["buckets"]), decode_k=eng["decode_k"],
        prefill_cohort=eng["prefill_cohort"],
        self_draft=eng["self_draft"]), mesh=mesh)
    return engine, leaves


# -- the comparison -----------------------------------------------------------
def reference_gaps(run, leaves, sample, live=(), live_logits=None,
                   draft_logits=None, quant=None):
    """The reference's logits on prompt + served tokens for the finished
    greedy ``sample`` (stamps) and the ``live`` (slot, request) pairs, one
    sequence and one layer at a time from the seeded weights; for the live
    ones the module's logits at the last position too. ``live_logits`` and
    ``draft_logits`` hold the program's rows of the live pairs. Returns the
    readings (module docstring) with what they were taken over; with
    ``quant`` also the control's (the reference computed with ``quant`` on
    every matmul operand, in the program's place)."""
    import jax
    import jax.numpy as jnp

    cfg, rcfg = run.config["as_run"], ref_cfg(run)
    chk = run.workload["check"]
    pad, pad_out = chk["reference_len"], chk["reference_out"]
    dtype = jnp.dtype(cfg["param_dtype"])
    n = cfg["n_layers"]
    seed = weights.seed_word(run.seed)
    reqs = [s.req for s in sample] + [r for _, r in live]
    ns = len(sample)
    kinds = [tuple(k) for k in cfg["pattern"]]
    no_bias = jnp.zeros((0,), jnp.float32)

    def layer_fn(kind, layer, q):
        make = layer_maker(run, leaves, layer)

        @functools.partial(jax.jit, donate_argnums=(2,))
        def f(seed, i, x, bias):
            p = ref.canonical_layer(make(seed, i, bias if bias.size else None),
                                    upcast_experts=False)
            return ref.block(x, p, kind, rcfg, q)

        return f

    def module_fn(q):
        make = layer_maker(run, leaves, n)

        @jax.jit
        def f(seed, x, nxt, at, bias, rest):
            """The module's logits at position ``at`` (its block runs the
            whole sequence: the position attends what lies before it)."""
            rest = ref.canonical_rest(rest)
            p = ref.canonical_mtp(make(seed, jnp.int32(n), bias),
                                  upcast_experts=False)
            hp = ref.mtp_hidden(x, ref.embed(nxt, rest), p, rcfg, q)
            return ref.mtp_logits(
                jax.lax.dynamic_index_in_dim(hp[0], at, 0, False), p, rest,
                rcfg, q)

        return f

    def forward(q):
        # embedding and head stay as stored and are upcast inside each call
        rest = hybrid.make_rest(run.seed, main_spec(leaves.spec), n, dtype)
        first = jax.jit(lambda toks, rest: ref.embed(
            toks, ref.canonical_rest(rest)))
        head = jax.jit(lambda x, rest: ref.head_logits(
            x, ref.canonical_rest(rest), rcfg, q))
        module = module_fn(q) if live else None
        fns, rows, drafts = {}, [], []
        with jax.default_matmul_precision("highest"):
            for j, r in enumerate(reqs):
                p, m = r.prompt.size, len(r.tokens)
                stream = np.concatenate([r.prompt,
                                         np.asarray(r.tokens, np.int32)])
                toks = np.zeros((2, pad), np.int32)
                toks[0, :stream.size - 1] = stream[:-1]
                toks[1, :stream.size - 1] = stream[1:]      # what follows
                x = first(jnp.asarray(toks[:1]), rest)
                for i, kind in enumerate(kinds):
                    if kind not in fns:
                        fns[kind] = layer_fn(kind, i, q)
                    x = fns[kind](seed, jnp.int32(i), x,
                                  leaves.biases.get(i, no_bias))
                at = np.minimum(p - 1 + np.arange(pad_out), pad - 1)
                rows.append(np.asarray(head(x[0][at], rest))[:m])
                if j >= ns:
                    drafts.append(np.asarray(module(
                        seed, x, jnp.asarray(toks[1:]),
                        jnp.int32(stream.size - 2), leaves.biases[n], rest)))
                del x
        return rows, drafts

    def gap_mean(picked):
        gaps = np.concatenate([w.max(-1) - w[np.arange(len(t)), t]
                               for w, t in zip(want[:ns], picked)])
        return float(gaps.mean()), float(gaps.max())

    def rms_rows(got, ref_rows):
        return sorted(float(np.sqrt(np.mean((g - w) ** 2)) / np.std(w))
                      for g, w in zip(got, ref_rows))

    want, want_draft = forward(ref.identity)
    want_live = [w[-1] for w in want[ns:]]
    served = [np.asarray(s.req.tokens) for s in sample]
    inf = float("inf")
    out = {"served_gap": inf, "served_gap_max": inf, "state_rms": inf,
           "state_rms_rows": [inf], "draft_rms": inf,
           "draft_rms_rows": [inf],
           "tokens": int(sum(map(len, served))), "live_rows": len(live),
           "positions": [r.prompt.size + len(r.tokens) - 1 for r in reqs]}
    if ns:
        out["served_gap"], out["served_gap_max"] = gap_mean(served)
    quartile = lambda rows: rows[len(rows) // 4]
    if live:
        rows = rms_rows(live_logits, want_live)
        out.update(state_rms=quartile(rows), state_rms_rows=rows)
        if draft_logits is not None:
            rows = rms_rows(draft_logits, want_draft)
            out.update(draft_rms=quartile(rows), draft_rms_rows=rows)
    if quant is not None:
        low, low_draft = forward(quant)
        if ns:
            out["control_gap"], out["control_gap_max"] = gap_mean(
                [l.argmax(-1) for l in low[:ns]])
        if live:
            rows = rms_rows([l[-1] for l in low[ns:]], want_live)
            out.update(control_rms=quartile(rows), control_rms_rows=rows)
            rows = rms_rows(low_draft, want_draft)
            out.update(control_draft_rms=quartile(rows),
                       control_draft_rms_rows=rows)
    return out


def bookkeeping(engine, stamps):
    """(streams over their budget, live slots whose cursor is not prompt +
    emitted - 1): both must be empty."""
    over = [s.req.request_id for s in stamps
            if len(s.req.tokens) > s.req.max_new_tokens]
    cursors = np.asarray(engine.steps.cursors())
    off = [(slot, int(cursors[slot]), r.prompt.size + len(r.tokens) - 1)
           for slot, r in sorted(engine.active.items())
           if cursors[slot] != r.prompt.size + len(r.tokens) - 1]
    return over, off


def acceptance_by_kind(stamps):
    """{"greedy" | "sampled": (drafts accepted, drafts proposed)} over the
    requests' own counters (``Request.drafts_*``, fed by the engine a
    dispatch)."""
    out = {"greedy": [0, 0], "sampled": [0, 0]}
    for s in stamps:
        kind = out["greedy" if s.greedy else "sampled"]
        kind[0] += s.req.drafts_accepted
        kind[1] += s.req.drafts_proposed
    return out


def after_window(run, engine, leaves, win, **kw):
    """Pick the samples, pull the live rows' logits, free the engine, run the
    reference. Returns ``reference_gaps``'s readings."""
    chk = run.workload["check"]
    sample = base.pick_sample(run.seed, win["completed"],
                              chk["sample_requests"])
    live = hybrid.live_sample(run.seed, engine, chk["sample_live"])
    slots = np.asarray([slot for slot, _ in live], np.int64)
    pull = lambda a: (None if a is None or not live
                      else np.asarray(a[slots]))
    live_logits = pull(engine.steps.last_decode_logits)
    draft_logits = pull(getattr(engine.steps, "last_draft_logits", None))
    engine.steps.last_draft_logits = None
    hybrid.drop_engine(engine)
    # not under run.reference(): that clock is taken off ``setup_s``, and
    # this reference runs after the window, outside set-up
    t0 = time.perf_counter()
    gaps = reference_gaps(run, leaves, sample, live, live_logits,
                          draft_logits, **kw)
    print(f"reference after the window: {time.perf_counter() - t0:.1f} s "
          f"({len(sample)} finished + {len(live)} live sequences of "
          f"{gaps['positions']} positions)", flush=True)
    return gaps


def run(run):
    w = run.workload
    tr, chk, eng = w["traffic"], w["check"], w["engine"]
    with run.spans.span("setup.build"):
        engine, leaves = build_engine(run)
    traffic = base.Traffic(run.seed, tr, run.config["as_run"]["vocab"])
    loop = base.ClosedLoop(engine, traffic, run.spans)
    with run.spans.span("setup.warm_up_and_ramp"):
        base.warm_up(run, engine, loop)
    submitted_before = traffic.j

    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        win = base.window(run, loop)
    finally:
        gc.enable()
        gc.unfreeze()
    peak = runtime.memory_peak_bytes(run.devices)
    scopes_s = None
    if run.traced:
        scopes_s = longdoc.scope_seconds(run)
        run.reduce_trace()

    stamps_in = [s for s in loop.done + loop.open if s.in_window]
    attempted = traffic.j - submitted_before
    steps = engine.steps
    traces = dict(decode_k=steps.decode_k_traces,
                  prefill=dict(steps.prefill_traces))
    queued_at_close = len(engine.queue)
    # an iteration's sample is taken after its step: one a ``loop.filled`` row
    rep = engine.report
    depth = dict(zip((t for t, _, _ in loop.filled),
                     rep.queue_depth_samples[-len(loop.filled):]))
    slot_bytes = steps.slot_bytes
    over, off = bookkeeping(engine, loop.done + loop.open)
    report = engine.report.summary()
    by_kind = acceptance_by_kind(loop.done + loop.open)
    gaps = after_window(run, engine, leaves, win)

    limits = chk["limits"]
    rows, drows = gaps["state_rms_rows"], gaps["draft_rms_rows"]
    print(f"state_logit_rms over {len(rows)} live rows: lower quartile "
          f"{gaps['state_rms']:.4f} median {rows[len(rows) // 2]:.4f} "
          f"largest {rows[-1]:.4f}; draft_logit_rms: lower quartile "
          f"{gaps['draft_rms']:.4f} median {drows[len(drows) // 2]:.4f} "
          f"largest {drows[-1]:.4f}; served_logit_gap over {gaps['tokens']} "
          f"tokens: mean {gaps['served_gap']:.4f} largest "
          f"{gaps['served_gap_max']:.4f}", flush=True)
    readings = [("served_logit_gap", gaps["served_gap"]),
                ("served_logit_gap_largest", gaps["served_gap_max"]),
                ("state_logit_rms", gaps["state_rms"]),
                ("state_logit_rms_largest", rows[-1])]
    if eng["self_draft"]:
        readings += [("draft_logit_rms", gaps["draft_rms"]),
                     ("draft_logit_rms_largest", drows[-1])]
    checks = [
        {"name": name, "value": value, "limit": limits[name],
         "ok": value <= limits[name]} for name, value in readings
    ] + [
        {"name": "served_tokens_compared", "value": gaps["tokens"],
         "limit": ">= %d" % chk["min_tokens"],
         "ok": gaps["tokens"] >= chk["min_tokens"]},
        {"name": "decode_k_traces", "value": traces["decode_k"],
         "limit": 1, "ok": traces["decode_k"] == 1},
        {"name": "prefill_traces_per_bucket",
         "value": max(traces["prefill"].values()), "limit": 1,
         "ok": max(traces["prefill"].values()) == 1},
        {"name": "prefill_buckets_compiled", "value": len(traces["prefill"]),
         "limit": chk["buckets_used"],
         "ok": len(traces["prefill"]) == chk["buckets_used"]},
        {"name": "programs_lowered_in_window",
         "value": run.compiles_in_window(), "limit": 0,
         "ok": run.compiles_in_window() == 0},
        {"name": "requests_accounted",
         "value": len(stamps_in) + win["failed"], "limit": attempted,
         "ok": len(stamps_in) + win["failed"] == attempted},
        {"name": "streams_over_budget", "value": len(over), "limit": 0,
         "ok": not over},
        {"name": "cursors_off_prompt_plus_emitted_less_1", "value": off[:4],
         "limit": [], "ok": not off},
    ]
    lo, hi = win["t0"], win["t0"] + win["elapsed"]
    trace_span = run.spans.named(runtime.trace_mod.WINDOW_ANNOTATION)
    facts = {
        "kind": "serve", "window_s": win["elapsed"], "tokens": win["tokens"],
        "ttft_s": [s.t_first - s.t_submit for s in stamps_in
                   if s.t_first is not None],
        "ttft_missing": sum(s.t_first is None for s in stamps_in),
        "queued_at_close": queued_at_close,
        "tpot_s": [(s.t_last - s.t_first) / (s.seen - 1)
                   for s in win["completed"] if s.seen > 1],
        "completed": len(win["completed"]),
        "occupancy": [o for t, _, o in loop.filled if lo <= t <= hi],
        "filled": [(t, n) for t, n, _ in loop.filled if lo <= t <= hi],
        "trace_span": trace_span[-1] if trace_span else None,
        "chips": 1, "peaks": run.peaks, "config": run.config, "workload": w,
        "trace": run.trace, "spans": run.spans, "slot_bytes": slot_bytes,
        "scopes_s": scopes_s,
    }
    iters = run.spans.named("engine.step", lo, hi)
    steps_ms = sorted(1e3 * (e - s) for s, e in iters)
    print(f"window iterations {len(steps_ms)}: engine.step ms median "
          f"{steps_ms[len(steps_ms) // 2]:.2f} mean "
          f"{sum(steps_ms) / len(steps_ms):.2f} lowest {steps_ms[0]:.2f} "
          f"highest {steps_ms[-1]:.2f}; outside engine.step "
          f"{1e3 * win['elapsed'] - sum(steps_ms):.1f} ms of the window",
          flush=True)
    # a run that stalls says where: one long iteration, or all of them slow
    longest = sorted(iters, key=lambda se: se[0] - se[1])[:3]
    print("longest iterations (ms at s into the window): " + ", ".join(
        f"{1e3 * (e - s):.1f} at {s - lo:.2f}" for s, e in longest),
        flush=True)
    occ = facts["occupancy"]
    share = lambda a: 100.0 * a[0] / a[1] if a[1] else float("nan")
    # while requests queue, an iteration admits the fixed round's next
    # cohort whatever the seed's acceptance: the regime the ramp is set for
    queue = [n for t, n in depth.items() if lo <= t <= hi]
    print(f"admission queue after each of the window's {len(queue)} "
          f"iterations: shallowest {min(queue, default=0)} first "
          f"{queue[0] if queue else 0} last {queue[-1] if queue else 0}; "
          f"occupancy first {100 * occ[0]:.1f}% last {100 * occ[-1]:.1f}%",
          flush=True)
    # what a caller feels per token: not a metric of the cell (the
    # benchmark's ``serve_tpot_p95_ms`` is ``sc2-3b-serve-batchgen``'s)
    tpot = sorted(1e3 * t for t in facts["tpot_s"])
    if tpot:
        print(f"tpot ms over {len(tpot)} completed: median "
              f"{tpot[len(tpot) // 2]:.2f} p95 "
              f"{tpot[min(len(tpot) - 1, int(0.95 * len(tpot)))]:.2f}",
              flush=True)
    print(f"self-drafting (the engine's whole life, ramp included): "
          f"acceptance {100 * report['acceptance_rate']:.2f}% of "
          f"{report['draft_tokens_proposed']} drafts, tokens a (slot, "
          f"round) {report['tokens_per_dispatch']:.4f}; greedy rows "
          f"{share(by_kind['greedy']):.2f}% of {by_kind['greedy'][1]}, "
          f"sampled rows {share(by_kind['sampled']):.2f}% of "
          f"{by_kind['sampled'][1]}; occupancy in the window mean "
          f"{100 * sum(occ) / max(len(occ), 1):.1f}% lowest "
          f"{100 * min(occ, default=0):.1f}%", flush=True)
    print(f"requests: attempted {attempted} completed {facts['completed']} "
          f"failed {win['failed']} no_first_token_yet {facts['ttft_missing']}"
          f" queued_at_close {queued_at_close} tokens {win['tokens']} "
          f"live_rows_compared {gaps.get('live_rows', 0)}", flush=True)
    return {"facts": facts, "checks": checks, "attempted": attempted,
            "failed": win["failed"], "memory_peak_bytes": peak}


def calibrate(run, seeds, control):
    """tools/calibrate.py: the readings seed by seed, each after a ramp and a
    window at the cell's own load (a fresh engine a seed: the reference needs
    the chip to itself); for the seeds in ``control`` also what the reference
    in fp8's precision gives in the program's place."""
    for seed in seeds:
        run.seed = seed
        engine, leaves = build_engine(run)
        loop = base.ClosedLoop(engine, base.Traffic(
            seed, run.workload["traffic"], run.config["as_run"]["vocab"]),
            run.spans)
        base.warm_up(run, engine, loop)
        win = base.window(run, loop)
        report = engine.report.summary()
        gaps = after_window(run, engine, leaves, win,
                            quant=ref.fake_fp8 if seed in control else None)
        gaps.update(seed=seed, completed=len(win["completed"]),
                    tokens_per_s=win["tokens"] / win["elapsed"],
                    acceptance=report["acceptance_rate"])
        del engine, loop
        gc.collect()
        yield gaps
