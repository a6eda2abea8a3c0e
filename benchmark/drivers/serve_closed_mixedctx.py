"""Driver: one ``serving.Engine`` with CHUNKED prefill over a model of K/V
pages and K/V rings with held experts (``laguna-xs.2``), under
``serve_closed``'s closed loop: long prompts (4k-30k) and short ones (64-480)
in ONE queue, 2,048 tokens an iteration beside the slots that decode — full
layers into capacity-long pages, window layers into rings of 512 columns.

The loop, the window and the stamps are ``serve_closed``'s own, the seeded
leaves' rules ``serve_closed_hybrid``'s, the warm-up and ramp, the wait for a
live slot and the reading of the trace by program scope
``serve_closed_longdoc``'s (imported, not copied). What is this driver's:

* ``round_of_sizes`` — the round is a MIX of two log-normals: every
  ``short_every``-th place short, the others long, interleaved; each kind's
  lengths are its own quantiles; every ``greedy_every``-th request of the
  round is greedy, the LAST of each ``greedy_every`` places (the odd ones of
  two): the short requests stand at odd places, so the comparison samples
  both kinds (``serve_closed``'s first-of-each would sample no short one).
* ``build_engine`` — ``HybridLM`` from the configuration's ``as_run`` sizes
  (the sizes that differ by layer kind among them) and an engine with
  ``prefill_chunk`` set: ONE chunk program ``[1, 2048]`` and one decode
  program serve every prompt length.
* ``balanced_biases`` — as ``serve_closed_longdoc`` does and why, run by
  THIS configuration's reference: the router's choice bias of each expert
  layer is what a bias-balancing rule comes to rest at on a seeded probe.
  Program and reference are handed the same arrays.
* ``reference_gaps`` — what the timed path served against
  ``references/laguna_mixed.py``'s full forward on prompt + served tokens,
  one sequence at a time padded to the least of ``check.reference_lens`` that
  holds it. The readings and why the mean and the lower quartile carry the
  limits are ``serve_closed_longdoc``'s: (1) the MEAN gap by which a served
  greedy token's reference logit lies below the reference's best
  (``served_logit_gap``) over two long requests and one SHORT one whose
  rings wrapped while it decoded; (2) the live slots' logits of the window's
  last decode dispatch and of the next few — after a prefill in 1 to 15
  chunks and up to 1,023 decode steps through pages and wrapped rings —
  against the reference's at that position, as the root-mean-square
  difference over the reference's standard deviation there: the lower
  quartile over the rows (``state_logit_rms``) and the largest over the
  slots of the slot's own lower quartile (``state_logit_rms_worst_slot``).
  ``window=False`` is the control that takes the window off the reference's
  sliding layers: a program whose rings are right then reads far off.

After the window the engine's parameters and pages are dropped before the
reference runs: a 32k-token float32 state and the program's 13 GB do not fit
one chip together.
"""
import functools
import gc
import math
import time

import numpy as np

from benchmark.drivers import serve_closed as base
from benchmark.drivers import serve_closed_hybrid as hybrid
from benchmark.drivers import serve_closed_longdoc as longdoc
from benchmark.harness import runtime, weights
from benchmark.references import laguna_mixed as ref
# a program from before ISSUE 39 has no such name: its run of a cell of this
# driver ends here, before any work on the device
from chainermn_tpu.models.hybrid import GQAMixer, HybridLM  # noqa: F401

MODEL_KEYS = ("vocab", "d_model", "n_heads", "d_head", "d_ff", "max_len",
              "n_kv_heads", "gqa_heads", "swa_heads", "gqa_rotary",
              "swa_rotary", "gqa_theta", "swa_theta", "gqa_scaling",
              "swa_scaling", "window", "attn_gate", "n_experts",
              "held_lo", "held_hi", "d_expert", "d_shared", "top_k",
              "n_group", "topk_group", "routed_scale", "norm_topk_prob",
              "norm_eps")
Leaves = longdoc.Leaves


# -- traffic -------------------------------------------------------------------
def round_of_sizes(tr):
    """One round: ``clients`` (prompt_len, max_new, greedy) triples from the
    workload file alone (module docstring). Output lengths an even grid over
    the whole round."""
    n, pl = tr["clients"], tr["prompt_len"]
    kinds = ["short" if i % pl["short_every"] == pl["short_every"] - 1
             else "long" for i in range(n)]
    rs = np.random.RandomState(tr["sizes_seed"])
    lengths = {}
    for kind in ("long", "short"):
        d, m = pl[kind], kinds.count(kind)
        qs = [int(min(d["max"], max(d["min"], round(d["median"] * math.exp(
            d["sigma"] * base.norm_ppf((i + 0.5) / m)))))) for i in range(m)]
        lengths[kind] = [qs[i] for i in rs.permutation(m)]
    ol = tr["output_len"]
    outs = [int(round(ol["min"] + (ol["max"] - ol["min"]) * i / (n - 1)))
            for i in rs.permutation(n)]
    every = tr["greedy_every"]
    left = {kind: iter(ls) for kind, ls in lengths.items()}
    return [(next(left[kind]), out, i % every == every - 1)
            for i, (kind, out) in enumerate(zip(kinds, outs))]


class Traffic(base.Traffic):
    """``serve_closed``'s stream of requests over the mixed round."""

    def __init__(self, seed, tr, vocab):
        self.tr, self.vocab = tr, vocab
        self.sizes = round_of_sizes(tr)
        self.rs = np.random.RandomState(weights.seed_word(seed) ^ 0x7AFF1C)
        self.j = 0


# -- weights and engine ----------------------------------------------------------
def model_and_spec(cfg, dtype):
    import jax

    model = HybridLM(pattern=tuple(tuple(p) for p in cfg["pattern"]),
                     dtype=dtype, **{k: cfg[k] for k in MODEL_KEYS})
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 8), np.int32))["params"])
    return model, weights.spec_of(shapes)


def block_maker(run, leaves, kind, layer):
    """``(seed, i, bias) -> block_i's leaves`` (``i`` traced, a layer of
    ``kind``), by the hybrid driver's rules and with the router bias handed
    in."""
    import jax.numpy as jnp

    cfg = run.config["as_run"]
    dtype, n_layers = jnp.dtype(cfg["param_dtype"]), cfg["n_layers"]
    inner = tuple((p[1:], leaves.spec[p])
                  for p in hybrid.block_paths(leaves.spec, layer))

    def make(seed, i, bias=None):
        blk = weights.unflatten({
            sub: hybrid.make_leaf(seed, i, hybrid.leaf_id(sub, n_layers),
                                  ("block_0",) + sub, shape, dtype)
            for sub, shape in inner})
        if kind[1] == "moe" and bias is not None:
            blk["moe"] = dict(blk["moe"], router_bias=bias)
        return blk

    return make


def ref_cfg(run, window=True):
    """The reference's sizes from ``as_run``; ``window=False`` takes the
    window off its sliding layers (the control)."""
    cfg = run.config["as_run"]
    kinds = {"gqa": {"n_heads": cfg["gqa_heads"], "rotary": cfg["gqa_rotary"],
                     "theta": cfg["gqa_theta"],
                     "scaling": cfg["gqa_scaling"]},
             "swa": {"n_heads": cfg["swa_heads"], "rotary": cfg["swa_rotary"],
                     "theta": cfg["swa_theta"],
                     "scaling": cfg["swa_scaling"],
                     "window": cfg["window"] if window else 0}}
    return dict({k: cfg[k] for k in ("n_kv_heads", "d_head", "top_k",
                                     "routed_scale", "norm_eps", "pattern")},
                kinds=kinds, q_block=run.workload["check"]["q_block"])


def balanced_biases(run, spec):
    """{expert layer: its router bias [E] float32}: the balancing run to
    rest by the REFERENCE, layer after layer, on a seeded probe of
    ``check.balance_tokens`` tokens (each layer balanced on what the
    balanced layers before it pass on)."""
    import jax
    import jax.numpy as jnp

    cfg, rcfg = run.config["as_run"], ref_cfg(run)
    n = run.workload["check"]["balance_tokens"]
    seed = weights.seed_word(run.seed)
    toks = np.random.RandomState(seed ^ 0xBA1A7CE).randint(
        0, cfg["vocab"], (1, n), np.int32)
    bare = Leaves(spec, {})
    fns, out = {}, {}

    def layer_fn(kind, layer):
        make = block_maker(run, bare, kind, layer)

        @jax.jit
        def f(seed, i, x):
            p = ref.canonical_layer(make(seed, i), upcast_experts=False)
            bias = jnp.zeros((0,), jnp.float32)
            if kind[1] == "moe":
                y = ref.ffn_input(x, p, kind, rcfg)
                bias = ref.balance_bias(y.reshape(-1, y.shape[-1]), p, rcfg)
                p = dict(p, router_bias=bias)
            return ref.block(x, p, kind, rcfg), bias

        return f

    with jax.default_matmul_precision("highest"):
        rest = hybrid.make_rest(run.seed, spec, cfg["n_layers"],
                                jnp.dtype(cfg["param_dtype"]))
        x = jax.jit(lambda t, rest: ref.embed(
            t, ref.canonical_rest(rest), rcfg))(jnp.asarray(toks), rest)
        for i, kind in enumerate(tuple(k) for k in cfg["pattern"]):
            if kind not in fns:
                fns[kind] = layer_fn(kind, i)
            x, bias = fns[kind](seed, jnp.int32(i), x)
            if bias.size:
                out[i] = bias
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
    params = hybrid.make_params(run.seed, spec, cfg["n_layers"],
                                jnp.dtype(cfg["param_dtype"]), sharding)
    for i, bias in leaves.biases.items():
        params[f"block_{i}"]["moe"]["router_bias"] = jax.device_put(
            bias, sharding)
    engine = Engine(model, params, EngineConfig(
        n_slots=eng["n_slots"], capacity=eng["capacity"],
        buckets=tuple(eng["buckets"]), decode_k=eng["decode_k"],
        prefill_cohort=eng["prefill_cohort"],
        prefill_chunk=eng["prefill_chunk"],
        token_budget=eng["token_budget"]), mesh=mesh)
    return engine, leaves


# -- the comparison -----------------------------------------------------------
def wrapped_short(run, r):
    """A short request whose rings wrapped while it decoded: it started to
    decode inside the window and has passed it."""
    w = run.config["as_run"]["window"]
    return r.prompt.size < w <= r.prompt.size + len(r.tokens) - 1


def pick_sample(run, completed, k):
    """``k`` greedy requests the window finished: the wrapped short one with
    the most positions, the longest of all, a seeded draw of the others."""
    greedy = [s for s in completed if s.greedy]
    size = lambda s: s.req.prompt.size + len(s.req.tokens)
    short = sorted((s for s in greedy if wrapped_short(run, s.req)), key=size)
    picks = short[-1:]
    rest = [s for s in greedy if s not in picks]
    if rest and len(picks) < k:
        longest = max(rest, key=size)
        picks.append(longest)
        rest = [s for s in rest if s is not longest]
    rs = np.random.RandomState(weights.seed_word(run.seed) ^ 0xC0FFEE)
    return picks + [rest[i] for i in rs.permutation(len(rest))[
        :max(k - len(picks), 0)]]


def live_captures(run, loop):
    """``serve_closed_longdoc.live_captures`` with the choice of slots this
    traffic needs: a short request past its window if one is decoding, then
    the slots with the most answer left."""
    chk = run.workload["check"]
    engine = loop.engine
    extra = longdoc.settle(loop)
    if extra:
        print(f"no slot was decoding at the close: {extra} more iterations "
              "outside the window before the live logits were taken",
              flush=True)
    live = sorted(((slot, r) for slot, r in engine.active.items()
                   if len(r.tokens) >= 2),
                  key=lambda sr: len(sr[1].tokens) - sr[1].max_new_tokens)
    ahead = chk["live_dispatches"] * run.workload["engine"]["decode_k"]
    short = [sr for sr in live if wrapped_short(run, sr[1])
             and sr[1].max_new_tokens - len(sr[1].tokens) > ahead]
    picked = (short[:1] + [sr for sr in live if sr not in short[:1]])[
        :chk["sample_live"]]
    caps = []
    for d in range(chk["live_dispatches"]):
        logits = engine.steps.last_decode_logits        # on the device
        for slot, r in picked:
            seen = any(q is r and n == len(r.tokens) for q, n, _ in caps)
            if engine.active.get(slot) is r and not seen:
                caps.append((r, len(r.tokens), np.asarray(logits[slot])))
        if d + 1 < chk["live_dispatches"]:
            loop.iterate()
    return caps


def reference_gaps(run, leaves, sample, captures=(), quant=None, window=True):
    """The reference's logits on prompt + served tokens for the finished
    greedy ``sample`` (stamps) and the requests of the live ``captures``,
    one sequence and one layer at a time from the seeded weights. Returns
    the readings (module docstring) with what they were taken over; with
    ``quant`` also the control's (the reference computed with ``quant`` on
    every matmul operand, in the program's place); with ``window=False``
    the reference itself has no window."""
    import jax
    import jax.numpy as jnp

    cfg, rcfg = run.config["as_run"], ref_cfg(run, window)
    chk = run.workload["check"]
    pads, pad_out = sorted(chk["reference_lens"]), chk["reference_out"]
    dtype = jnp.dtype(cfg["param_dtype"])
    n_layers = cfg["n_layers"]
    seed = weights.seed_word(run.seed)
    reqs = [s.req for s in sample]
    ns = len(reqs)
    for r, _, _ in captures:
        if not any(r is q for q in reqs[ns:]):
            reqs.append(r)
    kinds = [tuple(k) for k in cfg["pattern"]]
    no_bias = jnp.zeros((0,), jnp.float32)

    def layer_fn(kind, layer, q):
        make = block_maker(run, leaves, kind, layer)

        @functools.partial(jax.jit, donate_argnums=(2,))
        def f(seed, i, x, n_real, bias):
            p = ref.canonical_layer(make(seed, i, bias),
                                    upcast_experts=False)
            return ref.block(x, p, kind, rcfg, q, n_real)

        return f

    def forward(q):
        # embedding and head stay as stored and are upcast inside each call
        rest = hybrid.make_rest(run.seed, leaves.spec, n_layers, dtype)
        first = jax.jit(lambda toks, rest: ref.embed(
            toks, ref.canonical_rest(rest), rcfg))
        head = jax.jit(lambda x, rest: ref.head_logits(
            x, ref.canonical_rest(rest), rcfg, q))
        fns, rows = {}, []
        with jax.default_matmul_precision("highest"):
            for r in reqs:
                p, n = r.prompt.size, len(r.tokens)
                seq = np.concatenate([r.prompt,
                                      np.asarray(r.tokens[:-1], np.int32)])
                pad = next(x for x in pads if x >= seq.size)
                toks = np.zeros((1, pad), np.int32)
                toks[0, :seq.size] = seq
                x = first(jnp.asarray(toks), rest)
                for i, kind in enumerate(kinds):
                    if kind not in fns:
                        fns[kind] = layer_fn(kind, i, q)
                    x = fns[kind](seed, jnp.int32(i), x, jnp.int32(seq.size),
                                  leaves.biases.get(i, no_bias))
                at = np.minimum(p - 1 + np.arange(pad_out), pad - 1)
                rows.append(np.asarray(head(x[0][at], rest))[:n])
                del x
        return rows

    def gap_mean(picked):
        gaps = np.concatenate([w.max(-1) - w[np.arange(len(t)), t]
                               for w, t in zip(want[:ns], picked)])
        return float(gaps.mean()), float(gaps.max())

    def row_of(rows, r, n):
        """The logits that produced ``r``'s ``n``-th token."""
        return rows[next(i for i in range(ns, len(reqs))
                         if reqs[i] is r)][n - 1]

    def rms_rows(got):
        """Per capture: rms of (got - reference) over the reference's
        standard deviation at that position. Returns (all rows sorted, the
        rows of each request sorted, the largest over the requests with at
        least 4 rows of the request's own lower quartile)."""
        rows = [float(np.sqrt(np.mean((g - row_of(want, r, n)) ** 2))
                      / np.std(row_of(want, r, n)))
                for (r, n, _), g in zip(captures, got)]
        by_req = [sorted(x for x, (r, _, _) in zip(rows, captures) if r is q)
                  for q in reqs[ns:]]
        whole = [x for x in by_req if len(x) >= 4] or [sorted(rows)]
        return sorted(rows), by_req, max(x[len(x) // 4] for x in whole)

    want = forward(ref.identity)
    served = [np.asarray(s.req.tokens) for s in sample]
    inf = float("inf")
    out = {"served_gap": inf, "served_gap_max": inf, "state_rms": inf,
           "state_rms_rows": [inf], "state_rms_slot": inf,
           "tokens": int(sum(map(len, served))),
           "live_rows": len(captures),
           "positions": [r.prompt.size + len(r.tokens) - 1 for r in reqs],
           "wrapped_short": sum(wrapped_short(run, r) for r in reqs)}
    if ns:
        out["served_gap"], out["served_gap_max"] = gap_mean(served)
    if captures:
        rows, by_req, slot = rms_rows([g for _, _, g in captures])
        out.update(state_rms=rows[len(rows) // 4], state_rms_rows=rows,
                   state_rms_by_request=by_req, state_rms_slot=slot)
    if quant is not None:
        low = forward(quant)
        if ns:
            out["control_gap"], out["control_gap_max"] = gap_mean(
                [l.argmax(-1) for l in low[:ns]])
        if captures:
            ctl = [row_of(low, r, n) for r, n, _ in captures]
            rows, by_req, slot = rms_rows(ctl)
            out.update(control_rms=rows[len(rows) // 4],
                       control_rms_rows=rows, control_rms_slot=slot,
                       control_rms_by_request=by_req)
            # the control in ONE slot's place, the program's rows in the
            # others: (lower quartile, worst slot) for each choice of slot
            out["control_one_slot"] = [
                (mixed[0][len(mixed[0]) // 4], mixed[2])
                for mixed in (rms_rows([
                    c if r is q else g
                    for (r, _, g), c in zip(captures, ctl)])
                    for q in reqs[ns:])]
    return out


def after_window(run, loop, leaves, win, **kw):
    """Pick the samples, pull the live logits, free the engine, run the
    reference. Returns ``reference_gaps``'s readings."""
    chk = run.workload["check"]
    sample = pick_sample(run, win["completed"], chk["sample_requests"])
    caps = live_captures(run, loop)
    hybrid.drop_engine(loop.engine)
    # not under run.reference(): that clock is taken off ``setup_s``, and
    # this reference runs after the window, outside set-up
    t0 = time.perf_counter()
    gaps = reference_gaps(run, leaves, sample, caps, **kw)
    print(f"reference after the window: {time.perf_counter() - t0:.1f} s "
          f"({len(sample)} finished sequences and {len(caps)} live rows, "
          f"sequences of {gaps['positions']} positions, "
          f"{gaps['wrapped_short']} of them short ones that wrapped)",
          flush=True)
    return gaps


def run(run):
    w = run.workload
    tr, chk, eng = w["traffic"], w["check"], w["engine"]
    with run.spans.span("setup.build"):
        engine, leaves = build_engine(run)
    traffic = Traffic(run.seed, tr, run.config["as_run"]["vocab"])
    loop = base.ClosedLoop(engine, traffic, run.spans)
    with run.spans.span("setup.warm_up_and_ramp"):
        longdoc.warm_up(run, engine, loop)
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
                  chunk=dict(steps.prefill_chunk_traces),
                  prefill=dict(steps.prefill_traces))
    queued_at_close = len(engine.queue)
    phase = dict(prefilling=len(engine.prefilling),
                 decoding=len(engine.active))
    slot_bytes = steps.slot_bytes
    gaps = after_window(run, loop, leaves, win)

    limits = chk["limits"]
    rows = gaps["state_rms_rows"]
    print(f"state_logit_rms over {len(rows)} live rows: lower quartile "
          f"{gaps['state_rms']:.4f} median {rows[len(rows) // 2]:.4f} "
          f"largest {rows[-1]:.4f}, by request "
          + "; ".join(" ".join(f"{x:.3f}" for x in req)
                      for req in gaps.get("state_rms_by_request", []))
          + f"; served_logit_gap over {gaps['tokens']} tokens: mean "
          f"{gaps['served_gap']:.4f} largest {gaps['served_gap_max']:.4f}",
          flush=True)
    shape = (eng["prefill_cohort"], eng["prefill_chunk"])
    checks = [
        {"name": name, "value": value, "limit": limits[name],
         "ok": value <= limits[name]}
        for name, value in (
            ("served_logit_gap", gaps["served_gap"]),
            ("state_logit_rms", gaps["state_rms"]),
            ("state_logit_rms_worst_slot", gaps["state_rms_slot"]))
    ] + [
        {"name": "served_tokens_compared", "value": gaps["tokens"],
         "limit": ">= %d" % chk["min_tokens"],
         "ok": gaps["tokens"] >= chk["min_tokens"]},
        {"name": "wrapped_short_requests_compared",
         "value": gaps["wrapped_short"], "limit": ">= 1",
         "ok": gaps["wrapped_short"] >= 1},
        {"name": "decode_k_traces", "value": traces["decode_k"],
         "limit": 1, "ok": traces["decode_k"] == 1},
        {"name": "chunk_programs",
         "value": sorted([list(k), n] for k, n in traces["chunk"].items()),
         "limit": [[list(shape), 1]],
         "ok": traces["chunk"] == {shape: 1} and not traces["prefill"]},
        {"name": "programs_lowered_in_window",
         "value": run.compiles_in_window(), "limit": 0,
         "ok": run.compiles_in_window() == 0},
        {"name": "requests_accounted",
         "value": len(stamps_in) + win["failed"], "limit": attempted,
         "ok": len(stamps_in) + win["failed"] == attempted},
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
    done = win["completed"]
    window_len = run.config["as_run"]["window"]
    print(f"requests: attempted {attempted} completed {facts['completed']} "
          f"({sum(s.req.prompt.size < window_len for s in done)} short) "
          f"failed {win['failed']} no_first_token_yet {facts['ttft_missing']}"
          f" queued_at_close {queued_at_close} prefilling_at_close "
          f"{phase['prefilling']} decoding_at_close {phase['decoding']} "
          f"tokens {win['tokens']} live_rows_compared "
          f"{gaps.get('live_rows', 0)}", flush=True)
    return {"facts": facts, "checks": checks, "attempted": attempted,
            "failed": win["failed"], "memory_peak_bytes": peak}


def calibrate(run, seeds, control):
    """tools/calibrate.py: the readings seed by seed, each after a ramp and
    a window at the cell's own load (a fresh engine a seed: the reference
    needs the chip to itself); for the seeds in ``control`` also both
    controls: what the reference in fp8's precision gives in the program's
    place, and the program against a reference without the window."""
    for seed in seeds:
        run.seed = seed
        engine, leaves = build_engine(run)
        loop = base.ClosedLoop(engine, Traffic(
            seed, run.workload["traffic"], run.config["as_run"]["vocab"]),
            run.spans)
        longdoc.warm_up(run, engine, loop)
        win = base.window(run, loop)
        chk = run.workload["check"]
        sample = pick_sample(run, win["completed"], chk["sample_requests"])
        caps = live_captures(run, loop)
        hybrid.drop_engine(loop.engine)
        gaps = reference_gaps(
            run, leaves, sample, caps,
            quant=ref.fake_fp8 if seed in control else None)
        if seed in control:
            wide = reference_gaps(run, leaves, sample, caps, window=False)
            gaps.update(no_window_gap=wide["served_gap"],
                        no_window_rms=wide["state_rms"],
                        no_window_rms_slot=wide["state_rms_slot"])
        gaps.update(seed=seed, completed=len(win["completed"]),
                    tokens_per_s=win["tokens"] / win["elapsed"])
        del engine, loop
        gc.collect()
        yield gaps
