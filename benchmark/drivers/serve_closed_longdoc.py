"""Driver: one ``serving.Engine`` with CHUNKED prefill over a latent-attention
model with hyper-connections and held experts (``xing4.0-29b-a4b``), under
``serve_closed``'s closed loop: 8k-32k prompts stream into a latent page
2,048 tokens an iteration beside the slots that decode.

The loop, the traffic, the window and the sampling of finished requests are
``serve_closed``'s own, the seeded leaves' rules ``serve_closed_hybrid``'s
(imported, not copied). What is this driver's:

* ``build_engine`` — ``HybridLM`` from the configuration's ``as_run`` sizes
  (every layer latent attention, ``hc_mult`` streams, all 64 experts held)
  and an engine with ``prefill_chunk`` set: ONE chunk program ``[1, 2048]``
  and one decode program serve every prompt length.
* ``seeded`` — this configuration's rule on top of the hybrid driver's:
  ``b_res`` is ``N(0, 1/4) + 2 I``, so that the stream-mixing matrix is led
  by its diagonal (about 0.7) as a trained residual path is, and still mixes.
* ``balanced_biases`` — the router's choice bias of each expert layer is what
  ``noaux_tc``'s own balancing rule comes to rest at, run by the REFERENCE on
  a seeded probe of 2,048 tokens, from the rule's 0.02 N. A trained model of
  this kind arrives balanced; seeded routers without it send a tenth of the
  experts most of the pairs, how many tiles the prefill's grouped product
  runs and how many experts a decode step reads then hang on the seed, and
  six seeds' tokens per second spread by 0.54% where two sets with it read
  0.19% and 0.12% (my chip runs, PR 31). (`serve_moe_load_max_over_mean`
  does not show the difference here: it is read on decode dispatches, whose
  2 live slots give 8 pairs to 64 experts, so it reads 8 or more whatever
  the router does.) Program and reference are handed the same arrays
  (``Leaves.biases``).
* ``reference_gaps`` — what the timed path served against
  ``references/xing_mhc.py``'s full forward on prompt + served tokens, 8k-33k
  positions, ONE SEQUENCE AT A TIME padded to the page's capacity (one shape:
  one compile a layer kind; the reference's attention skips the blocks of
  queries past the sequence's end). The readings and why the mean and the
  lower quartile carry the tight limits and the largest only loose ones are
  ``serve_closed_hybrid``'s: a bfloat16 program and a float32 reference order
  a near-tied pair of expert scores differently, here the 4th and 5th of 64.
  (1) the MEAN gap by which a served greedy token's reference logit lies
  below the reference's best (``served_logit_gap``; the largest is printed
  and not held: a position whose routing flipped in an early layer reads as
  far off as a token that is not the program's, 4 of 131,072 unit-variance
  logits' 4.5); (2) the live slots' logits of the window's last decode
  dispatch and of the next 5, which run after the window untimed — after a
  prefill in 5 to 16 chunks and up to 255 decode steps through the page —
  against the reference's logits at that position, as the root-mean-square
  difference over the reference's standard deviation there: the lower
  quartile over the dozen rows (``state_logit_rms``) and, so that ONE slot
  whose page is wrong shows, the largest over the slots of the slot's own
  lower quartile (``state_logit_rms_worst_slot``; a slot with under 4 rows
  is left to the first). Here a flip does not ride a recurrent state into
  every later position; it cascades DOWN the layers of its own position
  (the later routers see a moved state: the program's state is 1-5% off
  the reference's by the later layers, the 4th and 5th of 64 scores lie
  0.024 apart in the mean, so a third of the late layers' choices differ),
  so rows are clean (0.05) or flipped (0.2 for a late layer to 1.0 for an
  early one) and the largest ROW reads what the fp8 control reads.
* the device seconds of the traced sub-window by program scope
  (``mhc_mix``, ``mla_chunk``, ...), read with ``tools/scope_table.py``'s
  decoder before the trace is reduced: ``facts["scopes_s"]``.

After the window the engine's parameters and pages are dropped before the
reference runs: a 33k-token float32 state and the program's 14.3 GB do not
fit one chip together.
"""
import collections
import functools
import gc
import os
import time

import numpy as np

from benchmark.drivers import serve_closed as base
from benchmark.drivers import serve_closed_hybrid as hybrid
from benchmark.harness import runtime, weights
from benchmark.harness import trace as trace_mod
from benchmark.references import xing_mhc as ref
from benchmark.tools import scope_table
# a program from before ISSUE 31 has no such name: its run of a cell of this
# driver ends here, before any work on the device
from chainermn_tpu.models.hybrid import HybridLM, latent_chunk_attention  # noqa: F401

MODEL_KEYS = ("vocab", "d_model", "n_heads", "d_head", "d_ff", "max_len",
              "d_nope", "d_rope", "kv_rank", "q_rank", "mla_gate",
              "rope_theta", "rope_scaling", "n_experts", "held_lo", "held_hi",
              "d_expert", "d_shared", "top_k", "n_group", "topk_group",
              "routed_scale", "norm_topk_prob", "norm_eps", "hc_mult",
              "hc_sinkhorn_iters", "hc_eps", "hc_clamp", "mla_block")
REF_KEYS = ("n_heads", "d_head", "d_nope", "d_rope", "kv_rank", "rope_theta",
            "rope_scaling", "top_k", "routed_scale", "norm_eps", "hc_mult",
            "hc_sinkhorn_iters", "hc_eps", "hc_clamp", "pattern")
B_RES_DIAGONAL = 2.0


# -- weights and engine ------------------------------------------------------
def seeded(blk):
    """A block's leaves by the hybrid driver's rules -> this configuration's:
    ``b_res`` led by its diagonal."""
    import jax.numpy as jnp

    out = dict(blk)
    for name in ("hc_mix", "hc_ffn"):
        b_res = blk[name]["b_res"]
        out[name] = dict(blk[name], b_res=b_res + B_RES_DIAGONAL * jnp.eye(
            b_res.shape[-1], dtype=b_res.dtype))
    return out


def model_and_spec(cfg, dtype):
    import jax

    model = HybridLM(pattern=tuple(tuple(p) for p in cfg["pattern"]),
                     dtype=dtype, **{k: cfg[k] for k in MODEL_KEYS})
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 8), np.int32))["params"])
    return model, weights.spec_of(shapes)


def make_params(seed, spec, n_layers, dtype, sharding=None):
    tree = hybrid.make_params(seed, spec, n_layers, dtype, sharding)
    for i in range(n_layers):
        tree[f"block_{i}"] = seeded(tree[f"block_{i}"])
    return tree


class Leaves(collections.namedtuple("Leaves", "spec biases")):
    """What regenerates the model's weights from the seed: the tree's
    ``{path: shape}`` and, beside the rules, the one leaf kind that is
    computed — ``biases[i]``, layer ``i``'s balanced router bias."""


def block_maker(run, leaves, kind, layer):
    """``(seed, i, bias) -> block_i's leaves`` (``i`` traced, a layer of
    ``kind``), by the rules and with the router bias handed in."""
    import jax.numpy as jnp

    cfg = run.config["as_run"]
    dtype, n_layers = jnp.dtype(cfg["param_dtype"]), cfg["n_layers"]
    inner = tuple((p[1:], leaves.spec[p])
                  for p in hybrid.block_paths(leaves.spec, layer))

    def make(seed, i, bias=None):
        blk = seeded(weights.unflatten({
            sub: hybrid.make_leaf(seed, i, hybrid.leaf_id(sub, n_layers),
                                  ("block_0",) + sub, shape, dtype)
            for sub, shape in inner}))
        if kind[1] == "moe" and bias is not None:
            blk["moe"] = dict(blk["moe"], router_bias=bias)
        return blk

    return make


def ref_cfg(run):
    cfg = run.config["as_run"]
    return dict({k: cfg[k] for k in REF_KEYS},
                q_block=run.workload["check"]["q_block"])


def balanced_biases(run, spec):
    """{expert layer: its router bias [E] float32}: ``noaux_tc``'s balancing
    run to rest by the REFERENCE, layer after layer, on a seeded probe of
    ``check.balance_tokens`` tokens (each layer balanced on what the
    balanced layers before it pass on). The program and the reference are
    both handed these arrays."""
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
    params = make_params(run.seed, spec, cfg["n_layers"],
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


def warm_up(run, engine, loop):
    """Both programs, once: a prompt of a chunk and a token (two chunk
    dispatches, the second final) decoded for two dispatches; then the loop
    itself for ``ramp_iterations`` scheduler iterations."""
    tr, eng = run.workload["traffic"], run.workload["engine"]
    engine.submit(np.random.RandomState(0).randint(
        0, 100, (eng["prefill_chunk"] + 1,), np.int32),
        max_new_tokens=eng["decode_k"] + 2,
        temperature=tr["temperature"], top_k=tr["top_k"])
    engine.run_until_drained()
    for _ in range(tr["clients"]):
        loop.submit()
    for _ in range(tr["ramp_iterations"]):
        loop.iterate()


# -- the comparison -----------------------------------------------------------
def reference_gaps(run, leaves, sample, captures=(), quant=None,
                   margins=False):
    """The reference's logits on prompt + served tokens for the finished
    greedy ``sample`` (stamps) and the requests of the live ``captures``
    (``live_captures``), one sequence and one layer at a time from the
    seeded weights. Returns the readings (module docstring) with what they
    were taken over; with ``quant`` also the control's (the reference
    computed with ``quant`` on every matmul operand, in the program's
    place); with ``margins`` the share of routed positions whose 4th and 5th
    biased scores lie within 1e-3."""
    import jax
    import jax.numpy as jnp

    cfg, rcfg = run.config["as_run"], ref_cfg(run)
    chk = run.workload["check"]
    pad, pad_out = chk["reference_len"], chk["reference_out"]
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
            out = ref.block(x, p, kind, rcfg, q, n_real)
            if margins and kind[1] == "moe":
                y = ref.ffn_input(x, p, kind, rcfg)
                return out, ref.route_margin(
                    y.reshape(-1, y.shape[-1]), p, rcfg)
            return out, no_bias

        return f

    def forward(q):
        # embedding and head stay as stored and are upcast inside each call:
        # two float32 copies of 131,072 rows would hold 3.8 GB throughout
        rest = hybrid.make_rest(run.seed, leaves.spec, n_layers, dtype)
        first = jax.jit(lambda toks, rest: ref.embed(
            toks, ref.canonical_rest(rest), rcfg))
        head = jax.jit(lambda x, rest: ref.head_logits(
            x, ref.canonical_rest(rest), rcfg, q))
        fns, near, routed, rows = {}, 0, 0, []
        with jax.default_matmul_precision("highest"):
            for r in reqs:
                p, n = r.prompt.size, len(r.tokens)
                seq = np.concatenate([r.prompt,
                                      np.asarray(r.tokens[:-1], np.int32)])
                toks = np.zeros((1, pad), np.int32)
                toks[0, :seq.size] = seq
                x = first(jnp.asarray(toks), rest)
                for i, kind in enumerate(kinds):
                    if kind not in fns:
                        fns[kind] = layer_fn(kind, i, q)
                    x, m = fns[kind](seed, jnp.int32(i), x,
                                     jnp.int32(seq.size),
                                     leaves.biases.get(i, no_bias))
                    if m.size:
                        near += int((np.asarray(m)[:seq.size] < 1e-3).sum())
                        routed += seq.size
                at = np.minimum(p - 1 + np.arange(pad_out), pad - 1)
                rows.append(np.asarray(head(x[0].sum(1)[at], rest))[:n])
                del x
        return rows, (near, routed)

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

    want, (near, routed) = forward(ref.identity)
    served = [np.asarray(s.req.tokens) for s in sample]
    inf = float("inf")
    out = {"served_gap": inf, "served_gap_max": inf, "state_rms": inf,
           "state_rms_rows": [inf], "state_rms_slot": inf,
           "tokens": int(sum(map(len, served))),
           "live_rows": len(captures),
           "positions": [r.prompt.size + len(r.tokens) - 1 for r in reqs]}
    if ns:
        out["served_gap"], out["served_gap_max"] = gap_mean(served)
    if captures:
        rows, by_req, slot = rms_rows([g for _, _, g in captures])
        out.update(state_rms=rows[len(rows) // 4], state_rms_rows=rows,
                   state_rms_by_request=by_req, state_rms_slot=slot)
    if margins:
        out["near_tie_share"] = near / max(routed, 1)
    if quant is not None:
        low, _ = forward(quant)
        if ns:
            out["control_gap"], out["control_gap_max"] = gap_mean(
                [l.argmax(-1) for l in low[:ns]])
        if captures:
            rows, _, slot = rms_rows([row_of(low, r, n)
                                      for r, n, _ in captures])
            out.update(control_rms=rows[len(rows) // 4],
                       control_rms_rows=rows, control_rms_slot=slot)
    return out


def settle(loop, limit=64):
    """The window may close on an iteration in which no slot decodes (one
    prompt prefills at a time and an answer is short, so 0 to 5 slots decode
    at once): go on, untimed and uncounted, until one has decoded two tokens,
    so that there is a live page to compare. Returns the iterations run."""
    n = 0
    while n < limit and not any(len(r.tokens) >= 2
                                for r in loop.engine.active.values()):
        loop.iterate()
        n += 1
    return n


def live_captures(run, loop):
    """The live slots' logits of the window's last decode dispatch and of the
    next ``check.live_dispatches - 1``, which run after the window, untimed
    and uncounted: ``[(request, tokens it had then, its logits row)]`` for
    up to ``check.sample_live`` of the slots decoding at the close. One
    reference forward a request serves all its captures, and a quartile
    over a dozen rows does not hang on whether the window closed on one
    live slot or on three."""
    chk = run.workload["check"]
    engine = loop.engine
    extra = settle(loop)
    if extra:
        print(f"no slot was decoding at the close: {extra} more iterations "
              "outside the window before the live logits were taken",
              flush=True)
    # the slots with the most answer left: they decode through every capture
    picked = sorted(
        ((slot, r) for slot, r in engine.active.items()
         if len(r.tokens) >= 2),
        key=lambda sr: len(sr[1].tokens) - sr[1].max_new_tokens)[
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


def after_window(run, loop, leaves, win, **kw):
    """Pick the samples, pull the live logits, free the engine, run the
    reference. Returns ``reference_gaps``'s readings."""
    chk = run.workload["check"]
    sample = base.pick_sample(run.seed, win["completed"],
                              chk["sample_requests"])
    caps = live_captures(run, loop)
    hybrid.drop_engine(loop.engine)
    # not under run.reference(): that clock is taken off ``setup_s``, and
    # this reference runs after the window, outside set-up
    t0 = time.perf_counter()
    gaps = reference_gaps(run, leaves, sample, caps, **kw)
    print(f"reference after the window: {time.perf_counter() - t0:.1f} s "
          f"({len(sample)} finished sequences and {len(caps)} live rows, "
          f"sequences of {gaps['positions']} positions)", flush=True)
    return gaps


def scope_seconds(run):
    """Device seconds of the traced sub-window by program scope, {scope:
    s}; None where the trace's file is not there to read (a test that stands
    a fixture in for the reduction)."""
    try:
        path = trace_mod.newest_xplane(os.path.join(run.scratch, "trace"))
    except trace_mod.TraceError:
        return None
    scopes = scope_table.SCOPES + tuple(run.workload["trace"]["scopes"])
    table = scope_table.analyse(path, n_devices=len(run.devices),
                                scopes=scopes)
    print("scopes_s " + ", ".join(f"{k} {v:.4f}" for k, v in
                                  table["by_scope_s"].items()), flush=True)
    return table["by_scope_s"]


def run(run):
    w = run.workload
    tr, chk, eng = w["traffic"], w["check"], w["engine"]
    with run.spans.span("setup.build"):
        engine, leaves = build_engine(run)
    traffic = base.Traffic(run.seed, tr, run.config["as_run"]["vocab"])
    loop = base.ClosedLoop(engine, traffic, run.spans)
    with run.spans.span("setup.warm_up_and_ramp"):
        warm_up(run, engine, loop)
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
        scopes_s = scope_seconds(run)
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
    ttft = sorted(facts["ttft_s"])
    if ttft:
        # not a metric of this cell: a request waits for the prompts queued
        # before it, a chunk an iteration
        print(f"ttft s over {len(ttft)}: median {ttft[len(ttft) // 2]:.2f} "
              f"largest {ttft[-1]:.2f}", flush=True)
    print(f"requests: attempted {attempted} completed {facts['completed']} "
          f"failed {win['failed']} no_first_token_yet {facts['ttft_missing']}"
          f" queued_at_close {queued_at_close} prefilling_at_close "
          f"{phase['prefilling']} decoding_at_close {phase['decoding']} "
          f"tokens {win['tokens']} live_rows_compared "
          f"{gaps.get('live_rows', 0)}", flush=True)
    return {"facts": facts, "checks": checks, "attempted": attempted,
            "failed": win["failed"], "memory_peak_bytes": peak}


def calibrate(run, seeds, control):
    """tools/calibrate.py: both readings seed by seed, each after a ramp and
    a window at the cell's own load (a fresh engine a seed: the reference
    needs the chip to itself); for the seeds in ``control`` also what the
    reference in fp8's precision gives in the program's place, and for
    every seed the share of routed positions with a near tie at the 4th
    place."""
    for seed in seeds:
        run.seed = seed
        engine, leaves = build_engine(run)
        loop = base.ClosedLoop(engine, base.Traffic(
            seed, run.workload["traffic"], run.config["as_run"]["vocab"]),
            run.spans)
        warm_up(run, engine, loop)
        win = base.window(run, loop)
        gaps = after_window(
            run, loop, leaves, win, margins=True,
            quant=ref.fake_fp8 if seed in control else None)
        gaps.update(seed=seed, completed=len(win["completed"]),
                    tokens_per_s=win["tokens"] / win["elapsed"])
        del engine, loop
        gc.collect()
        yield gaps
