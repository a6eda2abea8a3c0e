"""Driver: one ``serving.Engine`` over a hybrid model (linear-attention
state, latent page, held experts) under ``serve_closed``'s closed loop.

The loop, the traffic, the window and the sampling of finished requests are
``serve_closed``'s own (imported, not copied). What is this driver's:

* ``build_engine`` — ``models/hybrid.py``'s ``HybridLM`` from the
  configuration's ``as_run`` sizes, with weights made one layer at a time
  (a layer's expert kernels alone are 1.5 GB; their float32 normals must not
  all exist at once).
* seeded weights for heterogeneous layers (``make_leaf``): stacked expert
  kernels ``[E, d, f]`` have fan-in ``d``, not ``E``; the expert bias
  STARTS at 0.02 N (what this cell serves is ``balanced_biases``' below);
  ``a_log`` and ``dt_bias`` are drawn so that the per-channel decay
  ``α = exp(-5 σ(exp(a_log)(W_f x + dt_bias)))`` lies in about 0.9–0.999 a
  token, as in a trained model, so the state holds hundreds of tokens and an
  error in carrying it shows in the logits; everything else follows
  ``harness/weights.py``'s rules.
* ``balanced_biases`` — every expert layer's ``router_bias`` is what
  ``noaux_tc``'s balancing rule comes to rest at
  (``references/balance.py``, the one rule, handed ``ling_hybrid``'s
  ``route`` with its sigmoid scores and its group choice), run by the
  REFERENCE layer after layer on a seeded probe of ``check.balance_tokens``
  tokens in ``check.balance_sequences`` sequences; program and reference
  are handed the same float32 arrays (``Leaves``). It stands for the
  published ``moe_router_enable_expert_bias: true``: a trained Ling arrives
  with that bias at rest.
* ``reference_gaps`` — what the timed path served against
  ``references/ling_hybrid.py``'s full forward on prompt + served tokens,
  two readings, each held twice: (1) the gap by which a served greedy
  token's reference logit lies below the reference's best, as in
  ``serve_closed``: its MEAN over the compared tokens (``served_logit_gap``)
  and its LARGEST (``served_logit_gap_largest``); (2) LOGITS, not tokens: the
  last decode dispatch of the window left each live slot's logits on the
  device — after a prefill and up to 511 decode steps through the cache —
  and they are compared row by row with the reference's logits at that
  position, as the root-mean-square difference over the vocabulary over the
  reference's standard deviation there: the LOWER QUARTILE over the compared
  rows (``state_logit_rms``) and the LARGEST row
  (``state_logit_rms_largest``); the median is printed beside them. Router
  scores are float32 on both sides.

Why a mean and a quartile carry the tight limits, and the largest only loose
ones: 13% of the routed positions have their 8th and 9th expert scores
within 1e-3 (my chip runs, PR 27), a bfloat16 hidden state and a float32 one
order such a pair differently, and the position's output then moves by a
whole expert's. The flips cannot be masked out of the comparison: a KDA
layer's state carries a flipped position's output into every later position
of the sequence (decay 0.9–0.999 a token), and a 500-token sequence has some
390 near-tied (position, layer) pairs, so no compared position is clean.
Handing the program's own choices to the reference would remove them, but
the timed path would have to return 48 expert ids a token from every
dispatch, which it has no other use for. So: the lower precision (the
control) and a fault in carrying the state move EVERY row and token, the
least disturbed too, and are held by the mean and the quartile, with limits
between the sound program's readings and the control's; a fault that hits
only some rows or tokens — one prefill bucket, a few slots, a handful of
served tokens — reads far outside what flips do to any row or token, and is
held by the largest, with limits between the sound program's largest over
all seeds and what such a fault reads (PERF.md §4).

Every weight is drawn from ``--seed``, and the one computed leaf, the choice
bias, from those. Until PR 43 the bias was its 0.02 N noise, and that cost
steadiness: which experts a seed's router preferred — its columns, its bias,
the direction the blocks upstream give the hidden state — decided what
share of the pairs landed on the 128 held experts (21.8–25.6%, one expert
with 12 times the mean load) and how many of their kernels a step read,
and those reads are a third of a step since PR 40: seeds spread by 1.3–2.0%
of the median. With the bias at rest a quarter of the pairs lands here on
every seed (PERF.md §6 has the readings of both). The balancing runs before
``setup.build`` and on the reference's clock (``Run.reference``): it is the
benchmark making its weights by the reference, not the program's set-up.

After the window the engine's parameters and pages are dropped before the
reference runs: the reference's float32 layer and the program's 12.4 GB do
not fit one chip together.
"""
import collections
import functools
import gc
import time

import numpy as np

from benchmark.drivers import serve_closed as base
from benchmark.harness import runtime, weights
from benchmark.references import balance
from benchmark.references import ling_hybrid as ref
# a program from before ISSUE 27 has no such module: its run of a cell of
# this driver ends here, before any work on the device
from chainermn_tpu.models.hybrid import HybridLM

EXPERT_KERNELS = ("w_gate", "w_up", "w_down")


# -- weights -------------------------------------------------------------------
def make_leaf(seed, layer_id, leaf_id, path, shape, dtype):
    """One leaf by rule from its path; ``layer_id`` may be traced."""
    import jax
    import jax.numpy as jnp

    last = path[-1]
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(seed), layer_id),
        leaf_id & 0x7FFFFFFF)
    if last in EXPERT_KERNELS:
        z = jax.random.normal(key, shape, jnp.float32)
        return (z * float(shape[1]) ** -0.5).astype(dtype)
    if last == "router_bias":
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(
            jnp.float32)
    if last == "a_log":         # exp(a_log) in 0.27 .. 0.33, one a head
        return jnp.log(jax.random.uniform(
            key, shape, jnp.float32, 0.27, 0.33))
    if last == "dt_bias":
        # exp(a_log) * dt_bias in about -8.3 .. -4.1 a channel: with
        # W_f x ~ N(0, 1) the decay exp(-5 sigmoid(.)) is 0.9 .. 0.999
        return jax.random.uniform(key, shape, jnp.float32, -25.0, -15.3)
    return weights.make_leaf(seed, layer_id, leaf_id, path, shape, dtype)


def model_and_spec(cfg, dtype):
    import jax

    keys = ("vocab", "d_model", "n_heads", "d_head", "d_ff", "max_len",
            "d_nope", "d_rope", "kv_rank", "rope_theta", "conv_kernel",
            "kda_lower_bound", "n_experts", "held_lo", "held_hi", "d_expert",
            "d_shared", "top_k", "n_group", "topk_group", "routed_scale",
            "norm_topk_prob", "norm_eps")
    model = HybridLM(pattern=tuple(tuple(p) for p in cfg["pattern"]),
                     dtype=dtype, **{k: cfg[k] for k in keys})
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 8), np.int32))["params"])
    return model, weights.spec_of(shapes)


def block_paths(spec, layer):
    return sorted(p for p in spec if p[0] == f"block_{layer}")


def leaf_id(sub, n_layers):
    """The key of a leaf inside a block: the crc32 of its path there."""
    return weights.leaf_ids(("block_0",) + sub, n_layers)[1]


def make_block(seed, spec, layer, n_layers, dtype, sharding=None):
    """``block_<layer>``'s leaves, {path inside the block: leaf}. Blocks of
    one kind share one compiled maker (the layer id is traced); a stacked
    expert kernel is made in a call of its own, so that only one kernel's
    float32 normals (1 GB) exist at a time."""
    inner = tuple((p[1:], spec[p]) for p in block_paths(spec, layer))
    name = np.dtype(dtype).name
    seed, layer = weights.seed_word(seed), np.int32(layer)
    big = tuple(x for x in inner if x[0][-1] in EXPERT_KERNELS)
    small = tuple(x for x in inner if x not in big)
    flat = dict(_block_maker(small, n_layers, name, sharding)(seed, layer))
    for one in big:
        flat.update(_block_maker((one,), n_layers, name, sharding)(
            seed, layer))
    return weights.unflatten(flat)


@functools.lru_cache(maxsize=None)
def _block_maker(inner, n_layers, dtype, sharding):
    import jax

    @functools.partial(jax.jit, out_shardings=sharding)
    def build(seed, layer):     # {path inside the block: leaf}
        return {sub: make_leaf(seed, layer, leaf_id(sub, n_layers),
                               ("block_0",) + sub, shape, dtype)
                for sub, shape in inner}

    return build


def make_rest(seed, spec, n_layers, dtype, sharding=None):
    import jax

    paths = [p for p in sorted(spec) if not p[0].startswith("block_")]

    @functools.partial(jax.jit, out_shardings=sharding)
    def build(seed):
        return weights.unflatten({
            p: make_leaf(seed, *weights.leaf_ids(p, n_layers), p, spec[p],
                         dtype) for p in paths})

    return build(weights.seed_word(seed))


def make_params(seed, spec, n_layers, dtype, sharding=None):
    """The whole tree, every leaf from ``seed``."""
    tree = make_rest(seed, spec, n_layers, dtype, sharding)
    for i in range(n_layers):
        tree[f"block_{i}"] = make_block(seed, spec, i, n_layers, dtype,
                                        sharding)
    return tree


# -- the computed leaf ---------------------------------------------------------
class Leaves(collections.namedtuple("Leaves", "spec biases")):
    """What regenerates the model's weights from the seed: the tree's
    ``{path: shape}`` and, beside the rules, the one leaf kind that is
    computed — ``biases[i]``, expert layer ``i``'s router bias at rest."""


ROUTER_BIAS = ("moe", "router_bias")


def layer_maker(spec, layer, n_layers, dtype):
    """``(seed, i, bias=None) -> block_i's leaves`` by the rules for any
    layer ``i`` of block ``layer``'s kind (``i`` may be traced), the router
    bias replaced by ``bias`` where one is handed in."""
    inner = tuple((p[1:], spec[p]) for p in block_paths(spec, layer))

    def make(seed, i, bias=None):
        flat = {sub: make_leaf(seed, i, leaf_id(sub, n_layers),
                               ("block_0",) + sub, shape, dtype)
                for sub, shape in inner}
        if bias is not None and ROUTER_BIAS in flat:
            flat[ROUTER_BIAS] = bias
        return weights.unflatten(flat)

    return make


def balanced_biases(run, spec):
    """{expert layer: its router bias [E] float32}: ``references/balance.py``
    with this cell's reference, weights and probe (module docstring)."""
    import jax.numpy as jnp

    cfg, chk = run.config["as_run"], run.workload["check"]
    dtype, n = jnp.dtype(cfg["param_dtype"]), cfg["n_layers"]
    seed = weights.seed_word(run.seed)
    toks = balance.probe_tokens(seed, cfg["vocab"], chk["balance_tokens"],
                                chk["balance_sequences"])
    rest = ref.canonical_rest(make_rest(run.seed, spec, n, dtype))
    return balance.balanced_biases(
        ref, ref_cfg(cfg), cfg["pattern"],
        lambda kind, layer: layer_maker(spec, layer, n, dtype), seed,
        ref.embed(jnp.asarray(toks), rest))


def seeded_leaves(run):
    """(the model, its ``Leaves``): the balancing on the reference's clock,
    and outside every ``setup.*`` span, so that what it compiles is not
    counted as the program's set-up."""
    import jax.numpy as jnp

    cfg = run.config["as_run"]
    model, spec = model_and_spec(cfg, jnp.dtype(cfg["compute_dtype"]))
    t0 = time.perf_counter()
    with run.reference():
        biases = balanced_biases(run, spec)
        for b in biases.values():
            b.block_until_ready()
    print(f"router biases at rest: {len(biases)} expert layers in "
          f"{time.perf_counter() - t0:.1f} s (on the reference's clock)",
          flush=True)
    return model, Leaves(spec, biases)


# -- the engine ----------------------------------------------------------------
def build_engine(run, model, leaves):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from chainermn_tpu.serving import Engine, EngineConfig

    cfg, eng = run.config["as_run"], run.workload["engine"]
    mesh = Mesh(np.array(run.devices[:1]), ("serve",))
    sharding = NamedSharding(mesh, P())
    params = make_params(run.seed, leaves.spec, cfg["n_layers"],
                         jnp.dtype(cfg["param_dtype"]), sharding)
    for i, bias in leaves.biases.items():
        params[f"block_{i}"]["moe"]["router_bias"] = jax.device_put(
            bias, sharding)
    return Engine(model, params, EngineConfig(
        n_slots=eng["n_slots"], capacity=eng["capacity"],
        buckets=tuple(eng["buckets"]), decode_k=eng["decode_k"],
        prefill_cohort=eng["prefill_cohort"]), mesh=mesh)


def ref_cfg(cfg):
    return {k: cfg[k] for k in (
        "n_heads", "d_head", "d_nope", "d_rope", "kv_rank", "rope_theta",
        "kda_lower_bound", "n_group", "topk_group", "top_k", "routed_scale",
        "held_lo", "norm_eps", "pattern")}


def live_sample(seed, engine, k):
    """Up to ``k`` of the requests still decoding after the window's last
    iteration — the longest stream and a seeded draw of the others — with
    the slot whose logits the last dispatch left on the device."""
    live = [(slot, r) for slot, r in sorted(engine.active.items())
            if len(r.tokens) >= 2]
    if not live:
        return []
    longest = max(live, key=lambda sr: len(sr[1].tokens))
    rest = [sr for sr in live if sr is not longest]
    rs = np.random.RandomState(weights.seed_word(seed) ^ 0x51A7E)
    return [longest] + [rest[i] for i in rs.permutation(len(rest))[:k - 1]]


def reference_gaps(run, leaves, sample, live=(), live_logits=None,
                   quant=None, margins=False):
    """The reference's logits on prompt + served tokens for the finished
    greedy ``sample`` (stamps) and the ``live`` (slot, request) pairs, one
    layer at a time from the seeded weights and ``leaves.biases``.
    ``live_logits`` holds the program's logits rows of the live pairs.
    Returns the two readings
    (module docstring) with what they were taken over; with ``quant`` also
    the control's (the reference computed with ``quant`` on every matmul
    operand, in the program's place); with ``margins`` the share of routed
    positions whose 8th and 9th biased scores lie within 1e-3."""
    import jax
    import jax.numpy as jnp

    cfg = run.config["as_run"]
    rcfg = ref_cfg(cfg)
    chk = run.workload["check"]
    pad, pad_out = chk["reference_len"], chk["reference_out"]
    dtype = jnp.dtype(cfg["param_dtype"])
    n_layers = cfg["n_layers"]
    seed = weights.seed_word(run.seed)
    reqs = [s.req for s in sample] + [r for _, r in live]
    toks = np.zeros((len(reqs), pad), np.int32)
    for i, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        toks[i, :seq.size] = seq
    kinds = [tuple(k) for k in cfg["pattern"]]
    no_bias = jnp.zeros((0,), jnp.float32)

    def layer_fn(kind, layer, q):
        make = layer_maker(leaves.spec, layer, n_layers, dtype)

        @jax.jit
        def f(seed, i, x, bias):
            p = ref.canonical_layer(
                make(seed, i, bias if bias.size else None),
                upcast_experts=False)
            out = ref.block(x, p, kind, rcfg, q)
            if margins and kind[1] == "moe":
                y = ref.ffn_input(x, p, kind, rcfg)
                return out, ref.route_margin(
                    y.reshape(-1, y.shape[-1]), p, rcfg)
            return out, jnp.zeros((0,), jnp.float32)

        return f

    def forward(q):
        rest = ref.canonical_rest(make_rest(run.seed, leaves.spec, n_layers,
                                            dtype))
        head = jax.jit(lambda x, rest: ref.head_logits(x, rest, rcfg, q))
        fns, near, routed = {}, 0, 0
        with jax.default_matmul_precision("highest"):
            x = ref.embed(jnp.asarray(toks), rest)
            for i, kind in enumerate(kinds):
                if kind not in fns:
                    fns[kind] = layer_fn(kind, i, q)
                x, m = fns[kind](seed, jnp.int32(i), x,
                                 leaves.biases.get(i, no_bias))
                if m.size:
                    m = np.asarray(m).reshape(len(reqs), pad)
                    for j, r in enumerate(reqs):
                        n = r.prompt.size + len(r.tokens) - 1
                        near += int((m[j, :n] < 1e-3).sum())
                        routed += n
            rows = []
            for i, r in enumerate(reqs):
                p, n = r.prompt.size, len(r.tokens)
                at = np.minimum(p - 1 + np.arange(pad_out), pad - 1)
                rows.append(np.asarray(head(x[i][at], rest))[:n])
        return rows, (near, routed)

    def gap_mean(picked):
        """Mean and largest, over the compared tokens, of how far the picked
        token's reference logit lies below the reference's best."""
        gaps = np.concatenate([w.max(-1) - w[np.arange(len(t)), t]
                               for w, t in zip(want[:ns], picked)])
        return float(gaps.mean()), float(gaps.max())

    def rms_rows(got):
        return sorted(
            float(np.sqrt(np.mean((got[j] - want[ns + j][-1]) ** 2))
                  / np.std(want[ns + j][-1])) for j in range(len(live)))

    want, (near, routed) = forward(ref.identity)
    ns = len(sample)
    served = [np.asarray(s.req.tokens) for s in sample]
    inf = float("inf")
    out = {"served_gap": inf, "served_gap_max": inf, "state_rms": inf,
           "state_rms_rows": [inf], "tokens": int(sum(map(len, served))),
           "live_rows": len(live)}
    if ns:
        out["served_gap"], out["served_gap_max"] = gap_mean(served)
    if live:
        rows = rms_rows(live_logits)
        out["state_rms"], out["state_rms_rows"] = rows[len(rows) // 4], rows
    if margins:
        out["near_tie_share"] = near / max(routed, 1)
    if quant is not None:
        low, _ = forward(quant)
        if ns:
            out["control_gap"], out["control_gap_max"] = gap_mean(
                [l.argmax(-1) for l in low[:ns]])
        if live:
            rows = rms_rows([low[ns + j][-1] for j in range(len(live))])
            out["control_rms"] = rows[len(rows) // 4]
            out["control_rms_rows"] = rows
    return out


def drop_engine(engine):
    """Free the parameters and the pages (the reference needs the room)."""
    engine.steps.params = None
    engine.steps.cache = None
    engine.steps.last_decode_logits = None
    engine._keys = None
    gc.collect()


def after_window(run, engine, leaves, win, **kw):
    """Pick the samples, pull the live logits, free the engine, run the
    reference. Returns ``reference_gaps``'s readings."""
    chk = run.workload["check"]
    sample = base.pick_sample(run.seed, win["completed"],
                              chk["sample_requests"])
    live = live_sample(run.seed, engine, chk["sample_live"])
    logits = engine.steps.last_decode_logits        # on the device
    live_logits = (None if logits is None or not live else np.asarray(
        logits[np.asarray([slot for slot, _ in live])]))
    drop_engine(engine)
    # not under run.reference(): that clock is taken off ``setup_s``, and
    # this reference runs after the window, outside set-up
    t0 = time.perf_counter()
    gaps = reference_gaps(run, leaves, sample, live, live_logits, **kw)
    print(f"reference after the window: {time.perf_counter() - t0:.1f} s "
          f"({len(sample)} finished + {len(live)} live sequences)",
          flush=True)
    return gaps


def run(run):
    w = run.workload
    tr, chk = w["traffic"], w["check"]
    model, leaves = seeded_leaves(run)
    with run.spans.span("setup.build"):
        engine = build_engine(run, model, leaves)
    traffic = base.Traffic(run.seed, tr, run.config["as_run"]["vocab"])
    loop = base.ClosedLoop(engine, traffic, run.spans)
    with run.spans.span("setup.warm_up_and_ramp"):
        base.warm_up(run, engine, loop)
    submitted_before = traffic.j

    # no cyclic collection inside the window: the loop makes no cycles worth
    # collecting in 30 s, and a full pass over jax's objects is a pause
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        win = base.window(run, loop)
    finally:
        gc.enable()
        gc.unfreeze()
    peak = runtime.memory_peak_bytes(run.devices)
    if run.traced:
        run.reduce_trace()

    stamps_in = [s for s in loop.done + loop.open if s.in_window]
    attempted = traffic.j - submitted_before
    steps = engine.steps
    traces = dict(decode_k=steps.decode_k_traces,
                  prefill=dict(steps.prefill_traces))
    queued_at_close = len(engine.queue)
    slot_bytes = steps.slot_bytes
    gaps = after_window(run, engine, leaves, win)

    limits = chk["limits"]
    rows = gaps["state_rms_rows"]
    print(f"state_logit_rms over {len(rows)} live rows: lower quartile "
          f"{gaps['state_rms']:.4f} median {rows[len(rows) // 2]:.4f} "
          f"largest {rows[-1]:.4f}; served_logit_gap over {gaps['tokens']} "
          f"tokens: mean {gaps['served_gap']:.4f} largest "
          f"{gaps['served_gap_max']:.4f}", flush=True)
    checks = [
        {"name": name, "value": value, "limit": limits[name],
         "ok": value <= limits[name]}
        for name, value in (
            ("served_logit_gap", gaps["served_gap"]),
            ("served_logit_gap_largest", gaps["served_gap_max"]),
            ("state_logit_rms", gaps["state_rms"]),
            ("state_logit_rms_largest", rows[-1]))
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
    tpot = sorted(1e3 * t for t in facts["tpot_s"])
    if tpot:
        # not a metric of this cell: every request's gap is the iteration
        # over decode_k, and its tokens arrive decode_k at a time, one
        # burst an iteration
        print(f"tpot ms over {len(tpot)} completed: median "
              f"{tpot[len(tpot) // 2]:.2f} p95 "
              f"{tpot[min(len(tpot) - 1, int(0.95 * len(tpot)))]:.2f} "
              f"(tokens arrive {w['engine']['decode_k']} at a time, one "
              f"burst every engine.step)", flush=True)
    print(f"requests: attempted {attempted} completed {facts['completed']} "
          f"failed {win['failed']} no_first_token_yet {facts['ttft_missing']}"
          f" queued_at_close {queued_at_close} tokens {win['tokens']} "
          f"live_rows_compared {gaps.get('live_rows', 0)}", flush=True)
    return {"facts": facts, "checks": checks, "attempted": attempted,
            "failed": win["failed"], "memory_peak_bytes": peak}


def calibrate(run, seeds, control):
    """tools/calibrate.py: both readings seed by seed, each after a ramp and
    a window at the cell's own load (a fresh engine a seed: the reference
    needs the chip to itself); for the seeds in ``control`` also what the
    reference in fp8's precision gives in the program's place, and for
    every seed the share of routed positions with a near tie at the 8th
    place."""
    for seed in seeds:
        run.seed = seed
        model, leaves = seeded_leaves(run)
        engine = build_engine(run, model, leaves)
        loop = base.ClosedLoop(engine, base.Traffic(
            seed, run.workload["traffic"], run.config["as_run"]["vocab"]),
            run.spans)
        base.warm_up(run, engine, loop)
        win = base.window(run, loop)
        gaps = after_window(
            run, engine, leaves, win, margins=True,
            quant=ref.fake_fp8 if seed in control else None)
        gaps.update(seed=seed, completed=len(win["completed"]),
                    tokens_per_s=win["tokens"] / win["elapsed"])
        del engine, loop
        gc.collect()
        yield gaps
