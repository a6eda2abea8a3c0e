"""Driver: one ``serving.Engine`` under a closed loop of clients.

Each of ``clients`` callers submits its next request when its last one has
completed. The benchmark drives ``submit()`` and ``step()`` itself, so its
spans wrap every scheduler iteration, and stamps each ``Request`` on its own
clock after every ``step()``: first token seen, last token seen.

Sizes and order: one round of ``clients`` (prompt length, output length,
greedy or sampled) triples and the order it is served in are fixed by the
workload file alone (quantiles of its distributions, paired and ordered by its
``sizes_seed``). ``--seed`` makes the tokens, the sampling seeds and the
weights, none of which changes how long anything takes: which prompts meet in
a prefill cohort decides how many slots stay busy, so with the order drawn
from ``--seed`` the tokens per second of two seeds lay 9% of the median apart
(6 seeds, my chip run, PR 23), far more than two runs of one order.

After the window closes, a sample of the greedy requests it finished is run
through the plain reference, which is given the same seeded weights one layer
at a time: the number compared is the widest gap by which a served token's
reference logit lies below the reference's best at that position.
"""
import math
import time

import numpy as np

from benchmark.harness import runtime, weights
from benchmark.references import dense_decoder as ref
from benchmark.references import layouts


def norm_ppf(p):
    """Inverse normal CDF (Acklam's rational approximation, |err| < 1e-8
    after one Newton step is not needed here: lengths are rounded)."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00]
    if p < 0.02425:
        q = math.sqrt(-2 * math.log(p))
        return ((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                 + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q
                            + 1))
    if p > 1 - 0.02425:
        return -norm_ppf(1 - p)
    q = p - 0.5
    r = q * q
    return ((((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
             + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r
                             + b[4]) * r + 1))


def round_of_sizes(tr):
    """One round: ``clients`` (prompt_len, max_new, greedy) triples from the
    workload file alone. Prompt lengths are the quantiles of a log-normal,
    clipped; output lengths an even grid; greedy every ``greedy_every``-th."""
    n = tr["clients"]
    pl = tr["prompt_len"]
    prompts = [int(min(pl["max"], max(pl["min"], round(
        pl["median"] * math.exp(pl["sigma"] * norm_ppf((i + 0.5) / n))))))
        for i in range(n)]
    ol = tr["output_len"]
    outs = [int(round(ol["min"] + (ol["max"] - ol["min"]) * i / (n - 1)))
            for i in range(n)]
    rs = np.random.RandomState(tr["sizes_seed"])
    outs = [outs[i] for i in rs.permutation(n)]
    return [(prompts[i], outs[i], i % tr["greedy_every"] == 0)
            for i in rs.permutation(n)]


class Traffic:
    """The stream of requests a seed gives: request j has the sizes of
    ``round[j % clients]`` and tokens from the seed."""

    def __init__(self, seed, tr, vocab):
        self.tr, self.vocab = tr, vocab
        self.sizes = round_of_sizes(tr)
        self.rs = np.random.RandomState(weights.seed_word(seed) ^ 0x7AFF1C)
        self.j = 0

    def next(self):
        p, n, greedy = self.sizes[self.j % len(self.sizes)]
        self.j += 1
        kw = {} if greedy else dict(temperature=self.tr["temperature"],
                                    top_k=self.tr["top_k"])
        return dict(prompt=self.rs.randint(0, self.vocab, (p,), np.int32),
                    max_new_tokens=n, seed=int(self.rs.randint(1 << 30)),
                    **kw)


class Stamp:
    """The benchmark's own record of one request."""
    __slots__ = ("req", "greedy", "t_submit", "t_first", "t_last", "seen",
                 "in_window")

    def __init__(self, req, greedy, t_submit, in_window):
        self.req, self.greedy = req, greedy
        self.t_submit, self.in_window = t_submit, in_window
        self.t_first = self.t_last = None
        self.seen = 0


class ClosedLoop:
    """``clients`` callers over one engine."""

    def __init__(self, engine, traffic, spans):
        self.engine, self.traffic, self.spans = engine, traffic, spans
        self.open = []            # one Stamp per busy client
        self.done = []
        self.failed = 0
        self.refused = 0
        self.tokens = 0
        self.in_window = False
        self.filled = []          # (t, cached tokens over the live slots,
        #                           the engine's occupancy sample)

    def submit(self):
        """One client sends its next request; a refusal is an answer too, so
        the client goes on to the one after (at most one round of them)."""
        for _ in range(len(self.traffic.sizes)):
            kw = self.traffic.next()
            with self.spans.span("submit"):
                try:
                    req = self.engine.submit(**kw)
                except ValueError:
                    self.refused += 1
                    continue
            self.open.append(Stamp(req, "temperature" not in kw,
                                   time.perf_counter(), self.in_window))
            return

    def iterate(self):
        """One scheduler iteration, stamped; clients whose request ended
        submit their next. Returns tokens seen in this iteration."""
        with self.spans.span("engine.step"):
            self.engine.step()
        now = time.perf_counter()
        new, still, ended, cached = 0, [], 0, 0
        for s in self.open:
            n = len(s.req.tokens)
            if n > s.seen:
                if s.t_first is None:
                    s.t_first = now
                s.t_last = now
                new += n - s.seen
                s.seen = n
            if s.req.finished:
                ended += 1
                if s.req.state == "done":
                    self.done.append(s)
                else:
                    self.failed += 1
            else:
                still.append(s)
                if n:
                    cached += s.req.prompt.size + n
        self.open = still
        self.filled.append(
            (now, cached, self.engine.report.occupancy_samples[-1]))
        self.tokens += new
        for _ in range(ended):
            self.submit()
        return new


def build_engine(run):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.serving import Engine, EngineConfig

    cfg, eng = run.config["as_run"], run.workload["engine"]
    mesh = Mesh(np.array(run.devices[:1]), ("serve",))
    model = TransformerLM(
        vocab=cfg["vocab"], d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_kv_heads=(cfg["n_kv_heads"] if cfg["n_kv_heads"] != cfg["n_heads"]
                    else None),
        n_layers=cfg["n_layers"], d_ff=cfg["d_ff"], max_len=cfg["max_len"],
        pos_emb=cfg["pos_emb"], rope_theta=cfg.get("rope_theta", 10000.0),
        attention=eng["attention"], dtype=jnp.dtype(cfg["compute_dtype"]))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 8), np.int32))["params"])
    spec = weights.spec_of(shapes)
    params = weights.make_tree(run.seed, spec, cfg["n_layers"],
                               jnp.dtype(cfg["param_dtype"]),
                               NamedSharding(mesh, P()))
    engine = Engine(model, params, EngineConfig(
        n_slots=eng["n_slots"], capacity=eng["capacity"],
        buckets=tuple(eng["buckets"]), decode_k=eng["decode_k"],
        prefill_cohort=eng["prefill_cohort"],
        cache_dtype=jnp.dtype(eng["cache_dtype"])), mesh=mesh)
    return engine, spec


def warm_up(run, engine, loop):
    """Every program the traffic uses, once: one prompt per bucket the
    round's lengths reach, then the loop itself for ``ramp_iterations``
    scheduler iterations (a fixed amount of work), so the window opens on
    staggered clients and a queue as deep as it stays."""
    tr, eng = run.workload["traffic"], run.workload["engine"]
    lengths = sorted(p for p, _, _ in loop.traffic.sizes)
    rs = np.random.RandomState(0)
    below = 0
    for bucket in sorted(eng["buckets"]):
        fits = [p for p in lengths if below < p <= bucket]
        below = bucket
        if fits:
            engine.submit(rs.randint(0, 100, (fits[-1],), np.int32),
                          max_new_tokens=eng["decode_k"] + 1,
                          temperature=tr["temperature"], top_k=tr["top_k"])
    engine.run_until_drained()
    for _ in range(tr["clients"]):
        loop.submit()
    for _ in range(tr["ramp_iterations"]):
        loop.iterate()


def window(run, loop):
    trace_at = run.seconds * 0.3 if run.traced else None
    trace_len = run.workload["trace"]["seconds"]
    tracing = False
    done_before, tokens_before = len(loop.done), loop.tokens
    failed_before = loop.failed + loop.refused
    loop.in_window = True
    t0 = run.window_opens()
    deadline = t0 + run.seconds
    while time.perf_counter() < deadline:
        if trace_at is not None and not tracing and (
                time.perf_counter() - t0 >= trace_at):
            run.trace_start()
            tracing, t_trace = True, time.perf_counter()
        loop.iterate()
        if tracing and time.perf_counter() - t_trace >= trace_len:
            run.trace_stop()
            tracing, trace_at = False, None
    elapsed = run.window_closes()
    if tracing:
        run.trace_stop()
    loop.in_window = False
    return {"t0": t0, "elapsed": elapsed,
            "tokens": loop.tokens - tokens_before,
            "completed": loop.done[done_before:],
            "failed": loop.failed + loop.refused - failed_before}


def reference_gaps(run, spec, sample, quant=None):
    """For each sampled request: the reference's logits at the served
    positions, one layer at a time from the seeded weights. Returns the
    widest gap of a served token below the reference's best and, when
    ``quant`` is given, the same for the token a ``quant`` forward puts
    first (the lower-precision control)."""
    import jax
    import jax.numpy as jnp

    cfg = run.config["as_run"]
    chk = run.workload["check"]
    pad = chk["reference_len"]
    dtype = jnp.dtype(cfg["param_dtype"])
    n_layers = cfg["n_layers"]
    seed = weights.seed_word(run.seed)
    toks = np.zeros((len(sample), pad), np.int32)
    for i, s in enumerate(sample):
        seq = np.concatenate([s.req.prompt,
                              np.asarray(s.req.tokens[:-1], np.int32)])
        toks[i, :seq.size] = seq

    def upcast(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    def make_fns(q):
        @jax.jit
        def first(seed, toks):
            rest = layouts.canonical_rest(upcast(weights.make_rest(
                seed, spec, n_layers, dtype)))
            return ref.embed(toks, rest, cfg)

        @jax.jit
        def layer(seed, i, x):
            p = layouts.canonical_layer(upcast(weights.make_layer(
                seed, spec, i, n_layers, dtype)))
            return ref.block(x, p, cfg, q)

        @jax.jit
        def last(seed, x):
            rest = layouts.canonical_rest(upcast(weights.make_rest(
                seed, spec, n_layers, dtype)))
            return ref.head_logits(x, rest, cfg, q)

        return first, layer, last

    def forward(q):
        first, layer, last = make_fns(q)
        with jax.default_matmul_precision("highest"):
            x = first(seed, jnp.asarray(toks))
            for i in range(n_layers):
                x = layer(seed, jnp.int32(i), x)
            rows = []
            for i, s in enumerate(sample):
                p, n = s.req.prompt.size, len(s.req.tokens)
                at = np.minimum(p - 1 + np.arange(pad_out), pad - 1)
                rows.append(np.asarray(last(seed, x[i][at]))[:n])
        return rows

    pad_out = chk["reference_out"]
    want = forward(ref.identity)
    served = [np.asarray(s.req.tokens) for s in sample]
    gaps = [float(np.max(w.max(-1) - w[np.arange(len(t)), t]))
            for w, t in zip(want, served)]
    out = {"served_gap": max(gaps), "tokens": int(sum(map(len, served)))}
    if quant is not None:
        low = forward(quant)
        out["control_gap"] = max(
            float(np.max(w.max(-1) - w[np.arange(len(l)), l.argmax(-1)]))
            for w, l in zip(want, low))
    return out


def pick_sample(seed, completed, k):
    """``k`` greedy requests the window finished, the longest among them."""
    greedy = [s for s in completed if s.greedy]
    if not greedy:
        return []
    longest = max(greedy, key=lambda s: s.req.prompt.size + len(s.req.tokens))
    rest = [s for s in greedy if s is not longest]
    rs = np.random.RandomState(weights.seed_word(seed) ^ 0xC0FFEE)
    picks = [rest[i] for i in rs.permutation(len(rest))[:k - 1]]
    return [longest] + picks


def run(run):
    w = run.workload
    tr, eng_cfg, chk = w["traffic"], w["engine"], w["check"]
    with run.spans.span("setup.build"):
        engine, spec = build_engine(run)
    traffic = Traffic(run.seed, tr, run.config["as_run"]["vocab"])
    loop = ClosedLoop(engine, traffic, run.spans)
    with run.spans.span("setup.warm_up_and_ramp"):
        warm_up(run, engine, loop)
    submitted_before = traffic.j

    win = window(run, loop)
    peak = runtime.memory_peak_bytes(run.devices)
    if run.traced:
        run.reduce_trace()

    stamps_in = [s for s in loop.done + loop.open if s.in_window]
    attempted = traffic.j - submitted_before
    sample = pick_sample(run.seed, win["completed"], chk["sample_requests"])
    with run.reference():
        gaps = (reference_gaps(run, spec, sample) if sample
                else {"served_gap": float("inf"), "tokens": 0})

    steps = engine.steps
    buckets_used = sorted(steps.prefill_traces)
    checks = [
        {"name": "served_logit_gap", "value": gaps["served_gap"],
         "limit": chk["limits"]["served_logit_gap"],
         "ok": gaps["served_gap"] <= chk["limits"]["served_logit_gap"]},
        {"name": "served_tokens_compared", "value": gaps["tokens"],
         "limit": ">= %d" % chk["min_tokens"],
         "ok": gaps["tokens"] >= chk["min_tokens"]},
        {"name": "decode_k_traces", "value": steps.decode_k_traces,
         "limit": 1, "ok": steps.decode_k_traces == 1},
        {"name": "prefill_traces_per_bucket",
         "value": max(steps.prefill_traces.values()), "limit": 1,
         "ok": max(steps.prefill_traces.values()) == 1},
        {"name": "prefill_buckets_compiled", "value": len(buckets_used),
         "limit": chk["buckets_used"],
         "ok": len(buckets_used) == chk["buckets_used"]},
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
        "queued_at_close": len(engine.queue),
        "tpot_s": [(s.t_last - s.t_first) / (s.seen - 1)
                   for s in win["completed"] if s.seen > 1],
        "completed": len(win["completed"]),
        "occupancy": [o for t, _, o in loop.filled if lo <= t <= hi],
        "filled": [(t, n) for t, n, _ in loop.filled if lo <= t <= hi],
        "trace_span": trace_span[-1] if trace_span else None,
        "chips": 1, "peaks": run.peaks, "config": run.config, "workload": w,
        "trace": run.trace, "spans": run.spans,
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
    for name in ("ttft_s", "tpot_s"):
        xs = sorted(facts[name])
        if xs:
            print(f"{name}: n {len(xs)} median {xs[len(xs) // 2]:.4f} "
                  f"max {xs[-1]:.4f}", flush=True)
    print(f"requests: attempted {attempted} completed {facts['completed']} "
          f"failed {win['failed']} no_first_token_yet {facts['ttft_missing']}"
          f" queued_at_close {facts['queued_at_close']} tokens "
          f"{win['tokens']}", flush=True)
    return {"facts": facts, "checks": checks, "attempted": attempted,
            "failed": win["failed"], "memory_peak_bytes": peak}


def calibrate(run, seeds, control):
    """tools/calibrate.py: the served gap seed by seed through one engine
    (weights swapped in place), each after a ramp and a window at the cell's
    own load; for the seeds in ``control`` also the gap of the token the
    reference in fp8's precision puts first."""
    import gc

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = run.config["as_run"]
    engine, spec = build_engine(run)
    sharding = NamedSharding(engine.steps._mesh, P())
    for seed in seeds:
        if seed != run.seed:
            engine.abort_all()
            engine.steps.params = None      # 6 GB: free before the next 6
            gc.collect()
            run.seed = seed
            engine.steps.load_params(weights.make_tree(
                seed, spec, cfg["n_layers"], jnp.dtype(cfg["param_dtype"]),
                sharding))
        loop = ClosedLoop(engine, Traffic(seed, run.workload["traffic"],
                                          cfg["vocab"]), run.spans)
        warm_up(run, engine, loop)
        win = window(run, loop)
        sample = pick_sample(seed, win["completed"],
                             run.workload["check"]["sample_requests"])
        gaps = reference_gaps(
            run, spec, sample, quant=ref.fake_fp8 if seed in control else None)
        gaps.update(seed=seed, completed=len(win["completed"]),
                    tokens_per_s=win["tokens"] / win["elapsed"],
                    sample=[(s.req.prompt.size, len(s.req.tokens))
                            for s in sample])
        yield gaps
