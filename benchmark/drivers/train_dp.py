"""Driver: data-parallel training through the program's normal entry points.

``create_communicator`` -> ``create_multi_node_optimizer(adamw)`` ->
``make_data_parallel_train_step(loss_fn=fused_lm_loss)`` -> ``SerialIterator``
-> ``StandardUpdater.update()``, fed from the host every step. Set-up builds
ONE updater, drives it through its first steps for the comparison with the
plain reference (those steps also compile and warm the one program), and
hands the same object to the window.

The window keeps ``in_flight`` steps queued ahead of the device: the host
waits for the loss of the step ``in_flight`` back, never for the newest, so
the device does not drain; the window closes in ``block_until_ready`` on the
last step and the rate is all its steps over all its time.
"""
import collections
import functools
import time

import numpy as np

from benchmark.harness import runtime, weights
from benchmark.references import dense_decoder as ref
from benchmark.references import layouts


def make_rows(seed, n_rows, seq_len, vocab):
    """Rows of seq_len + 1 tokens from the seed; every row differs."""
    rs = np.random.RandomState(weights.seed_word(seed) ^ 0x5EED)
    return rs.randint(0, vocab, (n_rows, seq_len + 1)).astype(np.int32)


def leaf_norms(tree):
    """Per-leaf L2 norms of a reference-named tree, layers kept apart:
    {name: [n_layers] or scalar} on the device, in float32."""
    import jax.numpy as jnp

    def norm(a):
        a = a.astype(jnp.float32)
        if a.ndim == 0:
            return jnp.abs(a)
        return jnp.sqrt(jnp.sum(jnp.square(a), axis=tuple(range(1, a.ndim))))

    layers = {k: norm(v) for k, v in tree["layers"].items()}
    rest = {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree["rest"].items()}
    return {"layers": layers, "rest": rest}


def worst_gap(got, want):
    """Largest |got - want| over the leaves, against the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    g = np.concatenate([np.ravel(np.asarray(x, np.float64)) for x in
                        (list(got["layers"].values())
                         + list(got["rest"].values()))])
    w = np.concatenate([np.ravel(np.asarray(x, np.float64)) for x in
                        (list(want["layers"].values())
                         + list(want["rest"].values()))])
    return float(np.max(np.abs(g - w) / np.maximum(w, np.median(w))))


def reference_steps(cfg, opt, params0, rows, global_batch, n_steps,
                    micro_batch, devices, quant=ref.identity):
    """The reference through ``n_steps`` AdamW steps on the first batches:
    its losses, the per-leaf norms of its first gradient and of its
    parameters' change. ``params0`` in the reference's names, float32.

    It runs in blocks of ``micro_batch`` rows a device so that a float32
    backward pass fits; over several devices the block's rows are laid out
    across them (plain ``jit`` partitions the same jax.numpy code), which
    keeps its time under the window's at 32 rows a step."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices), ("d",))
    params0 = jax.device_put(params0, NamedSharding(mesh, P()))
    by_row = NamedSharding(mesh, P("d"))
    micro_batch *= len(devices)

    grad_fn = jax.jit(jax.value_and_grad(
        functools.partial(ref.loss, cfg=cfg, quant=quant)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    scale = jax.jit(lambda a, s: jax.tree_util.tree_map(
        lambda x: x * s, a))
    step_fn = jax.jit(functools.partial(ref.adamw, opt=opt),
                      donate_argnums=(0, 2, 3))
    norms_fn = jax.jit(leaf_norms)
    delta_fn = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))

    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    p = jax.tree_util.tree_map(jnp.copy, params0)
    m, v = zeros(p), zeros(p)
    losses, grad_norms = [], None
    n_micro = global_batch // micro_batch
    with jax.default_matmul_precision("highest"):
        for t in range(n_steps):
            batch = rows[t * global_batch:(t + 1) * global_batch]
            g_acc, loss_acc = None, 0.0
            for i in range(n_micro):
                mb = batch[i * micro_batch:(i + 1) * micro_batch]
                l, g = grad_fn(p, jax.device_put(mb[:, :-1], by_row),
                               jax.device_put(mb[:, 1:], by_row))
                g_acc = g if g_acc is None else add(g_acc, g)
                loss_acc += float(l)
            grads = scale(g_acc, 1.0 / n_micro)
            losses.append(loss_acc / n_micro)
            if t == 0:
                grad_norms = jax.device_get(norms_fn(grads))
            p, m, v = step_fn(p, grads, m, v, jnp.float32(t + 1))
            del g_acc, grads
        delta_norms = jax.device_get(delta_fn(p, params0))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta_norms}


def find_mu(opt_state):
    """AdamW's first moment inside whatever wraps it."""
    import jax

    found = []

    def visit(node):
        if hasattr(node, "mu") and hasattr(node, "nu"):
            found.append(node.mu)
        elif isinstance(node, (tuple, list)):
            for c in node:
                visit(c)
        elif hasattr(node, "inner"):
            visit(node.inner)

    visit(opt_state)
    if len(found) != 1:
        raise RuntimeError(f"found {len(found)} Adam states in the "
                           "optimizer state, want 1")
    return found[0]


def build(run):
    """The program: communicator, optimizer, ONE compiled step, updater."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import chainermn_tpu
    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.ops import fused_lm_loss
    from chainermn_tpu.training.step import make_data_parallel_train_step

    cfg, w = run.config["as_run"], run.workload
    prog, tr, opt_cfg = w["program"], w["traffic"], w["optimizer"]
    mesh = Mesh(np.array(run.devices), ("r",))
    comm = chainermn_tpu.create_communicator("xla", mesh=mesh)
    model = TransformerLM(
        vocab=cfg["vocab"], d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_layers=cfg["n_layers"], d_ff=cfg["d_ff"], max_len=cfg["max_len"],
        pos_emb=cfg["pos_emb"], attention=prog["attention"],
        dtype=jnp.dtype(cfg["compute_dtype"]), qkv_layout=prog["qkv_layout"])
    seq_len = tr["seq_len"]
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           np.zeros((1, seq_len), np.int32))["params"])
    spec = weights.spec_of(shapes)
    replicated = NamedSharding(mesh, P())

    def fresh_params():
        return weights.make_tree(run.seed, spec, cfg["n_layers"],
                                 jnp.dtype(cfg["param_dtype"]), replicated)

    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.adamw(opt_cfg["lr"], b1=opt_cfg["b1"], b2=opt_cfg["b2"],
                    eps=opt_cfg["eps"], weight_decay=opt_cfg["weight_decay"]),
        comm)
    step = make_data_parallel_train_step(model, optimizer, comm,
                                         loss_fn=fused_lm_loss)
    b = dict(comm=comm, model=model, spec=spec, fresh_params=fresh_params,
             optimizer=optimizer, step=step, seq_len=seq_len,
             global_batch=tr["batch_per_chip"] * comm.size)
    set_data(run, b)
    return b


def set_data(run, b):
    """The rows ``run.seed`` gives (again after the seed changed: the
    calibration tool reads many seeds through one compiled step)."""
    tr = run.workload["traffic"]
    b["rows"] = make_rows(run.seed, tr["dataset_batches"] * b["global_batch"],
                          b["seq_len"],
                          run.config["published"]["vocab_size"])
    b["dataset"] = [(r[:-1], r[1:]) for r in b["rows"]]


def to_reference(tree, cfg):
    import jax

    return jax.jit(functools.partial(
        layouts.canonical_tree, n_layers=cfg["n_layers"]))(tree)


def program_readings(run, b, n_steps):
    """Build the updater and drive it through its first ``n_steps`` steps by
    the window's own call and feed. Returns (updater, readings)."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.iterators import SerialIterator
    from chainermn_tpu.training import StandardUpdater

    cfg, opt_cfg = run.config["as_run"], run.workload["optimizer"]
    params = b["comm"].bcast_data(b["fresh_params"]())
    state = (params, b["optimizer"].init(params))
    updater = StandardUpdater(
        SerialIterator(b["dataset"], b["global_batch"], shuffle=False),
        b["step"], state, b["comm"])
    norms_fn = jax.jit(lambda t: leaf_norms(layouts.canonical_tree(
        t, cfg["n_layers"])))
    losses, grad_norms = [], None
    for t in range(n_steps):
        with run.spans.span("updater.update"):
            updater.update()
        losses.append(float(updater.last_metrics["main/loss"]))
        if t == 0:
            mu = find_mu(updater.state[1])
            grads = jax.jit(lambda m: jax.tree_util.tree_map(
                lambda x: x / (1.0 - opt_cfg["b1"]), m))(mu)
            grad_norms = jax.device_get(norms_fn(grads))
            del grads, mu
    p0 = b["fresh_params"]()
    delta_norms = jax.device_get(jax.jit(lambda a, z: leaf_norms(
        layouts.canonical_tree(jax.tree_util.tree_map(jnp.subtract, a, z),
                               cfg["n_layers"])))(updater.state[0], p0))
    del p0
    return updater, {"losses": losses, "grad_norms": grad_norms,
                     "delta_norms": delta_norms}


def compare(got, want):
    """The numbers compared, each to be held to a limit of its own."""
    return {
        "loss_gap": max(abs(a - b) for a, b in
                        zip(got["losses"], want["losses"])),
        "grad_norm_gap": worst_gap(got["grad_norms"], want["grad_norms"]),
        "delta_norm_gap": worst_gap(got["delta_norms"], want["delta_norms"]),
    }


def reference_readings(run, b, quant=ref.identity):
    import jax
    import jax.numpy as jnp

    cfg, w = run.config["as_run"], run.workload
    chk = w["check"]
    with run.reference():
        # the program's state is not made yet: the reference has the chips
        p0 = to_reference(b["fresh_params"](), cfg)
        want = reference_steps(
            cfg, w["optimizer"], p0, b["rows"][
                :chk["reference_steps"] * b["global_batch"]],
            b["global_batch"], chk["reference_steps"], chk["micro_batch"],
            run.devices, quant)
        del p0
    return want


def window(run, updater, b):
    """The measured window. Returns (steps, seconds, last loss)."""
    import jax

    depth = run.workload["program"]["in_flight"]
    trace_at = run.seconds * 0.3 if run.traced else None
    trace_len = run.workload["trace"]["seconds"]
    pending = collections.deque()
    steps, tracing = 0, False
    t0 = run.window_opens()
    deadline = t0 + run.seconds
    while True:
        now = time.perf_counter()
        if now >= deadline:
            break
        if trace_at is not None and not tracing and now - t0 >= trace_at:
            run.trace_start()
            tracing, t_trace = True, time.perf_counter()
        with run.spans.span("updater.update"):
            updater.update()
        steps += 1
        pending.append(updater.last_metrics["main/loss"])
        if len(pending) > depth:
            with run.spans.span("wait step -%d" % depth):
                pending.popleft().block_until_ready()
        if tracing and time.perf_counter() - t_trace >= trace_len:
            run.trace_stop()
            tracing, trace_at = False, None
    jax.block_until_ready(updater.state)
    elapsed = run.window_closes()
    if tracing:
        run.trace_stop()
    return steps, elapsed, float(updater.last_metrics["main/loss"])


def replicas_equal(params, devices):
    """Every device holds every parameter, and bitwise the same ones: each
    leaf's copies are moved to the first device and compared there as bits."""
    import jax
    import jax.numpy as jnp

    same = jax.jit(lambda a, b: jnp.all(
        jax.lax.bitcast_convert_type(a, jnp.uint32)
        == jax.lax.bitcast_convert_type(b, jnp.uint32)))
    verdicts = []
    for leaf in jax.tree_util.tree_leaves(params):
        shards = {s.device.id: s.data for s in leaf.addressable_shards}
        if set(shards) != {d.id for d in devices}:
            return False
        first = shards[devices[0].id]
        verdicts += [same(first, jax.device_put(shards[d.id], devices[0]))
                     for d in devices[1:]]
    return all(bool(v) for v in verdicts)


def run(run):
    import jax

    w = run.workload
    chk = w["check"]
    with run.spans.span("setup.build"):
        b = build(run)
    want = reference_readings(run, b)
    with run.spans.span("setup.first_steps"):
        updater, got = program_readings(run, b, chk["reference_steps"])
    numbers = compare(got, want)
    xs, _ = updater.shard_batch((b["rows"][:b["global_batch"], :-1],
                                 b["rows"][:b["global_batch"], 1:]))
    batch_devices = {d.id for d in xs.devices()}
    del xs

    steps, elapsed, last_loss = window(run, updater, b)
    peak = runtime.memory_peak_bytes(run.devices)
    if run.traced:
        run.reduce_trace()
    replicas_ok = replicas_equal(updater.state[0], run.devices)
    checks = [
        {"name": k, "value": numbers[k], "limit": chk["limits"][k],
         "ok": numbers[k] <= chk["limits"][k]} for k in sorted(numbers)]
    checks += [
        {"name": "loss_finite_after_window", "value": last_loss,
         "limit": "finite", "ok": bool(np.isfinite(last_loss))},
        {"name": "step_programs", "value": b["step"]._cache_size(),
         "limit": 1, "ok": b["step"]._cache_size() == 1},
        {"name": "programs_lowered_in_window",
         "value": run.compiles_in_window(), "limit": 0,
         "ok": run.compiles_in_window() == 0},
        {"name": "devices_holding_a_batch_shard", "value": len(batch_devices),
         "limit": len(run.devices),
         "ok": batch_devices == {d.id for d in run.devices}},
        {"name": "replicas_bitwise_equal", "value": replicas_ok,
         "limit": True, "ok": replicas_ok},
    ]
    facts = {
        "kind": "train", "window_s": elapsed, "steps": steps,
        "global_batch": b["global_batch"], "seq_len": b["seq_len"],
        "chips": len(run.devices), "peaks": run.peaks,
        "config": run.config, "workload": w, "trace": run.trace,
        "spans": run.spans,
    }
    return {"facts": facts, "checks": checks, "attempted": steps,
            "failed": 0, "memory_peak_bytes": peak}


def calibrate(run, seeds, control):
    """tools/calibrate.py: the readings ``correct`` compares, seed by seed
    through one compiled step; for the seeds in ``control`` also what the
    reference in fp8's precision gives in the program's place."""
    import gc

    b = build(run)
    for seed in seeds:
        run.seed = seed
        set_data(run, b)
        want = reference_readings(run, b)
        out = {"seed": seed}
        if seed in control:
            out["control"] = compare(
                reference_readings(run, b, ref.fake_fp8), want)
        updater, got = program_readings(
            run, b, run.workload["check"]["reference_steps"])
        out.update(sound=compare(got, want), losses=got["losses"])
        yield out
        del updater, got, want
        gc.collect()
