#!/usr/bin/env python
"""chip_smoke — the two main paths, end to end, on every chip JAX finds.

One process, two legs, one model (the 135M decoder of the gated LM rows:
vocab 32768, d_model 768, 12 heads of 64, 12 layers, d_ff 3072, RoPE,
bf16, max_len 2048), both through the entry points a user calls:

* **train** — ``create_communicator`` → ``bcast_data`` →
  ``create_multi_node_optimizer(adamw)`` →
  ``make_data_parallel_train_step(loss_fn=fused_lm_loss)`` with the
  Pallas flash kernels (bhld layout), driven by ``SerialIterator`` →
  ``StandardUpdater`` → ``Trainer`` on ``synthetic_text``. Data-parallel
  over every chip (batch 4 per chip, L=2048).
* **serve** — the trained parameters handed to ``serving.Engine`` at
  capacity 2048, one engine per chip on a one-device mesh; one chip
  drains with ``run_until_drained()``, several sit behind
  ``fleet.Router``. Mixed prompt lengths (one over 1k tokens), greedy
  and sampled.

What is checked is numbers, not streams: the first-step loss against
the float32 reference-attention / unfused-loss value, and the engine's
prefill-then-decode logits against a float32 full forward, both at
``"highest"`` matmul precision. On a TPU a float32 matmul runs in
reduced precision by default, so the repo's BITWISE stream contracts
belong to the CPU tests and are not expected to hold here.

The legs are importable and take widths (tests/test_chip_smoke.py runs
them at toy size on the CPU mesh, kernels interpreted); only ``main()``
insists on a TPU. Exit status is non-zero when the platform is not
``tpu``, when a check fails, or when anything raises. The last line of
stdout is ``{"ok": true, "device": {...}}``.

    python chip_smoke.py
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

MODEL_135M = dict(vocab=32768, d_model=768, n_heads=12, n_layers=12,
                  d_ff=3072, max_len=2048)

# |first-step loss − float32 reference loss|. The step computes in bf16
# (8 mantissa bits) through 12 layers and a bf16 head matmul, but the loss
# is a mean over >=8k tokens, so the roundings average out: measured 3e-5
# on the v5e at a loss of 10.88 (my chip run, PR 21). The bound sits ~150x
# above that and ~100x below the 0.49 the random-init logits add to
# ln(vocab) — a kernel that computes something else moves the loss more.
TRAIN_LOSS_TOL = 0.005
# max |engine logit − float32 full-forward logit| over the vocabulary at
# the last position, relative to the reference logits' spread (std). The
# engine runs bf16 activations and bf16 KV pages against f32 "highest";
# every logit carries a few bf16 roundings of O(1) activations: measured
# 0.041–0.047 at std 1.04 on the v5e (my chip run, PR 21), ~4.5% of the
# spread. The bound leaves 3x.
SERVE_LOGIT_TOL = 0.15

# per-stream budget: the prefill token plus two decode_k=4 dispatches, so a
# stream ends on a dispatch boundary and the engine's last logits are its own
_NEW_TOKENS = 9

_TRAIN_KERNELS = {"flash_fwd", "flash_bwd_fused", "fused_ce_fwd",
                  "fused_ce_dh", "fused_ce_dw"}
_PREFILL_KERNELS = {"flash_fwd"}


class SmokeFailure(RuntimeError):
    """A leg ran but one of its checks did not hold."""


def _check(ok, what):
    if not ok:
        raise SmokeFailure(what)


def build_model(widths):
    """The training-layout model: flash attention, head-major tensors."""
    import jax.numpy as jnp

    from chainermn_tpu.models.transformer import TransformerLM

    return TransformerLM(**widths, pos_emb="rope", attention="flash",
                         dtype=jnp.bfloat16, qkv_layout="bhld")


def _reference_model(model):
    """float32, XLA reference attention, blhd — the oracle both legs
    compare against (parameters via ``bhld_to_blhd_params``)."""
    import jax.numpy as jnp

    return model.clone(attention="reference", qkv_layout="blhd",
                       dtype=jnp.float32)


def _mosaic_kernels(lowered_text):
    """Names of the Mosaic (compiled Pallas) kernels in a lowered
    program — empty when the kernels were interpreted."""
    import re

    if "tpu_custom_call" not in lowered_text:
        return set()
    return set(re.findall(r'kernel_name = "([^"]+)"', lowered_text))


def _peak_bytes(devices):
    """Per-device ``peak_bytes_in_use`` (a running peak over the process;
    None where the backend keeps no statistics, e.g. the CPU)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def _devices_of(tree):
    import jax

    return sorted({d.id for leaf in jax.tree_util.tree_leaves(tree)
                   for d in leaf.devices()})


def train_leg(widths, *, seq_len=2048, batch_per_chip=4, steps=30,
              grad_accum=1, lr=3e-4, seed=0):
    """Train ``steps`` steps data-parallel over every device; returns
    ``(model, params, report)``. Raises :class:`SmokeFailure` when a
    check does not hold."""
    import jax
    import optax

    import chainermn_tpu
    from chainermn_tpu.iterators import SerialIterator
    from chainermn_tpu.models.transformer import (bhld_to_blhd_params,
                                                  lm_loss_with_aux)
    from chainermn_tpu.ops import fused_lm_loss
    from chainermn_tpu.training import LogReport, StandardUpdater, Trainer
    from chainermn_tpu.training.step import (make_data_parallel_train_step,
                                             make_eval_step)
    from chainermn_tpu.training.trainer import default_converter
    from examples.transformer_lm.train_lm import synthetic_text

    comm = chainermn_tpu.create_communicator("xla")
    model = build_model(widths)
    global_batch = batch_per_chip * comm.size
    # a small corpus the run revisits (every window is seen ~steps/4
    # times): the loss must FALL, not merely settle at ln(vocab)
    train = synthetic_text(4 * global_batch, seq_len, widths["vocab"],
                           seed=seed)
    sample = np.zeros((1, seq_len), np.int32)
    params = comm.bcast_data(
        jax.jit(model.init)(jax.random.PRNGKey(seed), sample)["params"])
    opt = chainermn_tpu.create_multi_node_optimizer(optax.adamw(lr), comm)
    step = make_data_parallel_train_step(model, opt, comm,
                                         loss_fn=fused_lm_loss,
                                         grad_accum=grad_accum)
    state = (params, opt.init(params))

    # the oracle for step 0: same parameters, same first batch, float32
    # reference attention + unfused loss at "highest" precision, through
    # the repo's own eval step (before the train step donates `params`)
    x0, y0 = default_converter(
        next(SerialIterator(train, global_batch, shuffle=True, seed=seed)))
    updater = StandardUpdater(
        SerialIterator(train, global_batch, shuffle=True, seed=seed),
        step, state, comm)
    xs0, ys0 = updater.shard_batch((x0, y0))
    ref_eval = make_eval_step(_reference_model(model), comm,
                              loss_fn=lm_loss_with_aux)
    with jax.default_matmul_precision("highest"):
        ref_loss = float(ref_eval(
            (bhld_to_blhd_params(model, params), {}), xs0, ys0
        )["validation/main/loss"])
    kernels = _mosaic_kernels(step.lower(state, xs0, ys0).as_text())

    trainer = Trainer(updater, stop_trigger=(steps, "iteration"))
    log = LogReport()
    trainer.extend(log, trigger=(1, "iteration"))
    trainer.run()

    losses = [obs["main/loss"] for obs in log.log]
    elapsed = [obs["elapsed_time"] for obs in log.log]
    step_s = float(np.median(np.diff(elapsed))) if steps > 1 else 0.0
    params, opt_state = updater.state
    n_dev = comm.size
    report = {
        "leg": "train", "devices": n_dev, "mesh": dict(comm.mesh.shape),
        "global_batch": global_batch, "seq_len": seq_len,
        "steps": len(losses),
        # first call minus a steady step: trace + lower + compile (or the
        # compile cache's load)
        "compile_s": round(elapsed[0] - step_s, 2),
        "step_s": round(step_s, 4),
        "step_programs": step._cache_size(),
        "first_loss": round(losses[0], 4), "last_loss": round(losses[-1], 4),
        "ref_first_loss": round(ref_loss, 4),
        "first_loss_err": round(abs(losses[0] - ref_loss), 5),
        "losses": [round(v, 4) for v in losses],
        "mosaic_kernels": sorted(kernels),
        "batch_devices": _devices_of((xs0, ys0)),
        "opt_state_devices": _devices_of(opt_state),
        "peak_bytes_in_use": _peak_bytes(comm.mesh.devices.flat),
    }
    print(json.dumps(report), flush=True)

    _check(len(losses) == steps, f"trainer stopped after {len(losses)} "
                                 f"of {steps} steps")
    _check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    _check(report["step_programs"] == 1,
           f"the train step compiled {report['step_programs']} programs "
           "for one shape")
    _check(losses[-1] < losses[0],
           f"loss did not fall: {losses[0]} -> {losses[-1]}")
    _check(abs(losses[0] - ref_loss) <= TRAIN_LOSS_TOL,
           f"first-step loss {losses[0]} vs float32 reference {ref_loss}: "
           f"off by more than {TRAIN_LOSS_TOL}")
    _check(len(report["batch_devices"]) == n_dev
           and len(report["opt_state_devices"]) == n_dev,
           "batch or optimizer state does not address every device: "
           f"{report['batch_devices']} / {report['opt_state_devices']}")
    return model, params, report


def _requests(capacity, vocab, seed):
    """The traffic mix: three short prompts and one over half the page
    (>1k tokens at capacity 2048), greedy but for one sampled stream."""
    rs = np.random.RandomState(seed)
    lens = [5, min(24, capacity // 4), min(100, capacity // 3),
            capacity // 2 + capacity // 32, 7]
    reqs = [dict(prompt=rs.randint(0, vocab, (n,)).astype(np.int32),
                 max_new_tokens=_NEW_TOKENS) for n in lens]
    reqs[-1].update(temperature=0.8, top_k=50, seed=seed + 7)
    return reqs


def _logit_parity(engine, full_forward, prompt, capacity):
    """Prefill ``prompt`` and decode on an otherwise idle engine, then
    compare the engine's final decode-step logits with the float32 full
    forward over prompt + emitted tokens at the same position. Returns
    (max abs error, reference std)."""
    req = engine.submit(prompt, max_new_tokens=_NEW_TOKENS)
    engine.step()              # admission binds the slot
    slot = req.slot
    engine.run_until_drained()
    _check(len(req.tokens) == _NEW_TOKENS,
           f"parity stream stopped at {req.tokens}")
    got = engine.last_logits[slot]
    # the last decode step consumed tokens[-2] and produced tokens[-1]
    seq = np.concatenate([prompt, np.asarray(req.tokens[:-1], np.int32)])
    padded = np.zeros((1, capacity), np.int32)
    padded[0, :seq.size] = seq
    want = np.asarray(full_forward(padded)[0, seq.size - 1])
    return float(np.max(np.abs(got - want))), float(np.std(want))


def serve_leg(model, params, *, devices, capacity=2048, n_slots=4, seed=0):
    """Serve the traffic mix on one engine per device; returns the
    report. Raises :class:`SmokeFailure` when a check does not hold."""
    import jax
    from jax.sharding import Mesh

    from chainermn_tpu.fleet import Router
    from chainermn_tpu.models.transformer import bhld_to_blhd_params
    from chainermn_tpu.serving import Engine, EngineConfig
    from chainermn_tpu.serving.kv_cache import prefill_apply

    cfg = EngineConfig(n_slots=n_slots, capacity=capacity)
    engines = [Engine(model, params, cfg, mesh=Mesh(np.array([d]), ("serve",)))
               for d in devices]
    placement = [{"device": d.id,
                  "params_on": _devices_of(e.steps.params),
                  "pages_on": _devices_of(e.steps.cache)}
                 for d, e in zip(devices, engines)]
    reqs = _requests(capacity, model.vocab, seed)

    def timed(drain):
        t0 = time.perf_counter()
        done = drain()
        return done, time.perf_counter() - t0

    # the mix twice: the cold pass compiles every program it touches, the
    # warm pass runs the same mix on compiled programs
    if len(engines) == 1:
        def drain():
            done = [engines[0].submit(**r) for r in reqs]
            engines[0].run_until_drained()
            return done

        (cold, cold_s), (warm, warm_s) = timed(drain), timed(drain)
    else:
        # a replica mid-compile does not heartbeat: the deadline has to
        # outlast the longest compile, not a decode step
        with Router(engines, health_timeout_ms=900_000) as router:
            def drain():
                # a session tag is sticky: the warm pass lands each
                # stream on the replica that compiled its bucket
                futs = [router.submit(r["prompt"], session=f"s{i}", **{
                    k: v for k, v in r.items() if k != "prompt"})
                    for i, r in enumerate(reqs)]
                return [router.result(f, timeout_ms=900_000) for f in futs]

            (cold, cold_s), (warm, warm_s) = timed(drain), timed(drain)
    for r in cold + warm:
        _check(r.state == "done" and len(r.tokens) == r.max_new_tokens,
               f"request {r.request_id} ended {r.state} with "
               f"{len(r.tokens)}/{r.max_new_tokens} tokens")

    # logit parity, per engine, on an idle grid (row independence keeps
    # the empty slots out of it): the shortest prompt (a bf16 slab of 8
    # rows through the flash forward) and the longest (>1k rows)
    ref = _reference_model(model)
    ref_params = bhld_to_blhd_params(model, params)

    ref_forward = jax.jit(lambda p, tokens: ref.apply({"params": p}, tokens))

    def full_forward(tokens):
        # the ORACLE alone runs at "highest"; the engine keeps the
        # precision it serves with
        with jax.default_matmul_precision("highest"):
            return ref_forward(ref_params, tokens)

    errs = []
    for eng in engines:
        for r in (reqs[0], reqs[3]):
            err, std = _logit_parity(eng, full_forward, r["prompt"],
                                     capacity)
            errs.append({"prompt_len": int(r["prompt"].size),
                         "max_abs_err": round(err, 5),
                         "ref_std": round(std, 4)})

    # what the prefill programs lower to: the pure function the engine's
    # per-bucket program wraps, at the smallest and largest bucket used
    steps0 = engines[0].steps
    buckets = sorted({k for e in engines for k in e.steps.prefill_traces})
    prefill_kernels = set()
    for s_rows, width in (buckets[0], buckets[-1]):
        lowered = jax.jit(functools.partial(prefill_apply, steps0.dm)).lower(
            steps0.params, steps0.cache,
            np.zeros((s_rows, width), np.int32),
            np.ones(s_rows, np.int32), np.zeros(s_rows, np.int32))
        prefill_kernels |= _mosaic_kernels(lowered.as_text())

    report = {
        "leg": "serve", "engines": len(engines),
        "front_door": "Engine.run_until_drained" if len(engines) == 1
        else "fleet.Router",
        "requests": len(cold) + len(warm) + len(errs),
        "tokens": (sum(len(r.tokens) for r in cold + warm)
                   + _NEW_TOKENS * len(errs)),
        "prompt_lens": [int(r["prompt"].size) for r in reqs],
        # the cold pass minus the same pass on compiled programs
        "compile_s": round(cold_s - warm_s, 2),
        "warm_drain_s": round(warm_s, 3),
        "decode_k_traces": [e.steps.decode_k_traces for e in engines],
        "prefill_buckets": buckets,
        "prefill_traces_max": max(n for e in engines
                                  for n in e.steps.prefill_traces.values()),
        # monolithic per-bucket prefill through model.attention; chunked
        # prefill (the XLA einsum path) is off unless prefill_chunk is set
        "prefill_attention": steps0.dm.attention,
        "prefill_mosaic_kernels": sorted(prefill_kernels),
        "logit_parity": errs,
        "placement": placement,
        "peak_bytes_in_use": _peak_bytes(devices),
    }
    print(json.dumps(report), flush=True)

    _check(all(n == 1 for n in report["decode_k_traces"]),
           f"decode_k traced {report['decode_k_traces']} times, want 1 "
           "per engine")
    _check(report["prefill_traces_max"] == 1,
           "a prefill bucket was traced "
           f"{report['prefill_traces_max']} times on one engine")
    for e in errs:
        _check(e["max_abs_err"] <= SERVE_LOGIT_TOL * e["ref_std"],
               f"prefill-then-decode logits off the float32 full forward "
               f"by {e['max_abs_err']} (> {SERVE_LOGIT_TOL} x std "
               f"{e['ref_std']}) at prompt length {e['prompt_len']}")
    for p in placement:
        _check(p["params_on"] == [p["device"]]
               and p["pages_on"] == [p["device"]],
               f"engine for device {p['device']} holds parameters on "
               f"{p['params_on']} and pages on {p['pages_on']}")
    return report


def main():
    import jax

    from chainermn_tpu.utils import use_compile_cache

    cache_dir = use_compile_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(json.dumps({"device": device, "compile_cache": cache_dir}),
          flush=True)
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, found platform "
              f"{device['platform']!r} ({device['kind']})", file=sys.stderr)
        return 1

    model, params, train = train_leg(MODEL_135M)
    serve = serve_leg(model, params, devices=devices)

    _check(_TRAIN_KERNELS <= set(train["mosaic_kernels"]),
           "the lowered train step lacks Mosaic kernels "
           f"{sorted(_TRAIN_KERNELS - set(train['mosaic_kernels']))}: "
           "the Pallas interpreter ran in their place")
    _check(_PREFILL_KERNELS <= set(serve["prefill_mosaic_kernels"]),
           "the lowered prefill lacks the Mosaic flash forward")
    for leg in (train, serve):
        _check(all(leg["peak_bytes_in_use"]),
               f"{leg['leg']}: a device reports no peak memory: "
               f"{leg['peak_bytes_in_use']}")
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(json.dumps({"compile_cache": cache_dir, "entries": entries}),
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
