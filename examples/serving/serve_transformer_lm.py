#!/usr/bin/env python
"""Continuous-batching serving example — train briefly, then serve.

Beyond-reference example (upstream ChainerMN had no serving story):
trains a tiny Transformer LM on the synthetic cyclic corpus for a few
hundred steps, publishes the weights through the manifest-verified
warm-weight plane, then stands up the continuous-batching engine behind
the thread-safe frontend and serves a burst of concurrent completions —
printing the ServingReport (TTFT, per-token latency percentiles, queue
depth, occupancy, tokens/s) at the end.

Because the corpus is cyclic with a per-sample stride, a trained model
visibly continues the pattern — the generated suffixes are checkable by
eye against the prompt's stride.

Run (CPU):
    JAX_PLATFORMS=cpu python examples/serving/serve_transformer_lm.py

For the supervised-replica form (restart loop, chaos drills, idempotent
output), see ``tools/serve_lm.py`` and docs/serving.md.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import jax
import jax.numpy as jnp
import optax

from chainermn_tpu.models.transformer import TransformerLM
from chainermn_tpu.serving import (Engine, EngineConfig, Frontend,
                                   publish_weights)


def train(model, steps, batch, length, vocab, lr=1e-2, seed=0):
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, length), jnp.int32))["params"]
    tx = optax.adam(lr)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt, xs, ys):
        def loss_fn(p):
            logits = model.apply({"params": p}, xs)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, ys).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        upd, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, upd), opt, loss

    rng = np.random.RandomState(seed)
    loss = None
    for i in range(steps):
        starts = rng.randint(0, vocab, size=batch)
        strides = rng.randint(1, 4, size=batch)
        pos = np.arange(length + 1)
        seq = (starts[:, None] + strides[:, None] * pos[None]) % vocab
        params, opt, loss = step(params, opt,
                                 jnp.asarray(seq[:, :-1], jnp.int32),
                                 jnp.asarray(seq[:, 1:], jnp.int32))
        if i % 50 == 0:
            print(f"train step {i}: loss {float(loss):.3f}")
    print(f"trained {steps} steps, final loss {float(loss):.3f}")
    return params


def main():
    p = argparse.ArgumentParser(
        description="ChainerMN-TPU example: continuous-batching serving")
    p.add_argument("--train-steps", type=int, default=200)
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=8)
    p.add_argument("--max-new-tokens", type=int, default=16)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--vocab", type=int, default=32)
    p.add_argument("--publish", default=None,
                   help="also publish weights here (the warm-reload "
                        "path supervised replicas boot from)")
    args = p.parse_args()

    model = TransformerLM(vocab=args.vocab, d_model=64, n_heads=4,
                          n_layers=2, d_ff=128, max_len=128,
                          attention="reference", pos_emb="rope")
    params = train(model, args.train_steps, batch=32,
                   length=32, vocab=args.vocab)
    if args.publish:
        publish_weights(params, args.publish)
        print(f"published weights to {args.publish}")

    eng = Engine(model, params,
                 EngineConfig(n_slots=args.slots, capacity=128,
                              max_new_tokens=args.max_new_tokens,
                              prefill_cohort=2))
    rng = np.random.RandomState(1)
    with Frontend(eng) as fe:
        prompts, futs = [], []
        for _ in range(args.requests):
            start, stride = rng.randint(0, args.vocab), rng.randint(1, 4)
            prompt = ((start + stride * np.arange(args.prompt_len))
                      % args.vocab).astype(np.int32)
            prompts.append((prompt, stride))
            futs.append(fe.submit(prompt))
        for (prompt, stride), fut in zip(prompts, futs):
            req = fe.result(fut, timeout_ms=120_000)
            want = ((prompt[-1] + stride * np.arange(
                1, len(req.tokens) + 1)) % args.vocab)
            hits = int(np.sum(np.asarray(req.tokens) == want))
            print(f"prompt(stride={stride}) {prompt.tolist()} -> "
                  f"{req.tokens}  [{hits}/{len(req.tokens)} on-pattern]")
    print(eng.report.json())


if __name__ == "__main__":
    from chainermn_tpu.resilience.supervisor import main_exit_code
    sys.exit(main_exit_code(main))
