"""The serving contracts every layer above the cache rests on, at toy
size, in tier-1.

The suites that explore these in depth (``test_kv_cache.py``,
``test_engine.py``, ``test_sampling.py``, ``test_speculative.py``) are
``slow``; this file holds the headline of each so a run of
``-m 'not slow'`` fails when one breaks:

* one greedy stream, whichever way it is decoded — full recompute, one
  cached step a token, ``decode_k`` steps a dispatch;
* one compiled decode program whatever the traffic, and one prefill
  program a bucket, where recompute compiles once a token;
* at most 8 device→host bytes a token on the emit path;
* speculative streams bitwise the plain engine's, greedy and sampled,
  from one propose and one verify program;
* ``int8-block`` pages at most 1/3.5 of float32 pages' bytes.

``attention="reference"`` throughout: bitwise equality with a row of the
full forward is that kernel's contract (the fast decode path has its own
tolerance suite, ``test_engine_fast_decode.py``).
"""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.models.transformer import TransformerLM
from chainermn_tpu.serving import (Engine, EngineConfig, ServingStep,
                                   SpeculativeEngine)

VOCAB = 64
CAPACITY = 64
N_NEW = 12
PROMPT = (np.arange(1, 9, dtype=np.int32) % VOCAB)[None]      # [1, 8]


def _lm(n_layers, seed):
    lm = TransformerLM(vocab=VOCAB, d_model=32, n_heads=4,
                       n_layers=n_layers, d_ff=64, max_len=CAPACITY,
                       attention="reference", pos_emb="rope")
    params = lm.init(jax.random.PRNGKey(seed),
                     jnp.zeros((1, 4), jnp.int32))["params"]
    return lm, params


@pytest.fixture(scope="module")
def target():
    return _lm(2, 0)


@pytest.fixture(scope="module")
def recompute(target):
    """The naive decode: a full forward over a sequence one token longer
    each step. Its trace count is the control for the counters below."""
    lm, params = target
    traces = [0]

    def fwd(p, t):
        traces[0] += 1
        return lm.apply({"params": p}, t)[:, -1]

    step = jax.jit(fwd)
    toks = jnp.asarray(PROMPT)
    for _ in range(N_NEW):
        nxt = np.asarray(jnp.argmax(step(params, toks), axis=-1))[:, None]
        toks = jnp.concatenate([toks, nxt.astype(np.int32)], axis=1)
    return types.SimpleNamespace(
        tokens=np.asarray(toks)[0, PROMPT.shape[1]:].tolist(),
        traces=traces[0])


@pytest.fixture(scope="module")
def cached(target):
    """One cached step a token through ``ServingStep`` alone."""
    lm, params = target
    steps = ServingStep(lm, params, n_slots=1, capacity=CAPACITY)
    logits = steps.prefill(PROMPT, np.full((1,), PROMPT.shape[1], np.int32),
                           np.zeros((1,), np.int32))
    cur = np.asarray(jnp.argmax(logits, -1), np.int32)
    tokens = [int(cur[0])]
    for _ in range(N_NEW - 1):
        cur = np.asarray(jnp.argmax(steps.decode(cur), -1), np.int32)
        tokens.append(int(cur[0]))
    return types.SimpleNamespace(tokens=tokens, steps=steps)


@pytest.fixture(scope="module")
def decode_k(target):
    """The engine's own path: ``decode_k`` steps a dispatch, sampled on
    the device. A second, longer prompt lands in the other bucket."""
    lm, params = target
    eng = Engine(lm, params,
                 EngineConfig(n_slots=1, capacity=CAPACITY,
                              max_new_tokens=N_NEW, prefill_cohort=1,
                              buckets=[PROMPT.shape[1], CAPACITY],
                              decode_k=4))
    first = eng.submit(PROMPT[0])
    eng.submit(np.arange(3, 23, dtype=np.int32) % VOCAB)
    eng.run_until_drained()
    return types.SimpleNamespace(tokens=list(first.tokens), engine=eng)


@pytest.mark.parametrize("path", ["cached", "decode_k"])
def test_greedy_stream_is_the_recomputed_one(request, recompute, path):
    run = request.getfixturevalue(path)
    assert len(run.tokens) == N_NEW
    assert run.tokens == recompute.tokens


def test_cached_decode_compiles_once_where_recompute_compiles_per_token(
        recompute, cached):
    assert recompute.traces == N_NEW
    assert cached.steps.decode_traces == 1
    assert cached.steps.prefill_traces == {PROMPT.shape: 1}


def test_decode_k_compiles_once_and_prefill_once_a_bucket(decode_k):
    steps = decode_k.engine.steps
    assert steps.decode_k_traces == 1
    assert steps.decode_traces == 0
    assert steps.prefill_traces == {(1, PROMPT.shape[1]): 1,
                                    (1, CAPACITY): 1}


def test_emit_path_moves_at_most_8_host_bytes_a_token(decode_k):
    s = decode_k.engine.report.summary()
    assert s["tokens_emitted"] == 2 * N_NEW
    assert 0 < s["host_bytes_per_token"] <= 8.0


# -- speculative decoding ---------------------------------------------------

SPEC_K = 3
N_SPEC = 8
_SAMPLING = {
    "greedy": lambda i: {},
    "sampled": lambda i: dict(temperature=0.8, top_k=6, seed=31 + i),
}


def _spec_cfg(max_new=N_SPEC):
    return EngineConfig(n_slots=2, capacity=32, max_new_tokens=max_new,
                        prefill_cohort=1, buckets=[8, 32])


def _spec_prompts():
    rng = np.random.RandomState(0)
    return [rng.randint(0, VOCAB, (8,)).astype(np.int32) for _ in range(4)]


def _drain(eng, kws, max_new):
    reqs = [eng.submit(p, max_new_tokens=max_new, **kw)
            for p, kw in zip(_spec_prompts(), kws)]
    eng.run_until_drained()
    return [list(r.tokens) for r in reqs]


@pytest.mark.parametrize("mode", sorted(_SAMPLING))
def test_speculative_stream_is_the_plain_engines(target, mode):
    lm, params = target
    draft, draft_params = _lm(1, 1)
    kws = [_SAMPLING[mode](i) for i in range(4)]
    plain = _drain(Engine(lm, params, _spec_cfg()), kws, N_SPEC)
    spec = SpeculativeEngine(lm, params, draft, draft_params, _spec_cfg(),
                             spec_k=SPEC_K)
    assert _drain(spec, kws, N_SPEC) == plain
    assert spec.draft.propose_traces == 1
    assert spec.verify_traces == 1


def test_self_draft_accepts_every_proposal(target):
    """Draft == target: acceptance is 1 by construction, so anything
    less is the verify pass or the shadow keys losing step. ``max_new``
    is the prefill token plus two full rounds."""
    lm, params = target
    max_new = 1 + 2 * (SPEC_K + 1)
    spec = SpeculativeEngine(lm, params, lm, params, _spec_cfg(max_new),
                             spec_k=SPEC_K)
    _drain(spec, [_SAMPLING["sampled"](i) for i in range(4)], max_new)
    s = spec.report.summary()
    assert s["acceptance_rate"] == 1.0
    assert s["tokens_per_dispatch"] == SPEC_K + 1


def test_int8_block_pages_are_under_a_3p5th_of_f32_pages(target):
    """A count of bytes from shapes, scale sidecars included: not a
    measurement of device memory."""
    lm, params = target
    f32 = ServingStep(lm, params, 2, 32).cache_bytes()
    q8 = ServingStep(lm, params, 2, 32, kv_dtype="int8-block").cache_bytes()
    assert 0 < q8 * 3.5 <= f32
