"""Continuous-batching engine: stream parity, retirement, admission
isolation, and the no-recompile invariant."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.models.transformer import TransformerLM, generate
from chainermn_tpu.serving.engine import (Engine, EngineConfig,
                                          default_buckets)


def _model(**kw):
    # 1 layer: scheduling/retirement don't depend on depth, and the
    # multi-layer cache path is pinned by test_kv_cache.py
    base = dict(vocab=43, d_model=32, n_heads=4, n_layers=1, d_ff=48,
                max_len=64, attention="reference", pos_emb="rope")
    base.update(kw)
    return TransformerLM(**base)


@functools.lru_cache(maxsize=None)
def _setup(seed=0):
    model = _model()
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def test_streams_match_serial_generate():
    """Slotted continuous batching emits, per request, exactly the token
    stream a serial generate() call produces — with requests of mixed
    lengths sharing slots and queueing behind a 2-slot grid."""
    model, params = _setup()
    rng = np.random.RandomState(0)
    lens = [3, 4, 4]
    prompts = [rng.randint(0, 43, (l,)).astype(np.int32) for l in lens]
    n_new = 5
    # exact-length buckets + singleton cohorts: the engine's prefill is
    # shape-identical to generate()'s, so greedy streams pin exactly
    cfg = EngineConfig(n_slots=2, capacity=16, max_new_tokens=n_new,
                       prefill_cohort=1, buckets=sorted(set(lens)) + [16])
    eng = Engine(model, params, cfg)
    reqs = [eng.submit(p) for p in prompts]
    eng.run_until_drained()

    for p, req in zip(prompts, reqs):
        ref = generate(model, params, p[None], n_new)
        np.testing.assert_array_equal(np.asarray(req.tokens),
                                      np.asarray(ref)[0, len(p):])
        assert req.state == "done"


def test_eos_retirement_matches_generate():
    model, params = _setup()
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, 43, (4,)).astype(np.int32)
    n_new = 8
    ref = np.asarray(generate(model, params, prompt[None], n_new))[0, 4:]
    # force a mid-stream retirement: the stream ends at the FIRST
    # occurrence of the eos id, so pick one whose first occurrence is
    # past the prefill token (the stream may repeat a token early)
    cut = next(i for i in range(1, n_new) if ref[i] not in ref[:i])
    eos = int(ref[cut])
    cfg = EngineConfig(n_slots=1, capacity=16, max_new_tokens=n_new,
                       prefill_cohort=1, buckets=[4, 16])
    eng = Engine(model, params, cfg)
    req = eng.submit(prompt, eos_id=eos)
    eng.run_until_drained()
    assert req.tokens == list(ref[:cut + 1])    # ends WITH the eos token
    assert req.state == "done"


def test_retirement_frees_slots():
    """4 requests through 2 slots: every slot is reused, occupancy never
    exceeds the grid, and the engine ends idle with all slots free."""
    model, params = _setup()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, 43, (4,)).astype(np.int32)
               for _ in range(4)]
    cfg = EngineConfig(n_slots=2, capacity=16, max_new_tokens=3,
                       prefill_cohort=2, buckets=[4, 16])
    eng = Engine(model, params, cfg)
    reqs = [eng.submit(p) for p in prompts]
    eng.run_until_drained()
    assert all(r.state == "done" for r in reqs)
    assert all(len(r.tokens) == 3 for r in reqs)
    assert sorted(eng.free_slots) == [0, 1]
    assert eng.idle()
    assert max(eng.report.occupancy_samples) <= 1.0
    s = eng.report.summary()
    assert s["requests"]["completed"] == 4
    assert s["tokens_emitted"] == 12


def test_admission_never_perturbs_other_slots():
    """Mid-flight admission into a free slot leaves every other slot's
    logits BITWISE unchanged: fixed decode shapes + row independence
    make this exact (the integer-valued-float collectives-parity
    pattern, without needing integer weights)."""
    model, params = _setup()
    rng = np.random.RandomState(3)
    pa = rng.randint(0, 43, (4,)).astype(np.int32)
    pb = rng.randint(0, 43, (4,)).astype(np.int32)
    cfg = EngineConfig(n_slots=2, capacity=32, max_new_tokens=10,
                       prefill_cohort=1, buckets=[4, 32])

    def run(with_b):
        eng = Engine(model, params, cfg)
        ra = eng.submit(pa)
        eng.step()                 # admit A, first decode
        solo = []
        slot_a = ra.slot
        for i in range(6):
            if with_b and i == 1:
                eng.submit(pb, max_new_tokens=3)
            eng.step()  # dlint: disable=DL104 — syncs via np.asarray
            solo.append(eng.last_logits[slot_a].copy())
        return ra, solo

    ra1, alone = run(False)
    ra2, crowded = run(True)
    assert ra1.tokens == ra2.tokens
    for a, c in zip(alone, crowded):
        np.testing.assert_array_equal(a, c)


def test_no_recompilation_under_mixed_traffic():
    """Any traffic mix executes ONE decode program and one prefill
    program per bucket — the DL108 invariant, asserted by trace count."""
    model, params = _setup()
    rng = np.random.RandomState(4)
    cfg = EngineConfig(n_slots=3, capacity=32, max_new_tokens=4,
                       prefill_cohort=2, buckets=[4, 8, 32])
    eng = Engine(model, params, cfg)
    for l in (3, 4, 6, 8, 2, 5):
        eng.submit(rng.randint(0, 43, (l,)).astype(np.int32))
        eng.step()  # dlint: disable=DL104 — syncs via np.asarray
    eng.run_until_drained()
    # the multi-token program inherits the invariant: ONE decode_k
    # trace under any traffic mix (the single-step program never runs)
    assert eng.steps.decode_k_traces == 1
    assert eng.steps.decode_traces == 0
    # buckets 4 and 8 were exercised, each compiled exactly once
    assert set(eng.steps.prefill_traces) == {(2, 4), (2, 8)}
    assert all(v == 1 for v in eng.steps.prefill_traces.values())


def test_default_buckets_cover_capacity():
    assert default_buckets(256) == (8, 16, 32, 64, 128, 256)
    assert default_buckets(24) == (8, 16, 24)
    eng_cfg = EngineConfig(n_slots=1, capacity=24)
    assert eng_cfg.bucket_table()[-1] == 24


def test_submit_validation():
    model, params = _setup()
    cfg = EngineConfig(n_slots=1, capacity=8, buckets=[8])
    eng = Engine(model, params, cfg)
    with pytest.raises(ValueError, match="empty"):
        eng.submit(np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="bucket"):
        eng.submit(np.zeros((9,), np.int32))


def test_abort_all_requeue_preserves_requests():
    model, params = _setup()
    cfg = EngineConfig(n_slots=1, capacity=16, max_new_tokens=6,
                       prefill_cohort=1, buckets=[4, 16])
    eng = Engine(model, params, cfg)
    rng = np.random.RandomState(5)
    pr = rng.randint(0, 43, (4,)).astype(np.int32)
    r1 = eng.submit(pr)
    eng.step()
    assert r1.state == "running" and r1.tokens
    hit = eng.abort_all(requeue=True)
    assert len(hit) == 1 and hit[0] is r1
    assert r1.state == "queued" and not r1.tokens
    assert eng.free_slots == [0] and not eng.active
    # the requeued request replays to the same stream as a fresh run
    eng.run_until_drained()
    ref = generate(model, params, pr[None], 6)
    np.testing.assert_array_equal(np.asarray(r1.tokens),
                                  np.asarray(ref)[0, 4:])


# numerics-heavy compile farm: covered nightly via the full run,
# excluded from the tier-1 wall-clock budget
pytestmark = pytest.mark.slow
