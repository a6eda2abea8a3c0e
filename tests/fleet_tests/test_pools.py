"""Disaggregated prefill/decode: raw-format streams are BITWISE the
single-engine streams (and generate()'s), across chunked and monolithic
prefill; corrupt handoffs fall back to a clean re-prefill that still
matches; quantized handoffs drain with the wire accounted."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.fleet import DisaggregatedFleet, FleetReport
from chainermn_tpu.models.transformer import TransformerLM, generate
from chainermn_tpu.serving.engine import Engine, EngineConfig

# numerics-heavy compile farm: covered nightly via the full run,
# excluded from the tier-1 wall-clock budget
pytestmark = pytest.mark.slow

VOCAB = 43
N_NEW = 6


def _model(**kw):
    base = dict(vocab=VOCAB, d_model=32, n_heads=4, n_layers=1, d_ff=48,
                max_len=64, attention="reference", pos_emb="rope")
    base.update(kw)
    return TransformerLM(**base)


@functools.lru_cache(maxsize=None)
def _setup(seed=0):
    model = _model()
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def _cfg(**kw):
    # exact-length buckets + singleton cohorts: prefill is shape-
    # identical to generate()'s, so greedy streams pin exactly
    base = dict(n_slots=2, capacity=16, max_new_tokens=N_NEW,
                prefill_cohort=1, buckets=[3, 4, 16])
    base.update(kw)
    return EngineConfig(**base)


def _prompts(seed=0, lens=(3, 4, 4, 3)):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, (l,)).astype(np.int32) for l in lens]


@pytest.mark.parametrize("chunk", [3, 5])
def test_raw_disagg_streams_bitwise_vs_single_engine(chunk):
    """The acceptance bitwise gate with a chunked prefill on engine A,
    decode on engine B: stream == single-engine Engine == generate(),
    token for token. (Monolithic prefill: tier-1,
    ``test_handoff.py::test_raw_disagg_streams_bitwise_vs_single_engine``.)"""
    model, params = _setup()
    prompts = _prompts()
    pre_cfg = _cfg(prefill_chunk=chunk, buckets=None)
    fleet = DisaggregatedFleet(Engine(model, params, pre_cfg),
                               Engine(model, params, _cfg()))
    streams = [fleet.submit(p, max_new_tokens=N_NEW) for p in prompts]
    fleet.run_until_drained()

    single = Engine(model, params, _cfg())
    reqs = [single.submit(p, max_new_tokens=N_NEW) for p in prompts]
    single.run_until_drained()

    for p, s, r in zip(prompts, streams, reqs):
        ref = np.asarray(generate(model, params, p[None], N_NEW))[0, len(p):]
        np.testing.assert_array_equal(np.asarray(s.tokens), ref)
        np.testing.assert_array_equal(np.asarray(r.tokens), ref)
        assert s.finished and not s.fell_back
    assert fleet.report.handoffs == len(prompts)
    assert fleet.report.handoff_fallbacks == 0


def test_sampled_disagg_streams_bitwise_vs_single_engine():
    """Stochastic sampling crosses the handoff bitwise too: the key
    CONTINUES (one split consumed by the prefill token), so the decode
    pool's tokens equal the single engine's under the same seed."""
    model, params = _setup()
    prompts = _prompts(seed=5)
    kw = dict(temperature=0.8, top_k=7)
    fleet = DisaggregatedFleet(Engine(model, params, _cfg()),
                               Engine(model, params, _cfg()))
    streams = [fleet.submit(p, max_new_tokens=N_NEW, seed=i, **kw)
               for i, p in enumerate(prompts)]
    fleet.run_until_drained()

    single = Engine(model, params, _cfg())
    reqs = [single.submit(p, max_new_tokens=N_NEW, seed=i, **kw)
            for i, p in enumerate(prompts)]
    single.run_until_drained()

    for s, r in zip(streams, reqs):
        assert s.tokens == r.tokens


def test_int8_handoff_drains_with_wire_accounted():
    model, params = _setup()
    prompts = _prompts()
    report = FleetReport()
    fleet = DisaggregatedFleet(Engine(model, params, _cfg()),
                               Engine(model, params, _cfg()),
                               wire_format="int8-block", report=report)
    streams = [fleet.submit(p, max_new_tokens=N_NEW) for p in prompts]
    fleet.run_until_drained()
    assert all(s.finished and len(s.tokens) == N_NEW for s in streams)
    assert report.handoffs == len(prompts)
    assert report.handoff_wire_bytes["int8-block"] > 0
    summary = fleet.summary()
    assert summary["fleet"]["handoffs"] == len(prompts)
    assert summary["requests"]["completed"] == 2 * len(prompts)


def test_corrupt_handoff_falls_back_to_clean_reprefill(monkeypatch):
    """Chaos flips wire bytes on every handoff → the decode pool
    refuses each one and re-prefills from scratch; the client streams
    still match generate() bitwise, no slot is poisoned, and the
    fallbacks are counted."""
    monkeypatch.setenv("CHAINERMN_TPU_CHAOS", "corrupt_handoff@offset=64")
    model, params = _setup()
    prompts = _prompts()
    fleet = DisaggregatedFleet(Engine(model, params, _cfg()),
                               Engine(model, params, _cfg()))
    streams = [fleet.submit(p, max_new_tokens=N_NEW) for p in prompts]
    fleet.run_until_drained()
    for p, s in zip(prompts, streams):
        ref = np.asarray(generate(model, params, p[None], N_NEW))[0, len(p):]
        np.testing.assert_array_equal(np.asarray(s.tokens), ref)
        assert s.fell_back
    assert fleet.report.handoff_fallbacks == len(prompts)
    # no poisoned slots: both engines end idle with every slot free
    assert sorted(fleet.decode.engine.free_slots) == [0, 1]
    assert sorted(fleet.prefill.engine.free_slots) == [0, 1]


def test_truncated_handoff_falls_back(monkeypatch):
    """keep=N truncates the wire blob mid-array — the length check
    refuses it before the digest is even computed."""
    monkeypatch.setenv("CHAINERMN_TPU_CHAOS", "corrupt_handoff@keep=32")
    model, params = _setup()
    prompts = _prompts()[:2]
    fleet = DisaggregatedFleet(Engine(model, params, _cfg()),
                               Engine(model, params, _cfg()))
    streams = [fleet.submit(p, max_new_tokens=N_NEW) for p in prompts]
    fleet.run_until_drained()
    for p, s in zip(prompts, streams):
        ref = np.asarray(generate(model, params, p[None], N_NEW))[0, len(p):]
        np.testing.assert_array_equal(np.asarray(s.tokens), ref)
        assert s.fell_back


def test_eos_at_prefill_crosses_handoff():
    """A stream whose FIRST token is eos arrives at the decode pool
    already terminal — import retires it immediately and the stream
    still reports exactly the single-engine tokens."""
    model, params = _setup()
    prompt = _prompts()[0]
    ref = np.asarray(generate(model, params, prompt[None], N_NEW))[0,
                                                                   len(prompt):]
    eos = int(ref[0])              # force termination at the handoff
    fleet = DisaggregatedFleet(Engine(model, params, _cfg()),
                               Engine(model, params, _cfg()))
    stream = fleet.submit(prompt, max_new_tokens=N_NEW, eos_id=eos)
    fleet.run_until_drained()
    assert stream.tokens == [eos]
    assert stream.finished


def test_streamed_disagg_streams_bitwise_vs_generate():
    """Format-5 per-layer chunk frames assemble back to the exact
    handoff: streamed raw streams match generate() bitwise and the
    report prices the streamed wire byte-exact vs the monolithic
    blob."""
    model, params = _setup()
    prompts = _prompts()
    report = FleetReport()
    fleet = DisaggregatedFleet(Engine(model, params, _cfg()),
                               Engine(model, params, _cfg()),
                               streamed=True, report=report)
    streams = [fleet.submit(p, max_new_tokens=N_NEW) for p in prompts]
    fleet.run_until_drained()
    for p, s in zip(prompts, streams):
        ref = np.asarray(generate(model, params, p[None], N_NEW))[0, len(p):]
        np.testing.assert_array_equal(np.asarray(s.tokens), ref)
        assert not s.fell_back
    assert report.handoffs == len(prompts)

    mono = DisaggregatedFleet(Engine(model, params, _cfg()),
                              Engine(model, params, _cfg()),
                              report=FleetReport())
    for p in prompts:
        mono.submit(p, max_new_tokens=N_NEW)
    mono.run_until_drained()
    assert report.handoff_wire_bytes["f32"] \
        == mono.report.handoff_wire_bytes["f32"]
