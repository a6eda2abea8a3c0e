"""On-device sampling, decode_k, and chunked prefill: the bitwise
contracts ISSUE 10 promises.

Four families of pins:

* **Greedy parity** — on-device argmax sampling is bit-identical to the
  host ``np.argmax`` path it replaced, and one ``decode_k`` dispatch
  equals ``k`` single-step decodes token-for-token.
* **Chunked == monolithic** — prefilling a prompt in fixed-size chunks
  leaves the SAME cache bytes and samples the SAME first token as one
  monolithic prefill, for every chunk size (including sizes that don't
  divide the prompt and chunks crossing bucket boundaries).
* **Seed determinism** — a fixed per-request seed replays the same
  sampled stream under any scheduler shape (``decode_k``, chunking,
  neighbouring traffic), because each slot consumes exactly one key
  split per sampled token.
* **Top-k by selection == top-k by sort** (tier-1; the families above
  are ``slow``) — ``sample_tokens`` finds the k-th largest logit
  without sorting the vocabulary and returns bitwise the tokens and keys
  of the sort form it replaced, which lives on here as the oracle; and
  no compiled ``decode_k`` program sorts along the vocabulary.
"""

import functools
import re

import numpy as np

import jax
import jax.numpy as jnp

from chainermn_tpu.models.transformer import TransformerLM, generate
from chainermn_tpu.serving.engine import Engine, EngineConfig
from chainermn_tpu.serving import kv_cache, sampling
from chainermn_tpu.serving.kv_cache import (ServingStep, decode_k_apply,
                                            init_cache)
from chainermn_tpu.serving.sampling import init_keys, sample_tokens

import pytest
# numerics-heavy compile farm: covered nightly via the full run,
# excluded from the tier-1 wall-clock budget
slow = pytest.mark.slow


# single layer keeps compiles cheap — the contracts here are about
# scheduling and sampling, not depth (the cache-bytes test opts into 2)
@functools.lru_cache(maxsize=None)
def _setup(seed=0, n_layers=1):
    model = TransformerLM(vocab=43, d_model=32, n_heads=4,
                          n_layers=n_layers, d_ff=48, max_len=64,
                          attention="reference", pos_emb="rope")
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def _prompts(seed, lens, vocab=43):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, (l,)).astype(np.int32) for l in lens]


def _stream_with_fresh_id(model, params, plen, n_new):
    """(prompt, greedy stream, i) where ref[i] does NOT occur earlier in
    the stream — an eos candidate whose stop mask can only fire at step
    i. Tiny-vocab greedy streams repeat values quickly, so probe prompt
    seeds until one qualifies (generate() is cached per prompt length)."""
    for ps in range(32):
        p = _prompts(ps, [plen])[0]
        ref = np.asarray(generate(model, params, p[None], n_new))[0, plen:]
        i = next((j for j in range(2, len(ref)) if ref[j] not in ref[:j]),
                 None)
        if i is not None:
            return p, ref, i
    raise AssertionError("no greedy stream with a fresh mid-stream id")


# --------------------------------------------------------------------
# greedy parity: device sampling == host argmax
# --------------------------------------------------------------------

@slow
def test_greedy_sampling_matches_host_argmax_bitwise():
    """temperature <= 0 rows are a plain jnp.argmax — identical ids to
    np.argmax over the same logits, ties resolved to the first index."""
    rng = np.random.RandomState(0)
    logits = rng.randn(5, 43).astype(np.float32)
    logits[2, 7] = logits[2, 11] = logits[2].max() + 1.0   # forced tie
    toks, _ = jax.jit(sample_tokens)(
        jnp.asarray(logits), init_keys(5),
        np.zeros(5, np.float32), np.zeros(5, np.int32))
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.argmax(logits, axis=-1))
    assert int(np.asarray(toks)[2]) == 7      # first-index tie rule


@slow
def test_decode_k_equals_k_single_steps_greedy():
    """One decode_k dispatch == k single-step decodes, token for token,
    against an identically prefilled grid (same params, same cache)."""
    model, params = _setup()
    prompts = _prompts(1, [4, 4])
    k = 5

    # reference: prefill + k host-argmax single steps (the old hot loop)
    ref = ServingStep(model, params, n_slots=2, capacity=32)
    last = np.asarray(ref.prefill(np.stack(prompts), [4, 4], [0, 1]))
    cur = np.argmax(last, axis=-1).astype(np.int32)
    t0 = cur.copy()
    want = []
    for _ in range(k):
        logits = ref.decode(cur)
        cur = np.asarray(jnp.argmax(logits, axis=-1), dtype=np.int32)
        want.append(cur.copy())
    want = np.stack(want, axis=1)              # [2, k]

    dev = ServingStep(model, params, n_slots=2, capacity=32)
    tok0, keys = dev.prefill_sampled(
        np.stack(prompts), [4, 4], [0, 1], init_keys(2),
        np.zeros(2, np.float32), np.zeros(2, np.int32))
    np.testing.assert_array_equal(np.asarray(tok0), t0)
    toks, _ = dev.decode_k(
        np.asarray(tok0), keys, np.zeros(2, np.float32),
        np.zeros(2, np.int32), np.full(2, -1, np.int32),
        np.full(2, 100, np.int32), np.ones(2, bool),
        np.zeros(2, np.int32), k)
    np.testing.assert_array_equal(np.asarray(toks), want)
    assert dev.decode_k_traces == 1


@slow
def test_decode_k_eos_and_budget_masks():
    """The in-scan stop masks: a slot that emits eos_id stops (later
    columns are -1), and `remaining` caps emissions exactly."""
    model, params = _setup()
    p, ref, i = _stream_with_fresh_id(model, params, plen=4, n_new=6)
    eos = int(ref[i])
    st = ServingStep(model, params, n_slots=1, capacity=32)
    tok0, keys = st.prefill_sampled(
        p[None], [4], [0], init_keys(1), np.zeros(1, np.float32),
        np.zeros(1, np.int32))
    toks, _ = st.decode_k(
        np.asarray(tok0), keys, np.zeros(1, np.float32),
        np.zeros(1, np.int32), np.asarray([eos], np.int32),
        np.full(1, 100, np.int32), np.ones(1, bool),
        np.zeros(1, np.int32), 5)
    got = np.asarray(toks)[0]
    assert int(got[i - 1]) == eos              # ref[i] is decode_k col i-1
    assert all(int(t) == -1 for t in got[i:])  # stopped after eos
    # budget mask: remaining=2 emits exactly 2 then parks
    st2 = ServingStep(model, params, n_slots=1, capacity=32)
    tok0, keys = st2.prefill_sampled(
        p[None], [4], [0], init_keys(1), np.zeros(1, np.float32),
        np.zeros(1, np.int32))
    toks, _ = st2.decode_k(
        np.asarray(tok0), keys, np.zeros(1, np.float32),
        np.zeros(1, np.int32), np.full(1, -1, np.int32),
        np.asarray([2], np.int32), np.ones(1, bool),
        np.zeros(1, np.int32), 5)
    got = np.asarray(toks)[0]
    assert int(got[0]) >= 0 and int(got[1]) >= 0
    assert all(int(t) == -1 for t in got[2:])


# --------------------------------------------------------------------
# chunked prefill == monolithic, bitwise (tokens AND cache bytes)
# --------------------------------------------------------------------

@slow
def test_chunked_prefill_matches_monolithic_cache_bitwise():
    """Every chunk size — dividing, non-dividing, and full-prompt —
    writes byte-identical K/V pages and cursors to one monolithic
    prefill, and samples the same first token."""
    model, params = _setup(n_layers=2)     # every block's page checked
    p = _prompts(3, [13])[0]
    mono = ServingStep(model, params, n_slots=2, capacity=32)
    tok_m, _ = mono.prefill_sampled(
        p[None], [13], [0], init_keys(2), np.zeros(2, np.float32),
        np.zeros(2, np.int32))
    want = int(np.asarray(tok_m)[0])
    ref_cache = jax.device_get(mono.cache)

    for c in (3, 5, 13):
        st = ServingStep(model, params, n_slots=2, capacity=32)
        keys = init_keys(2)
        pos = 0
        while pos < 13:
            v = min(c, 13 - pos)
            toks = np.zeros((1, c), np.int32)
            toks[0, :v] = p[pos:pos + v]
            tok, keys = st.prefill_chunk(
                toks, [pos], [v], [0], [pos + v == 13], keys,
                np.zeros(2, np.float32), np.zeros(2, np.int32))
            pos += v
            if pos < 13:
                assert int(np.asarray(tok)[0]) == -1   # not final yet
        assert int(np.asarray(tok)[0]) == want, f"chunk={c}"
        got_cache = jax.device_get(st.cache)
        for name in ref_cache:
            np.testing.assert_array_equal(
                got_cache[name]["k"][0, :13], ref_cache[name]["k"][0, :13],
                err_msg=f"chunk={c} {name} K")
            np.testing.assert_array_equal(
                got_cache[name]["v"][0, :13], ref_cache[name]["v"][0, :13],
                err_msg=f"chunk={c} {name} V")
            assert got_cache[name]["idx"][0] == 13
        assert len(st.prefill_chunk_traces) == 1      # ONE (S, C) program


@slow
def test_engine_chunked_streams_match_generate():
    """End to end: the chunked+budgeted scheduler emits exactly the
    serial generate() streams — chunk sizes straddling the old bucket
    boundaries, prompts longer than any single chunk, mixed lengths
    queueing behind a 2-slot grid."""
    model, params = _setup()
    prompts = _prompts(4, [3, 9, 13, 6])
    n_new = 6
    refs = [np.asarray(generate(model, params, p[None],
                                n_new))[0, len(p):] for p in prompts]
    for c, budget in ((4, 16), (16, 12)):
        cfg = EngineConfig(n_slots=2, capacity=32, max_new_tokens=n_new,
                           prefill_cohort=2, prefill_chunk=c,
                           token_budget=budget)
        eng = Engine(model, params, cfg)
        reqs = [eng.submit(p) for p in prompts]
        eng.run_until_drained()
        for ref, req in zip(refs, reqs):
            assert req.tokens == ref.tolist(), (c, budget)
            assert req.state == "done"
        # the DL108 invariant in chunked mode: ONE chunk program, ONE
        # decode_k program, regardless of prompt lengths
        assert set(eng.steps.prefill_chunk_traces) == {(2, c)}
        assert all(v == 1
                   for v in eng.steps.prefill_chunk_traces.values())
        assert eng.steps.decode_k_traces == 1


@slow
def test_engine_chunked_eos_retirement():
    model, params = _setup()
    n_new = 8
    p, ref, i = _stream_with_fresh_id(model, params, plen=9, n_new=n_new)
    eos = int(ref[i])
    cfg = EngineConfig(n_slots=1, capacity=32, max_new_tokens=n_new,
                       prefill_cohort=1, prefill_chunk=4, token_budget=8)
    eng = Engine(model, params, cfg)
    req = eng.submit(p, eos_id=eos)
    eng.run_until_drained()
    assert req.tokens == list(ref[:i + 1])      # ends WITH the eos token
    assert req.state == "done"


# --------------------------------------------------------------------
# sampled-decode determinism under a fixed seed
# --------------------------------------------------------------------

def _run_sampled(model, params, prompts, seeds, cfg, n_new=7, temp=0.8,
                 top_k=5):
    eng = Engine(model, params, cfg)
    reqs = [eng.submit(p, temperature=temp, top_k=top_k, seed=s)
            for p, s in zip(prompts, seeds)]
    eng.run_until_drained()
    assert all(r.state == "done" for r in reqs)
    return [r.tokens for r in reqs]


@slow
def test_sampled_decode_deterministic_across_scheduler_shapes():
    """Same per-request seed → same sampled stream, no matter how the
    scheduler carves the work: decode_k 1 vs 4, monolithic vs chunked
    prefill (two chunk sizes), budgeted vs not. One key split per
    sampled token makes the stream a function of (seed, #tokens) only."""
    model, params = _setup()
    prompts = _prompts(6, [4, 9, 6])
    seeds = [11, 22, 33]
    n_new = 7
    base = dict(n_slots=2, capacity=32, max_new_tokens=n_new,
                prefill_cohort=2)
    shapes = [
        EngineConfig(**base, decode_k=1, buckets=[4, 16, 32]),
        EngineConfig(**base, decode_k=4, prefill_chunk=4,
                     token_budget=16),
        EngineConfig(**base, decode_k=2, prefill_chunk=5,
                     token_budget=None),
    ]
    ref = _run_sampled(model, params, prompts, seeds, shapes[0],
                       n_new=n_new)
    assert any(len(set(t)) > 1 for t in ref)    # actually sampling
    for cfg in shapes[1:]:
        got = _run_sampled(model, params, prompts, seeds, cfg,
                           n_new=n_new)
        assert got == ref, (cfg.decode_k, cfg.prefill_chunk,
                            cfg.token_budget)


@slow
def test_sampled_stream_independent_of_neighbours():
    """A request's sampled stream is identical whether it runs alone or
    sharing the grid — neighbouring slots never consume its key splits."""
    model, params = _setup()
    prompts = _prompts(7, [4, 4, 4])
    cfg = EngineConfig(n_slots=2, capacity=32, max_new_tokens=6,
                       prefill_cohort=1, buckets=[4, 32], decode_k=3)
    solo = _run_sampled(model, params, prompts[:1], [99], cfg, n_new=6)
    crowd = _run_sampled(model, params, prompts, [99, 5, 6], cfg, n_new=6)
    assert crowd[0] == solo[0]


@slow
def test_different_seeds_give_different_streams():
    model, params = _setup()
    prompts = _prompts(8, [6, 6])
    cfg = EngineConfig(n_slots=2, capacity=32, max_new_tokens=8,
                       prefill_cohort=2, buckets=[8, 32])
    a, b = _run_sampled(model, params, prompts, [1, 2], cfg, n_new=8,
                        temp=1.5, top_k=0)
    assert a != b


@slow
def test_greedy_engine_ignores_seed():
    """temperature None → the stream is the argmax stream, whatever the
    seed (the greedy path never reads the PRNG). generate() is the
    seed-independent reference, so one non-default seed suffices."""
    model, params = _setup()
    p = _prompts(9, [5])[0]
    cfg = EngineConfig(n_slots=1, capacity=32, max_new_tokens=5,
                       prefill_cohort=1, buckets=[8, 32])
    ref = np.asarray(generate(model, params, p[None], 5))[0, 5:]
    eng = Engine(model, params, cfg)
    req = eng.submit(p, seed=123)
    eng.run_until_drained()
    assert req.tokens == ref.tolist()


@slow
def test_host_bytes_per_token_is_4():
    """The report's observable for DL110: with on-device sampling the
    emit path moves exactly one int32 per token — padding rows included
    still lands ≤ 8 bytes/token."""
    model, params = _setup()
    prompts = _prompts(10, [4, 4])
    cfg = EngineConfig(n_slots=2, capacity=32, max_new_tokens=6,
                       prefill_cohort=2, buckets=[4, 32], decode_k=2)
    eng = Engine(model, params, cfg)
    for p in prompts:
        eng.submit(p)
    eng.run_until_drained()
    s = eng.report.summary()
    assert s["tokens_emitted"] == 12
    assert s["host_bytes_per_token"] <= 8.0
    assert "itl_ms" in s


# --------------------------------------------------------------------
# top-k by selection == top-k by sort, bitwise (tier-1)
# --------------------------------------------------------------------

def _sort_form_mask(scaled, top_k):
    """The kept set as the sort form defines it: sort the whole row
    descending, gather its k-th entry, keep everything at or above."""
    v = scaled.shape[-1]
    kth_idx = jnp.clip(top_k - 1, 0, v - 1)
    srt = -jnp.sort(-scaled, axis=-1)
    kth = jnp.take_along_axis(srt, kth_idx[:, None], axis=-1)
    return scaled >= kth


def sort_form_sample_tokens(logits, keys, temperature, top_k):
    """THE ORACLE: ``sample_tokens`` as it stood while it sorted the
    vocabulary (serving/sampling.py until PR 30), kept line for line."""
    logits = logits.astype(jnp.float32)
    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    new_keys, sub = sampling.split_keys(keys)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    truncated = jnp.where(_sort_form_mask(scaled, top_k), scaled, -jnp.inf)
    scaled = jnp.where((top_k > 0)[:, None], truncated, scaled)
    sampled = jax.vmap(jax.random.categorical)(sub, scaled).astype(jnp.int32)

    tokens = jnp.where(temperature > 0, sampled, greedy)
    return tokens, new_keys


def _case(kind, v, n, seed):
    """(logits, keys, temperature, top_k) of one case. Greedy and
    sampling rows alternate (row 0 samples), per-row k walks the menu,
    every row has its own key."""
    rng = np.random.RandomState(seed)
    rows = np.arange(n)
    temperature = np.where(rows % 2 == 0, 0.8, 0.0).astype(np.float32)
    menu = np.array([50, -1, 0, 1, 2, v - 1, v, v + 5], np.int32)
    top_k = menu[(rows // 2 + rows + seed) % 8]
    # quantised to 1/4: about 50 distinct values over the row, so the
    # k-th value is shared by many entries
    logits = (np.round(rng.randn(n, v) * 3.0 * 4) / 4).astype(np.float32)
    if kind == "zero_kth":
        # the k-th largest value IS zero, and both zeros are in the row:
        # fewer than k entries above zero, then +0.0 and -0.0 in turn
        logits = -np.abs(logits) - 1.0
        for r in rows:
            k = int(np.clip(top_k[r], 2, v - 1))
            top_k[r] = k
            pos = rng.permutation(v)
            above, zeros = pos[:k // 2], pos[k // 2:k + 3]
            logits[r, above] = 2.0 + (rng.randint(0, 3, above.size) / 4)
            logits[r, zeros[0::2]] = 0.0
            logits[r, zeros[1::2]] = -0.0
    elif kind == "neg_inf_and_twins":
        # most of each row is -inf (a large k reaches into it), and the
        # rows come in identical pairs that differ in their keys only
        logits[rng.rand(n, v) < 0.6] = -np.inf
        temperature = np.full(n, 0.8, np.float32)
        temperature[3::4] = 0.0
        logits[1::2] = logits[:n - n % 2:2]
        top_k[1::2] = top_k[:n - n % 2:2]
    else:
        assert kind == "ties"
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n) + 1000 * seed + 1)
    return jnp.asarray(logits), keys, temperature, top_k


@pytest.mark.parametrize("n", [1, 2, 64])
@pytest.mark.parametrize("v", [97, 1000, 4099])
@pytest.mark.parametrize("kind", ["ties", "zero_kth", "neg_inf_and_twins"])
def test_selection_equals_the_sort_form_bitwise(kind, v, n):
    """27 cases: tokens AND keys of ``sample_tokens`` are the sort
    form's, and so is the kept set itself (a draw can agree by luck, a
    set cannot), over odd vocabularies, one row to a grid of 64, every k
    of the menu from -1 to past the vocabulary, ties at the k-th value,
    a zero of either sign as the k-th value, rows of mostly -inf."""
    logits, keys, temperature, top_k = _case(kind, v, n, seed=v + n)
    want = jax.jit(sort_form_sample_tokens)(logits, keys, temperature,
                                            top_k)
    got = jax.jit(sample_tokens)(logits, keys, temperature, top_k)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))

    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    keep = np.asarray(jax.jit(sampling._top_k_mask)(scaled, top_k))
    np.testing.assert_array_equal(
        keep, np.asarray(jax.jit(_sort_form_mask)(scaled, top_k)))
    # the cases hold what they claim to: ties beyond k at the k-th value,
    # and both zeros kept where zero is that value
    k_eff = np.clip(top_k, 1, v)
    if kind == "ties" and n == 64:
        assert (keep.sum(axis=1) > k_eff).any()
    if kind == "zero_kth":
        lg = np.asarray(logits)
        for r in range(n):
            zeros = lg[r] == 0
            assert keep[r][zeros].all() and np.signbit(lg[r][zeros]).any()
            assert not np.signbit(lg[r][zeros]).all()
    if kind == "neg_inf_and_twins" and n > 1:
        assert (np.asarray(got[1])[0] != np.asarray(got[1])[1]).any()


def _sampled_engine_run(model, params):
    cfg = EngineConfig(n_slots=4, capacity=32, max_new_tokens=9,
                       prefill_cohort=2, buckets=[8, 32], decode_k=4)
    eng = Engine(model, params, cfg)
    reqs = []
    for i, p in enumerate(_prompts(12, [4, 7, 5, 6, 3, 8])):
        # greedy and sampled interleaved; k from 1 to past the vocabulary
        kw = {} if i % 3 == 2 else dict(
            temperature=0.9, top_k=[1, 5, 43, 50, 2, 0][i], seed=70 + i)
        reqs.append(eng.submit(p, **kw))
    eng.run_until_drained()
    assert all(r.state == "done" for r in reqs)
    assert eng.steps.decode_k_traces == 1
    return [r.tokens for r in reqs], np.asarray(eng._keys)


def test_engine_streams_and_final_keys_equal_the_sort_forms(monkeypatch):
    """One engine run at ``decode_k`` 4 with the selection, one with the
    sort form patched into every compiled program: equal streams, equal
    final per-slot keys."""
    model, params = _setup()
    got_streams, got_keys = _sampled_engine_run(model, params)
    monkeypatch.setattr(kv_cache, "sample_tokens", sort_form_sample_tokens)
    want_streams, want_keys = _sampled_engine_run(model, params)
    assert any(len(set(t)) > 1 for t in want_streams)
    assert got_streams == want_streams
    np.testing.assert_array_equal(got_keys, want_keys)


_SORT = re.compile(
    r'"?stablehlo\.sort"?.*?dimension = (\d+).*?\(tensor<([0-9x]+)x\w+>',
    re.S)


def _sorted_extents(text):
    """The length of the sorted dimension of every sort in a lowered
    program's text."""
    return [int(shape.split("x")[int(dim)])
            for dim, shape in _SORT.findall(text)]


def _decode_k_text(model, params, n=2, k=4):
    """Lowered text of the ``decode_k`` program (never run)."""
    spec = jax.ShapeDtypeStruct
    cache = init_cache(model, n, 32, jnp.float32)
    return jax.jit(functools.partial(decode_k_apply, model, k=k)).lower(
        params, cache, spec((n,), jnp.int32), spec((n, 2), jnp.uint32),
        spec((n,), jnp.float32), spec((n,), jnp.int32),
        spec((n,), jnp.int32), spec((n,), jnp.int32), spec((n,), bool),
        spec((n,), jnp.int32)).as_text()


def test_decode_k_program_sorts_nothing_along_the_vocabulary(monkeypatch):
    """A later edit cannot bring the full sort back unnoticed: no sort
    in the lowered ``decode_k`` program has the vocabulary (43, like no
    other extent of this model) as its sorted dimension. The control
    lowers the same program with the sort form patched in and finds it."""
    model, params = _setup()
    assert 43 not in _sorted_extents(_decode_k_text(model, params))
    monkeypatch.setattr(kv_cache, "sample_tokens", sort_form_sample_tokens)
    assert 43 in _sorted_extents(_decode_k_text(model, params))
