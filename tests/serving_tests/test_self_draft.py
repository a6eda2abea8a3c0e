"""Self-drafted decode (``EngineConfig.self_draft``; serving/state_cache.py::
``state_self_draft_k_apply``) at toy size on the CPU: a ``HybridLM`` with a
multi-token-prediction module drafts its own next token inside the decode
program, two positions a slot a round.

The contract: the draft decides how far a round goes, never what is emitted —
streams are bitwise those of the same weights served with ``n_mtp`` 0, greedy
and sampled, at every ``decode_k``, through EOS and budget stops inside a
round and at the page's end. A module WIRED to be right gives two tokens a
round, one wired to be wrong gives one, both the same stream. The pages —
the module's own too — hold exactly the accepted positions, and what the
last dispatch left on the device (main logits, the module's draft logits)
agrees with the plain reference's full forward
(benchmark/references/deepseek_mtp.py)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.models.hybrid import HybridLM
from chainermn_tpu.serving import Engine, EngineConfig
from chainermn_tpu.serving.sampling import acceptance_scan, sample_tokens
from chainermn_tpu.serving.state_cache import StateServingStep

from tests.models_tests.test_mtp_module import (PATTERN, SIZES,
                                                reference_logits, setup)

VOCAB = SIZES["vocab"]
CAP = 64


def plain_of(model, params):
    """The same weights without the module: what ``n_mtp`` 0 serves."""
    return model.clone(n_mtp=0), {k: v for k, v in params.items()
                                  if k != "mtp_0"}


def engine(model, params, *, self_draft, decode_k=4, n_slots=4, cohort=2):
    return Engine(model, params, EngineConfig(
        n_slots=n_slots, capacity=CAP, buckets=(16, CAP), decode_k=decode_k,
        prefill_cohort=cohort, self_draft=self_draft))


def prompts(n, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, VOCAB, (5 + 3 * i,)).astype(np.int32)
            for i in range(n)]


def serve(eng, reqs):
    """reqs: list of submit kwargs. Drains; returns the streams."""
    out = [eng.submit(**kw) for kw in reqs]
    eng.run_until_drained()
    return [list(r.tokens) for r in out]


@pytest.fixture(scope="module")
def toy():
    return setup()


@pytest.fixture(scope="module")
def oracle(toy):
    """Streams of the six requests below through the PLAIN engine (``n_mtp``
    0), greedy and sampled: what every self-drafted run must reproduce."""
    model, params = plain_of(*toy)
    out = {}
    for temp in (None, 1.0):
        out[temp] = serve(engine(model, params, self_draft=False),
                          requests(temp))
    return out


def requests(temp, **kw):
    return [dict(prompt=p, max_new_tokens=9 + 2 * i, temperature=temp,
                 seed=11 + i, **kw) for i, p in enumerate(prompts(6))]


# -- streams -----------------------------------------------------------------

@pytest.mark.parametrize("decode_k", [1, 4, 8])
@pytest.mark.parametrize("temp", [None, 1.0], ids=["greedy", "sampled"])
def test_streams_are_bitwise_those_of_n_mtp_0(toy, oracle, temp, decode_k):
    eng = engine(*toy, self_draft=True, decode_k=decode_k)
    assert serve(eng, requests(temp)) == oracle[temp]
    assert eng.steps.decode_k_traces == 1       # one decode program
    assert eng.steps.decode_traces == 0
    s = eng.report.summary()
    assert s["draft_tokens_proposed"] > 0
    assert 1.0 <= s["tokens_per_dispatch"] <= 2.0
    assert s["tokens_per_dispatch"] == pytest.approx(
        1.0 + s["acceptance_rate"], abs=0.2)    # budgets cut a few rounds
    if temp:
        # target and draft share the key row: logits that know nothing of
        # each other still agree on the Gumbel noise
        assert s["acceptance_rate"] > 0.15


def test_the_model_with_a_module_serves_plainly_when_not_told_to_draft(
        toy, oracle):
    """One decode program, chosen at construction: ``self_draft`` False runs
    the one-token steps whatever the model carries."""
    eng = engine(*toy, self_draft=False)
    assert serve(eng, requests(None)) == oracle[None]
    assert eng.report.summary()["draft_tokens_proposed"] == 0
    assert eng.steps.last_draft_logits is None


@pytest.mark.parametrize("at", [1, 2, 3, 4, 5, 6])
def test_eos_inside_a_round_stops_the_row_where_plain_decode_stops(
        toy, oracle, at):
    """Whatever place of a round the EOS falls on (first or second, by
    ``at`` and by what was accepted before it), the stream ends with it."""
    model, params = toy
    temp = 1.0
    want = oracle[temp][3]
    eos = want[at]
    cut = want[:want.index(eos) + 1]
    got = serve(engine(model, params, self_draft=True),
                [dict(requests(temp)[3], eos_id=eos)])
    assert got == [cut]


# -- a module wired by hand ---------------------------------------------------

def wired(params, right=True):
    """Weights under which the module's draft is ALWAYS the target's next
    token (or never): the main model is made a function of the current token
    alone (no attention output, blocks 0 and 1 add nothing, embedding rows of
    unit RMS), and the module that same function of ``Emb(t_{i+1})`` — the
    token half of ``W_eh`` the identity, its block the last main block, its
    last norm the final norm (negated for the module that is never right:
    its best token is then the target's worst)."""
    d = SIZES["d_model"]
    p = jax.tree_util.tree_map(lambda a: a, params)
    emb = p["tok_emb"]["embedding"]
    emb = emb / jnp.sqrt(jnp.mean(emb * emb, -1, keepdims=True))
    p["tok_emb"] = {"embedding": emb}
    zero = lambda a: jnp.zeros_like(a)

    def quiet(blk, all_of_it):
        blk = dict(blk, mla=dict(blk["mla"], o_proj={
            "kernel": zero(blk["mla"]["o_proj"]["kernel"])}))
        if all_of_it and "ffn" in blk:
            blk["ffn"] = dict(blk["ffn"], down={
                "kernel": zero(blk["ffn"]["down"]["kernel"])})
        if all_of_it and "moe" in blk:
            blk["moe"] = dict(blk["moe"], w_down=zero(blk["moe"]["w_down"]))
            blk["shared"] = dict(blk["shared"], down={
                "kernel": zero(blk["shared"]["down"]["kernel"])})
        return blk

    last = len(PATTERN) - 1
    for i in range(len(PATTERN)):
        p[f"block_{i}"] = quiet(p[f"block_{i}"], i < last)
    sign = 1.0 if right else -1.0
    p["mtp_0"] = {
        "norm_h": p["mtp_0"]["norm_h"],
        "norm_e": {"scale": jnp.ones((d,))},
        "eh_proj": {"kernel": jnp.concatenate(
            [jnp.zeros((d, d)), jnp.eye(d)], 0)},
        "block": p[f"block_{last}"],
        "norm_out": {"scale": sign * p["norm_f"]["scale"]}}
    return p


@pytest.mark.parametrize("right", [True, False], ids=["right", "wrong"])
def test_a_wired_module_gives_two_tokens_a_round_or_one_and_one_stream(
        toy, right):
    model, params = toy
    p = wired(params, right)
    reqs = [dict(prompt=q, max_new_tokens=1 + 2 * 6, seed=i)
            for i, q in enumerate(prompts(3, seed=1))]
    want = serve(engine(*plain_of(model, p), self_draft=False), reqs)
    eng = engine(model, p, self_draft=True, decode_k=2)
    assert serve(eng, reqs) == want
    s = eng.report.summary()
    if right:
        # prefill emits token 0, then six whole rounds of two
        assert s["acceptance_rate"] == 1.0
        assert s["tokens_per_dispatch"] == 2.0
        assert eng.report.spec_dispatches == 3 * 6
    else:
        assert s["acceptance_rate"] == 0.0
        assert s["tokens_per_dispatch"] == 1.0
        assert eng.report.spec_dispatches == 3 * 12


def test_the_budget_ends_a_row_on_the_first_of_a_rounds_two_tokens(toy):
    """A module that is always right, and a budget that leaves ONE token at
    the start of a round: the round emits one, not two, and nothing more."""
    model, params = toy
    p = wired(params, True)
    reqs = [dict(prompt=prompts(1, seed=2)[0], max_new_tokens=1 + 2 * 3 + 1)]
    want = serve(engine(*plain_of(model, p), self_draft=False), reqs)
    eng = engine(model, p, self_draft=True, decode_k=8)
    got = serve(eng, reqs)
    assert got == want and len(got[0]) == 8
    # three whole rounds and the cut one, whose first token ended the row:
    # its draft was not verified
    assert eng.report.draft_tokens_proposed == 3
    assert eng.report.spec_dispatches == 4
    assert eng.report.draft_tokens_accepted == 3
    assert eng.report.spec_tokens_emitted == 7


def test_a_row_may_end_two_short_of_the_capacity_and_no_nearer(toy, oracle):
    model, params = toy
    prompt = np.arange(20, dtype=np.int32) % VOCAB
    budget = CAP - 1 - prompt.size          # prompt + max_new + 1 == capacity
    reqs = [dict(prompt=prompt, max_new_tokens=budget, temperature=1.0,
                 seed=5)]
    want = serve(engine(*plain_of(model, params), self_draft=False), reqs)
    eng = engine(model, params, self_draft=True)
    assert serve(eng, reqs) == want and len(want[0]) == budget
    with pytest.raises(ValueError, match=r"\+ 1 exceeds the page capacity"):
        eng.submit(prompt, max_new_tokens=budget + 1)
    # the plain engine takes that request: the margin is the draft's row
    engine(*plain_of(model, params), self_draft=False).submit(
        prompt, max_new_tokens=budget + 1)


# -- the pages and the logits --------------------------------------------------

def mid_stream(toy, temp, iterations=3):
    """An engine stopped mid-stream after rejections and acceptances."""
    model, params = toy
    eng = engine(model, params, self_draft=True, decode_k=3)
    reqs = [eng.submit(prompt=p, max_new_tokens=30, temperature=temp,
                       seed=40 + i) for i, p in enumerate(prompts(3, seed=3))]
    for _ in range(iterations):
        eng.step()  # dlint: disable=DL104
    assert all(r.state == "running" for r in reqs)
    return eng, reqs


def test_the_pages_hold_exactly_the_accepted_positions(toy):
    """After rounds that rejected and rounds that accepted, rows ``[0,
    cursor)`` of every page — the module's own too — are what ONE pass over
    prompt + emitted tokens writes, and the cursor is prompt + emitted - 1."""
    model, params = toy
    eng, reqs = mid_stream(toy, 1.0)
    s = eng.report.summary()
    assert 0 < s["draft_tokens_accepted"] < s["draft_tokens_proposed"]
    cursors = np.asarray(eng.steps.cursors())
    dm = model.clone(decode=True, max_len=CAP)
    for r in reqs:
        fill = r.prompt.size + len(r.tokens) - 1
        assert cursors[r.slot] == fill
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        fresh = jax.tree_util.tree_map(
            lambda a: jnp.zeros((1,) + a.shape[1:], a.dtype), eng.steps.cache)
        (_, hidden), upd = dm.apply(
            {"params": params, "cache": fresh}, seq[None, :fill],
            return_hidden=True, mutable=["cache", "stats"])
        _, upd = dm.apply(
            {"params": params, "cache": upd["cache"]}, seq[None, 1:fill + 1],
            hidden=hidden, pos_offset=jnp.zeros((1,), jnp.int32),
            mutable=["cache", "stats"])
        want = jax.tree_util.tree_leaves_with_path(upd["cache"])
        got = dict(jax.tree_util.tree_leaves_with_path(eng.steps.cache))
        pages = 0
        for path, leaf in want:
            if leaf.ndim == 3:      # a latent page
                pages += 1
                np.testing.assert_allclose(
                    got[path][r.slot, :fill], leaf[0, :fill], atol=2e-5,
                    rtol=2e-5, err_msg=str(path))
        assert pages == len(PATTERN) + 1


@pytest.mark.parametrize("temp", [None, 1.0], ids=["greedy", "sampled"])
def test_served_logits_match_the_reference_main_and_module(toy, temp):
    """Prefill, then self-drafted rounds through the pages: the main logits
    of each live row's last emitted token and the module's logits of its
    last draft against the reference's full teacher-forced forward on
    prompt + emitted tokens. Logits, not tokens."""
    model, params = toy
    eng, reqs = mid_stream(toy, temp)
    main = np.asarray(eng.last_logits)
    draft = np.asarray(eng.steps.last_draft_logits)
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        pad = -(seq.size - 1) % 8       # the reference's query block
        tokens = np.pad(seq[:-1], (0, pad))[None]
        nxt = np.pad(seq[1:], (0, pad))[None]
        want_main, want_mtp = reference_logits(params, tokens, nxt)
        at = seq.size - 2       # the position that produced the last token
        np.testing.assert_allclose(main[r.slot], want_main[0, at],
                                   atol=3e-4, rtol=3e-4)
        np.testing.assert_allclose(draft[r.slot], want_mtp[0, at],
                                   atol=3e-4, rtol=3e-4)
        # the draft the slot holds is the module's sample at that position
        held = int(np.asarray(eng.steps.cache["draft"])[r.slot])
        if temp is None:
            assert held == int(np.argmax(draft[r.slot]))


def test_the_first_draft_comes_from_the_prefill(toy):
    """The module runs over the prompt in the prefill program: its page is
    whole and the slot holds a draft before the first round."""
    model, params = toy
    eng = engine(model, params, self_draft=True)
    r = eng.submit(prompts(1, seed=4)[0], max_new_tokens=20)
    eng._admit(float("inf"))
    assert len(r.tokens) == 1 and eng.steps.decode_k_traces == 0
    seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
    pad = -(seq.size - 1) % 8
    _, want_mtp = reference_logits(
        params, np.pad(seq[:-1], (0, pad))[None],
        np.pad(seq[1:], (0, pad))[None])
    held = int(np.asarray(eng.steps.cache["draft"])[r.slot])
    assert held == int(np.argmax(want_mtp[0, seq.size - 2]))
    page = np.asarray(eng.steps.cache["mtp_0"]["block"]["mla"]["ckv"])
    assert np.abs(page[r.slot, :r.prompt.size]).max() > 0
    assert np.abs(page[r.slot, r.prompt.size:]).max() == 0


# -- the round through the decode kernel ---------------------------------------

def test_a_self_drafted_dispatch_through_the_kernel_is_the_loops():
    """At widths the decode kernel serves (lane tiles, eight heads: sixteen
    query-heads a row, two positions side by side) every latent layer of the
    round — the module's too — takes the kernel, interpreted here; the
    streams, the last main logits and the module's last draft logits are
    those of the same weights through the loop."""
    from tests.models_tests.test_mla_long import kernel_path

    model, params = setup(n_heads=8, d_head=128, d_nope=128, d_rope=64,
                          kv_rank=128)

    def run():
        eng = engine(model, params, self_draft=True, decode_k=3)
        reqs = [eng.submit(**kw) for kw in requests(1.0)[:3] + requests(
            None)[3:5]]
        for _ in range(4):
            eng.step()  # dlint: disable=DL104
        assert any(r.state == "running" for r in reqs)
        return ([list(r.tokens) for r in reqs], np.asarray(eng.last_logits),
                np.asarray(eng.steps.last_draft_logits), eng)

    with kernel_path():
        toks, main, draft, eng = run()
    assert eng.steps.decode_attention == "kernel"
    assert eng.steps.decode_k_traces == 1
    s = eng.report.summary()
    assert 0 < s["draft_tokens_accepted"] < s["draft_tokens_proposed"]
    want_toks, want_main, want_draft, eng = run()
    assert eng.steps.decode_attention == "loop:not on a TPU"
    assert toks == want_toks
    np.testing.assert_allclose(main, want_main, atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(draft, want_draft, atol=3e-4, rtol=3e-4)


# -- refusals, counters, the scan ---------------------------------------------

def test_a_recurrent_leaf_still_refuses_and_names_the_leaf():
    from tests.serving_tests.test_state_cache import setup as ling_setup

    model, params = ling_setup()[:2]
    with pytest.raises(ValueError, match="recurrent state") as err:
        Engine(model.clone(n_mtp=1), params, EngineConfig(
            n_slots=2, capacity=64, buckets=(32, 64), self_draft=True))
    assert "kda" in str(err.value) and "self-drafting" in str(err.value)


def test_self_draft_needs_a_module_blocked_pages_and_bucketed_prefill(toy):
    model, params = toy
    cfg = dict(n_slots=2, capacity=CAP, buckets=(16, CAP))
    with pytest.raises(ValueError, match="n_mtp 1"):
        Engine(*plain_of(model, params),
               EngineConfig(self_draft=True, **cfg))
    with pytest.raises(ValueError, match="mla_block"):
        StateServingStep(model.clone(mla_block=0), params, 2, CAP,
                         self_draft=True)
    with pytest.raises(ValueError, match="bucketed prefill"):
        Engine(model, params, EngineConfig(self_draft=True, prefill_chunk=8,
                                           **cfg))
    with pytest.raises(ValueError, match="chunked prefill is not written"):
        StateServingStep(model, params, 2, CAP,
                         self_draft=True).prefill_chunk()


def test_the_budget_and_the_wrap_guard_reckon_two_tokens_a_round(toy):
    model, params = toy
    eng = engine(model, params, self_draft=True, decode_k=4)
    assert eng._max_decode_advance() == 8
    assert engine(model, params, self_draft=False,
                  decode_k=4)._max_decode_advance() == 4


def test_the_decode_span_carries_the_rounds_counters(toy, profiler_session):
    from chainermn_tpu import tracing

    model, params = toy
    eng = engine(model, params, self_draft=True, decode_k=2)
    for kw in requests(1.0)[:3]:
        eng.submit(**kw)
    tracing.clear()
    with profiler_session():
        eng.run_until_drained()
    rows = [r for r in tracing.rows() if r.name == "engine.decode.enqueue"]
    tracing.clear()
    assert rows
    for name in ("drafts_verified", "drafts_accepted", "tokens_emitted",
                 "rounds", "experts_touched", "pairs_held",
                 "mtp_experts_touched", "mtp_pairs_held", "mtp_pairs_routed"):
        assert all(name in r.attrs for r in rows), name
    assert all(r.attrs["rounds"] == 2 for r in rows)
    verified = sum(r.attrs["drafts_verified"] for r in rows)
    accepted = sum(r.attrs["drafts_accepted"] for r in rows)
    emitted = sum(r.attrs["tokens_emitted"] for r in rows)
    assert 0 < accepted < verified
    # every decode token came out of a round; the first of each request out
    # of its prefill
    assert emitted == eng.report.tokens_emitted - 3
    assert emitted == eng.report.spec_tokens_emitted
    assert accepted == eng.report.draft_tokens_accepted
    # one definition of a verified draft, on the device and in the report:
    # the round's first token left the row alive
    assert verified == eng.report.draft_tokens_proposed
    assert eng.report.spec_dispatches >= verified


def test_the_shared_scan_is_the_one_speculative_verify_uses():
    """``acceptance_scan`` alone: accepted prefix, correction, bonus, keys
    advanced once an emitted token, EOS and budget."""
    import inspect

    from chainermn_tpu.serving import speculative, state_cache

    assert "acceptance_scan(" in inspect.getsource(speculative.verify_apply)
    assert "acceptance_scan(" in inspect.getsource(
        state_cache.state_self_draft_k_apply)
    n, v = 4, 7
    best = jnp.asarray([[1, 2, 3], [1, 2, 3], [1, 2, 3], [1, 2, 3]])
    logits = jnp.moveaxis(10.0 * jax.nn.one_hot(best, v), 1, 0)  # [3, n, v]
    drafts = jnp.asarray([[1, 1, 9, 1], [2, 9, 2, 2]])           # [2, n]
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(n)])
    out, keys2, rem, alive, m = acceptance_scan(
        logits, drafts, keys, jnp.zeros((n,)), jnp.zeros((n,), jnp.int32),
        jnp.asarray([-1, -1, -1, 2]), jnp.asarray([9, 9, 9, 9]),
        jnp.asarray([True, True, True, True]))
    assert out.tolist() == [[1, 2, 3], [1, 2, -1], [1, -1, -1], [1, 2, -1]]
    assert m.tolist() == [3, 2, 1, 2]
    assert rem.tolist() == [6, 7, 8, 7]
    assert alive.tolist() == [True, True, True, False]      # row 3 hit EOS
    for i, steps in enumerate(m.tolist()):
        k = keys[i:i + 1]
        for _ in range(steps):
            _, k = sample_tokens(logits[0, i:i + 1], k, jnp.zeros((1,)),
                                 jnp.zeros((1,), jnp.int32))
        assert np.array_equal(np.asarray(k[0]), np.asarray(keys2[i]))
