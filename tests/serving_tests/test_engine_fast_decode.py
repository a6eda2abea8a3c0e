"""An ``Engine`` over a model that does NOT ask for the reference kernel:
prefill by the flash kernel, decode by the grouped contraction over bf16
pages (models/transformer.py ``_grouped_cache_attention``). Such a model
is not held to bitwise parity with the full forward (docs/serving.md
§Numerics contract) but to the benchmark's ``served_logit_gap`` at toy
size: every greedy token the engine served must be the float32 reference's
best token at its position, or within a stated gap of it."""
import jax
import jax.numpy as jnp
import numpy as np

from chainermn_tpu.models.transformer import TransformerLM
from chainermn_tpu.serving.engine import Engine, EngineConfig

# read on the CPU at this size over 4 seeds of 7 greedy requests each: the
# widest gap is 0.0096 here and 0.0056 with attention="reference" and the
# same bf16 blocks and pages (near-ties that bf16 activations flip); a
# served token replaced by another reads 3.2. The limit is 3 x the former.
GAP_LIMIT = 0.03


def _served_gap(ref, params, prompt, tokens):
    """Widest gap by which a served token's float32 logit lies below the
    float32 forward's best at its position (first token through prefill,
    the rest through the cache)."""
    full = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    logits = np.asarray(ref.apply({"params": params}, full[None]))[0]
    rows = logits[prompt.size - 1:prompt.size - 1 + len(tokens)]
    return float(np.max(rows.max(-1) - rows[np.arange(len(tokens)), tokens]))


def test_fast_decode_engine_serves_within_the_reference_gap():
    model = TransformerLM(vocab=43, d_model=32, n_heads=4, n_kv_heads=2,
                          n_layers=2, d_ff=48, max_len=64,
                          attention="flash", pos_emb="rope",
                          dtype=jnp.bfloat16)
    ref = model.clone(attention="reference", dtype=jnp.float32)
    params = ref.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 4), jnp.int32))["params"]
    eng = Engine(model, params, EngineConfig(
        n_slots=3, capacity=32, buckets=[8, 16, 32], decode_k=4,
        prefill_cohort=2, cache_dtype=jnp.bfloat16))
    for page in eng.steps.cache.values():
        assert page["k"].dtype == jnp.bfloat16

    rng = np.random.RandomState(5)
    lens = [3, 7, 12, 5, 9, 16, 4]
    prompts = [rng.randint(0, 43, (n,)).astype(np.int32) for n in lens]
    # every 2nd request greedy, the others sampled: one program serves both
    reqs = [eng.submit(p, max_new_tokens=6 + i,
                       **({} if i % 2 == 0 else
                          dict(temperature=0.8, top_k=10, seed=100 + i)))
            for i, p in enumerate(prompts)]
    eng.run_until_drained()

    assert eng.steps.decode_k_traces == 1
    for i, req in enumerate(reqs):
        assert req.state == "done"
        assert len(req.tokens) == 6 + i
    greedy = [(p, r) for i, (p, r) in enumerate(zip(prompts, reqs))
              if i % 2 == 0]
    gaps = [_served_gap(ref, params, p, r.tokens) for p, r in greedy]
    assert max(gaps) <= GAP_LIMIT, gaps
    # the limit can tell a wrong token from a near-tie
    p, r = greedy[-1]
    wrong = list(r.tokens)
    wrong[2] = (wrong[2] + 17) % 43
    assert _served_gap(ref, params, p, wrong) > 2 * GAP_LIMIT
