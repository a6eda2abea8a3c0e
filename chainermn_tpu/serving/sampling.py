"""On-device token sampling — the piece that lets decode stop shipping
logits across the host boundary.

The host-side loop this replaces (``np.asarray`` of the full
``[n_slots, vocab]`` logits + a Python ``np.argmax``/softmax per slot —
dlint DL110's target) moved the one array that grows with vocabulary
over PCIe once per generated token. Here sampling compiles INTO the
decode program: :func:`sample_tokens` is pure jax, takes the per-slot
PRNG keys/temperatures/top-k the engine threads as state, and returns
int32 token ids — so a ``decode_k`` dispatch transfers ``O(n_slots)``
ids instead of ``O(n_slots × vocab)`` floats (held to ≤ 8 bytes/token by
``tests/serving_tests/test_serving_contracts.py``).

Encoding conventions (the engine's ``None`` → array mapping):

* ``temperature <= 0``  → greedy ``jnp.argmax`` (first-index ties —
  bit-identical to the host ``np.argmax`` path it replaces);
* ``top_k <= 0``        → no truncation (full vocabulary);
* keys are RAW uint32 ``[n, 2]`` PRNG keys (``jax.random.PRNGKey``
  layout) so they scan/scatter as plain arrays.

Determinism contract: one key split per SAMPLED token, per slot —
independent of ``decode_k``, chunk size, or neighbouring traffic — so a
fixed per-request ``seed`` replays the same stream under any scheduler
interleaving (tested in tests/serving_tests/test_sampling.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["request_key", "init_keys", "split_keys", "sample_tokens",
           "draft_shadow_keys", "acceptance_scan"]


def request_key(seed: int):
    """Raw uint32 ``[2]`` key for one request (set into the engine's
    per-slot key matrix at admission)."""
    return jax.random.PRNGKey(seed)


def init_keys(n: int):
    """The engine's resting key state: ``[n, 2]`` zeros (free slots
    sample garbage rows nobody reads — row independence, as everywhere
    in the serving grid)."""
    return jnp.zeros((n, 2), jnp.uint32)


def split_keys(keys):
    """Per-row key split: ``[n, 2]`` → (advanced keys, subkeys)."""
    both = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    return both[:, 0], both[:, 1]


def _ordered_bits(x):
    """uint32 keys whose unsigned order is the float order of ``x`` (f32,
    no NaN): a negative float has every bit flipped, any other its sign
    bit. ``-0.0`` and ``+0.0`` get ONE key, because ``>=`` holds them
    equal."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    flip = jnp.where(b >> 31 == 1, jnp.uint32(0xFFFFFFFF),
                     jnp.uint32(0x80000000))
    return jnp.where(x == 0, jnp.uint32(0x80000000), b ^ flip)


def _kth_largest(keys, k):
    """Per row the ``k``-th largest of ``keys`` (``[n, v]`` uint32; ``k``
    ``[n]`` int32 in ``1..v``), EXACTLY and without sorting: the largest
    ``t`` with ``count(keys >= t) >= k``, found by bisecting its 32 bits
    from the top — each pass is one compare-and-count reduction over the
    row, and keeps the bit it tried iff ``k`` entries still reach it."""
    def one_bit(i, t):
        cand = t | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
        cnt = jnp.sum(keys >= cand[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(cnt >= k, cand, t)

    return jax.lax.fori_loop(0, 32, one_bit,
                             jnp.zeros(keys.shape[:1], jnp.uint32))


def _top_k_mask(scaled, top_k):
    """Where ``scaled`` is at or above its row's ``top_k``-th largest
    value (``top_k`` clipped to ``1..vocab``): ties AT that value all
    count, so a row can keep more than ``top_k`` entries."""
    keys = _ordered_bits(scaled)
    kth = _kth_largest(keys, jnp.clip(top_k, 1, scaled.shape[-1]))
    return keys >= kth[:, None]


@jax.named_scope("sample")   # the one sampling site of every program
def sample_tokens(logits, keys, temperature, top_k):
    """One sampled token per row, entirely on device.

    logits ``[n, vocab]`` f32 (no NaN); keys ``[n, 2]`` uint32;
    temperature ``[n]`` f32 (``<= 0`` → greedy); top_k ``[n]`` int32
    (``<= 0`` → full vocab). Returns ``(tokens [n] int32, new_keys
    [n, 2])``.

    Every row consumes exactly one split — greedy rows too — so the key
    stream position depends only on how many tokens a slot has sampled,
    never on its neighbours' sampling modes. Callers freeze keys for
    rows that didn't really sample (dead/pad rows) with a ``where`` on
    the returned keys.

    Top-k keeps every logit at or above the row's k-th largest value:
    ties AT that value all stay, ``top_k >= vocab`` keeps everything.
    The value is found by selection (:func:`_top_k_mask`: 32 counting
    passes over the row), not by sorting the vocabulary; the kept set,
    and with it the tokens and keys, are bitwise what a full descending
    sort and a gather of its k-th entry give
    (tests/serving_tests/test_sampling.py keeps that form as the oracle).
    """
    logits = logits.astype(jnp.float32)
    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    new_keys, sub = split_keys(keys)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    # top-k truncation with a TRACED k: find the k-th largest value per
    # row by selection on order-preserving integer keys, mask everything
    # strictly below it (same >=-kth tie rule as generate()'s static-k
    # lax.top_k path)
    truncated = jnp.where(_top_k_mask(scaled, top_k), scaled, -jnp.inf)
    scaled = jnp.where((top_k > 0)[:, None], truncated, scaled)
    sampled = jax.vmap(jax.random.categorical)(sub, scaled).astype(jnp.int32)

    tokens = jnp.where(temperature > 0, sampled, greedy)
    return tokens, new_keys


def draft_shadow_keys(keys):
    """SHADOW copy of the target's per-slot keys for a speculative
    draft pass (serving/speculative.py).

    The draft model proposes tokens by sampling with the SAME key
    values, at the same stream positions, that the target will use to
    verify — that alignment is what makes the Gumbel-max categorical
    draws coincide whenever draft and target logits are close, so
    sampled-mode acceptance is nonzero. The shadow is discarded after
    every speculative round: only the verify pass advances the REAL key
    rows, exactly one split per emitted token, which is what keeps
    accepted streams bitwise-identical to non-speculative decode and
    keeps migration/replay contracts intact.

    A draft-sampled token must NEVER be committed from this shadow
    stream without a verify pass blessing it — dlint DL125
    (draft-target-key-confusion) flags exactly that dataflow.
    """
    return jnp.asarray(keys, jnp.uint32).copy()


def acceptance_scan(logits, drafts, keys, temps, top_ks, eos_ids, remaining,
                    live):
    """The acceptance scan every verifier shares (``speculative.py::
    verify_apply`` after a separate draft model, ``state_cache.py``'s
    self-drafting round after the model's own module).

    ``logits [w, n, vocab]``: the TARGET's logits at ``w`` consecutive
    stream positions of every row, position ``j`` computed with the drafts
    ``d_1 .. d_j`` as its inputs; ``drafts [w - 1, n]`` int32: ``d_1 ..
    d_{w-1}``. Position by position the scan samples ``s_j`` from
    ``logits[j]`` with the row's REAL key and emits it while the row is
    alive and every earlier ``s_i`` equalled ``d_{i+1}``: the longest
    accepted prefix plus one more target-sampled token (the CORRECTION at
    the first mismatch, the BONUS ``s_{w-1}`` after a full accept). A key
    row advances ONLY when its row emits — one split a sampled token, as in
    ``decode_k_apply`` — and EOS and the budget (``remaining``) stop a row
    exactly as there. So what is emitted is what plain decode emits; the
    drafts decide how far a round goes, never what it yields.

    Returns ``(emitted [n, w] int32, -1 past each row's stop; keys;
    remaining; alive — the rows that may emit again; m [n] — tokens
    emitted)``."""
    w, n = logits.shape[:2]
    nxt = jnp.concatenate(
        [jnp.asarray(drafts, jnp.int32),
         jnp.full((1, n), -1, jnp.int32)], axis=0)          # [w, n]: d_{j+1}
    is_bonus = jnp.arange(w) == w - 1

    def body(carry, xs):
        keys, rem, alive, accepting, m = carry
        lj, dj, bonus = xs
        s, keys2 = sample_tokens(lj, keys, temps, top_ks)
        emit = accepting & alive
        # only emitting rows consume a split — the key stream position
        # stays a pure function of tokens sampled, as everywhere else
        keys = jnp.where(emit[:, None], keys2, keys)
        rem = rem - emit.astype(jnp.int32)
        hit_eos = (s == eos_ids) & (eos_ids >= 0)
        alive = alive & ~(emit & (hit_eos | (rem <= 0)))
        accepting = accepting & alive & ~bonus & (s == dj)
        out = jnp.where(emit, s, jnp.int32(-1))
        return (keys, rem, alive, accepting,
                m + emit.astype(jnp.int32)), out

    init = (keys, remaining, live, live, jnp.zeros((n,), jnp.int32))
    (keys, rem, alive, _, m), outs = jax.lax.scan(
        body, init, (logits, nxt, is_bonus))
    return outs.T, keys, rem, alive, m
