"""The routers' choice bias at rest: one rule for every reference module.

A published configuration of this family says that its router carries an
expert bias (``moe_router_enable_expert_bias``, ``noaux_tc``): during training
every expert chosen more often than the mean has its bias lowered and every
one chosen less often raised, so a trained model ARRIVES with a bias under
which its experts are about evenly loaded. Seeded routers have no such bias —
a share of every layer's normed input is one vector common to all tokens, so
a seed's router prefers the same few experts whatever the token — and a cell
served on them reads a load no deployment has, and one that moves with the
seed. So the benchmark computes the bias that rule comes to rest at, here,
with the REFERENCE's own router, and hands the same float32 arrays to the
program and to the reference.

The reference module is an argument. What is asked of it:

* ``route(y [T, d], p, cfg)`` — a tuple whose first entry is the chosen expert
  ids ``[T, top_k]`` under ``p["router_bias"]``, with whatever limits the
  model's choice has (groups kept, experts absent);
* ``ffn_input(x, p, kind, cfg)`` — what layer ``p``'s router is given;
* ``block(x, p, kind, cfg)`` and ``canonical_layer(tree, upcast_experts=)``.

It imports nothing of the program. ``xing_mhc``, ``deepseek_mtp`` and
``laguna_mixed`` carry older copies of ``balance_bias`` written against their
own routers (PERF.md section 7).
"""
import numpy as np

PROBE_KEY = 0xBA1A7CE


def probe_tokens(seed_word, vocab, n_tokens, n_sequences):
    """The seeded probe ``[n_sequences, n_tokens // n_sequences]``. MANY
    sequences, as a decode step holds: one sequence's positions share a
    direction of their own, and a bias run to rest on ONE sequence undoes
    that sequence's preference for every other one."""
    return np.random.RandomState(seed_word ^ PROBE_KEY).randint(
        0, vocab, (n_sequences, n_tokens // n_sequences), np.int32)


def balance_bias(ref, y, p, cfg, steps=400, rate=0.02):
    """The choice bias that the balancing rule comes to rest at on the tokens
    ``y [T, d]``: from ``p["router_bias"]``, each step every expert that
    ``ref.route`` chose more often than the mean moves down and every one
    chosen less often up, by ``rate`` falling linearly to 0 (the scores lie
    in (0, 1))."""
    import jax
    import jax.numpy as jnp

    e = p["router"].shape[-1]

    def one(bias, i):
        chosen = ref.route(y, dict(p, router_bias=bias), cfg)[0]
        load = (chosen[..., None] == jnp.arange(e)).sum((0, 1))
        return bias + rate * (1.0 - i / steps) * jnp.sign(
            load.mean() - load), None

    return jax.lax.scan(one, p["router_bias"],
                        jnp.arange(steps, dtype=jnp.float32))[0]


def balanced_biases(ref, cfg, pattern, maker, seed_word, x):
    """{expert layer: its router bias [E] float32}: the rule run to rest
    layer after layer on the embedded probe ``x [B, L, d]``, each layer on
    what the balanced layers before it pass on. ``maker(kind, layer)`` gives
    ``(seed, i) -> block_i's leaves`` for any layer ``i`` of ``layer``'s kind
    (``i`` traced: one compiled program a kind); ``cfg`` is the reference's."""
    import jax
    import jax.numpy as jnp

    fns, out = {}, {}

    def layer_fn(kind, layer):
        make = maker(kind, layer)

        @jax.jit
        def f(seed, i, x):
            p = ref.canonical_layer(make(seed, i), upcast_experts=False)
            bias = jnp.zeros((0,), jnp.float32)
            if kind[1] == "moe":
                y = ref.ffn_input(x, p, kind, cfg)
                bias = balance_bias(ref, y.reshape(-1, y.shape[-1]), p, cfg)
                p = dict(p, router_bias=bias)
            return ref.block(x, p, kind, cfg), bias

        return f

    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(tuple(k) for k in pattern):
            if kind not in fns:
                fns[kind] = layer_fn(kind, i)
            x, bias = fns[kind](seed_word, jnp.int32(i), x)
            if bias.size:
                out[i] = bias
    return out
