"""The decode step's KDA recurrence as a kernel (ops/kda_state.py) in the
Pallas interpreter at small lane-aligned widths: equal to ``kda_step``
(models/hybrid.py) on the rows that are live, whichever they are; a dead
row's state byte for byte what it was and its ``o`` zero; chained under a
scan with the state donated; and the dispatcher's rule
(``models/hybrid.py::kda_decode_step``), reason by reason, with the path it
notes. tests/ops_tests/test_grouped_swiglu_compile.py compiles the kernel
for the chip at the benchmark's widths."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.models import hybrid
from chainermn_tpu.ops import kda_state as ks
from chainermn_tpu.ops.latent_attention import record_paths
from chainermn_tpu.ops.page_write import partitioned_pages

B, H, DK, DV = 5, 2, 128, 128
TOL = dict(rtol=2e-5, atol=2e-5)    # the interpreter sums in another order


def draw(seed=0, b=B, h=H, dk=DK, dv=DV, state_dtype=jnp.float32):
    rs = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    return dict(q=unit(f(b, h, dk)) * dk ** -0.5, k=unit(f(b, h, dk)),
                v=f(b, h, dv), g=-jax.nn.sigmoid(2 * f(b, h, dk)),
                beta=jax.nn.sigmoid(f(b, h)),
                state=f(b, h, dk, dv).astype(state_dtype))


def masked(a, live):
    """The arguments as ``KDAMixer`` hands them over: ``g = 0, beta = 0`` on
    a row that takes no token."""
    live = jnp.asarray(live, bool)
    return dict(a, g=jnp.where(live[:, None, None], a["g"], 0.0),
                beta=jnp.where(live[:, None], a["beta"], 0.0)), live


LIVE = {"none": [0, 0, 0, 0, 0], "some": [0, 1, 1, 0, 1],
        "the-last": [0, 0, 0, 0, 1], "all": [1, 1, 1, 1, 1]}


@pytest.mark.parametrize("heads", [None, 8], ids=["all-heads", "8-heads"])
@pytest.mark.parametrize("case", list(LIVE))
def test_the_kernel_is_kda_step_on_live_rows_and_leaves_the_others(case,
                                                                   heads):
    a, live = masked(draw(h=16 if heads else H), LIVE[case])
    before = np.asarray(a["state"]).view(np.uint8).copy()
    want_o, want_s = hybrid.kda_step(**a)
    o, s = ks.kda_step_fwd(**a, live=live, heads=heads)
    o, s, on = np.asarray(o), np.asarray(s), np.asarray(live)
    np.testing.assert_allclose(o[on], np.asarray(want_o)[on], **TOL)
    np.testing.assert_allclose(s[on], np.asarray(want_s)[on], **TOL)
    np.testing.assert_array_equal(s[~on].view(np.uint8), before[~on])
    assert not o[~on].any()
    if on.any():
        assert not np.array_equal(s[on].view(np.uint8), before[on])


def test_heads_a_grid_step_tile_the_heads():
    assert ks.head_block(32, 128, 128) == 32          # the cell's: 2 MB
    assert ks.head_block(64, 256, 256) == 32          # 16 MB a row: halves
    assert ks.head_block(6, 1024, 1024) == 6          # no divisor in eights
    a, live = masked(draw(h=16), LIVE["all"])
    with pytest.raises(ValueError, match="12 heads a grid step"):
        ks.kda_step_fwd(**a, live=live, heads=12)
    with pytest.raises(ValueError, match="4 heads a grid step"):
        ks.kda_step_fwd(**a, live=live, heads=4)


def chained(step, a, lives):
    """16 tokens through ``step`` under one scan, the state carried."""
    def body(state, x):
        i, live = x
        r = lambda t: jnp.roll(t, i, axis=0)         # other vectors a token
        b, _ = masked(dict(a, q=r(a["q"]), k=r(a["k"]), v=r(a["v"])), live)
        o, state = step(b["q"], b["k"], b["v"], b["g"], b["beta"], state,
                        live)
        return state, jnp.where(live[:, None, None], o, 0.0)

    n = lives.shape[0]
    return jax.lax.scan(body, a["state"], (jnp.arange(n), lives))


def test_sixteen_chained_steps_under_a_scan_with_the_state_donated():
    """As ``decode_k`` runs it: rows come and go between the steps, the
    state rides the scan's carry and is donated to the program."""
    rs = np.random.RandomState(3)
    lives = jnp.asarray(rs.rand(16, B) < 0.5)
    lives = lives.at[5].set(False).at[9].set(True)
    a = draw(seed=4)
    want_s, want_o = chained(
        lambda *x: hybrid.kda_step(*x[:-1]), a, lives)
    run = jax.jit(lambda state: chained(
        lambda *x: ks.kda_step_fwd(*x), dict(a, state=state), lives),
        donate_argnums=(0,))
    got_s, got_o = run(jnp.array(a["state"]))         # a copy to give away
    np.testing.assert_allclose(got_s, want_s, **TOL)
    np.testing.assert_allclose(got_o, want_o, **TOL)
    never = ~np.asarray(lives).any(0)
    np.testing.assert_array_equal(np.asarray(got_s)[never],
                                  np.asarray(a["state"])[never])


def test_the_dispatcher_takes_the_kernel_where_it_can_and_says_so(
        monkeypatch):
    """Through ``kda_decode_step`` under ``jit``, on a "TPU" whose kernels
    the interpreter runs: the path is noted once, at trace time."""
    monkeypatch.setattr(ks, "on_tpu", lambda: True)     # take the kernel...
    a, live = masked(draw(seed=5), LIVE["some"])
    assert ks.step_kernel_refusal(a["q"], a["v"], a["state"]) is None
    want_o, want_s = hybrid.kda_step(**a)
    step = jax.jit(hybrid.kda_decode_step)
    # the interpreter's loads and stores are host calls into ``jax.numpy``:
    # each program is waited for before this thread dispatches anything else
    with pltpu.force_tpu_interpret_mode():              # ...interpreted
        with record_paths(ks.PATHS) as paths, record_paths() as attention:
            o, s = jax.block_until_ready(step(*a.values(), live))
            jax.block_until_ready(step(*a.values(), live))  # no second trace
    assert paths == ["kernel"] and attention == []
    on = np.asarray(live)
    np.testing.assert_allclose(np.asarray(s), want_s, **TOL)
    np.testing.assert_allclose(np.asarray(o)[on], np.asarray(want_o)[on],
                               **TOL)
    assert not np.asarray(o)[~on].any()


REFUSALS = {
    "a-bfloat16-state": (dict(state_dtype=jnp.bfloat16),
                         "state bfloat16, not float32"),
    "d_k-of-64": (dict(dk=64), "d_k 64, d_v 128 are not both multiples"),
    "d_v-of-192": (dict(dv=192), "d_k 128, d_v 192 are not both multiples"),
    "several-devices": (dict(), "state split over several devices"),
    "off-the-chip": (dict(), "not on a TPU"),
}


@pytest.mark.parametrize("case", list(REFUSALS))
def test_the_dispatcher_keeps_kda_step_and_names_the_reason(case,
                                                            monkeypatch):
    over, reason = REFUSALS[case]
    if case != "off-the-chip":
        monkeypatch.setattr(ks, "on_tpu", lambda: True)
    a, live = masked(draw(b=2, **over), [1, 0])
    with partitioned_pages(case == "several-devices"):
        refusal = ks.step_kernel_refusal(a["q"], a["v"], a["state"])
        assert reason in refusal
        with record_paths(ks.PATHS) as paths:
            o, s = hybrid.kda_decode_step(**a, live=live)
    assert paths == [f"xla:{refusal}"]
    want_o, want_s = hybrid.kda_step(**a)       # the same call, every row
    np.testing.assert_array_equal(np.asarray(o, np.float32),
                                  np.asarray(want_o, np.float32))
    np.testing.assert_array_equal(np.asarray(s, np.float32),
                                  np.asarray(want_s, np.float32))


def test_paths_are_noted_only_inside_a_scope_of_their_kind():
    a, live = masked(draw(b=2), [1, 1])
    call = lambda: hybrid.kda_decode_step(**a, live=live)
    call()                                  # no scope: nothing to note into
    with record_paths() as attention:       # the latent attention's kind
        call()
    with record_paths(ks.PATHS) as outer:
        call()
        with record_paths(ks.PATHS) as inner:
            call()
            call()
        call()
    assert attention == []
    assert len(inner) == 2 and len(outer) == 2
    assert set(inner + outer) == {"xla:not on a TPU"}
