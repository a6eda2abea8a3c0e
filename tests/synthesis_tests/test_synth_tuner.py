"""Synthesis meets the tuner: programs enter the candidate grid, persist
through the profile DB as plain dicts, and
``create_multi_node_optimizer(tune=...)`` rebuilds the exact reducer.
(That a program wins the canned fixture with STRICTLY higher DL201
overlap than every fixed reducer is held in tier-1:
``tests/tuning_tests/test_tuner.py``.)
"""

import dataclasses

import numpy as np
import optax
import pytest

import chainermn_tpu
from chainermn_tpu.synthesis import (
    Program,
    SynthesizedReducer,
    check_program,
    enumerate_programs,
)
from chainermn_tpu.tuning import (
    ProfileDB,
    default_candidates,
    tune_canned,
    two_tier,
)
from tests.synthesis_tests.test_sketch import three_tier
from tests.synthesis_tests.test_synth_reducer import _reduce_fn

GRAD_BYTES = 51 << 20


@pytest.fixture(scope="module")
def comm():
    return chainermn_tpu.create_communicator("xla")


# ---------------------------------------------------------------------------
# the search
# ---------------------------------------------------------------------------

def test_lossy_sweep_places_the_narrow_wire_by_tier():
    res = tune_canned(two_tier(4, 2), GRAD_BYTES, lossy=True)
    assert res.plan.strategy == "synth"
    assert res.plan.wire_format != "f32"
    # the recorded format is the program's own wire, not a free knob
    prog = Program.from_dict(res.plan.program)
    assert prog.wire_format == res.plan.wire_format


def test_tuning_with_programs_is_deterministic():
    a = tune_canned(two_tier(4, 2), GRAD_BYTES, lossy=True)
    b = tune_canned(two_tier(4, 2), GRAD_BYTES, lossy=True)
    assert a.plan == b.plan
    assert a.rows == b.rows


@pytest.mark.parametrize("topo", [two_tier(4, 2), three_tier()],
                         ids=["4x2", "2x2x2"])
def test_every_synth_candidate_is_a_valid_program(topo):
    """Property over the whole grid (including the 3-tier topology):
    every program candidate the tuner will ever score passes the
    checker, round-trips through dict form, and prices finitely."""
    cands = [c for c in default_candidates(topo, lossy=True)
             if c.strategy == "synth"]
    assert len(cands) >= len(enumerate_programs(topo, lossy=True))
    res = tune_canned(topo, GRAD_BYTES, lossy=True)
    for c in cands:
        assert check_program(c.program) == []
        assert Program.from_dict(c.program.to_dict()) == c.program
        assert c.wire_format == c.program.wire_format
        row = next(r for r in res.rows
                   if r["candidate"] == dataclasses.asdict(c))
        assert 0.0 <= row["overlap_fraction"] <= 1.0
        assert row["comm_us"] > 0.0


# ---------------------------------------------------------------------------
# DB -> optimizer round trip
# ---------------------------------------------------------------------------

def test_plan_round_trips_db_to_optimizer(comm, tmp_path):
    res = tune_canned(two_tier(4, 2), GRAD_BYTES, model_key="rn50ish")
    path = str(tmp_path / "profiles.json")
    db = ProfileDB(path)
    db.put_plan(res.plan)
    db.save()

    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1), comm, tune=path, model_key="rn50ish",
        topology=two_tier(4, 2))
    red = opt.grad_reducer
    assert isinstance(red, SynthesizedReducer)
    assert red.program.name == res.plan.program["name"]
    assert opt.plan == res.plan

    # and the rebuilt reducer still reduces exactly
    rs = np.random.RandomState(5)
    g = rs.randint(-8, 9, size=(comm.size, 1024)).astype(np.float32)
    got, _ = _reduce_fn(comm, red)(g, ())
    np.testing.assert_array_equal(
        np.asarray(got), np.tile(g.sum(axis=0) / comm.size, (comm.size, 1)))


def test_roundtrip_requires_the_matching_topology(comm, tmp_path):
    res = tune_canned(two_tier(4, 2), GRAD_BYTES)
    path = str(tmp_path / "profiles.json")
    db = ProfileDB(path)
    db.put_plan(res.plan)
    db.save()

    # without topology= the mesh infers a single-tier fingerprint that
    # cannot find (or match) the factored plan
    with pytest.raises(ValueError,
                       match="no tuned schedule|stale schedule"):
        chainermn_tpu.create_multi_node_optimizer(
            optax.sgd(0.1), comm, tune=path)
    # and a topology whose rank count disagrees is refused outright
    with pytest.raises(ValueError, match="ranks"):
        chainermn_tpu.create_multi_node_optimizer(
            optax.sgd(0.1), comm, tune=path, topology=two_tier(4, 4))


# numerics-heavy compile farm: covered nightly via the full run,
# excluded from the tier-1 wall-clock budget
pytestmark = pytest.mark.slow
