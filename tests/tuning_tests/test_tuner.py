"""The schedtune search: deterministic, overlap-driven, honest about
its default. All on the canned scheduled-HLO emulator — no compiler,
no devices, no wall clock.
"""

import dataclasses

import pytest

from chainermn_tpu.analysis import dp_overlap_fraction
from chainermn_tpu.tuning import (
    Candidate,
    canned_compile_fn,
    canned_schedule_hlo,
    ProfileDB,
    default_candidates,
    default_flat_candidate,
    estimate_comm_us,
    score_candidate,
    single_tier,
    tune,
    tune_canned,
    two_tier,
)

#: representative payload: ResNet-50-ish 51 MiB of f32 grads
GRAD_BYTES = 51 << 20


# ---------------------------------------------------------------------------
# the canned emulator: fraction structure the tuner exploits
# ---------------------------------------------------------------------------

def test_canned_more_buckets_overlap_earlier():
    few = dp_overlap_fraction(canned_schedule_hlo(n_buckets=2))
    many = dp_overlap_fraction(canned_schedule_hlo(n_buckets=13))
    assert many > few > 0.0


def test_canned_single_bucket_cannot_overlap():
    # one giant all-reduce only issues after the full gradient exists
    assert dp_overlap_fraction(canned_schedule_hlo(n_buckets=1)) == 0.0


def test_canned_size_order_front_loads_the_first_launch():
    em = dp_overlap_fraction(
        canned_schedule_hlo(n_buckets=13, bucket_order="emission"))
    sz = dp_overlap_fraction(
        canned_schedule_hlo(n_buckets=13, bucket_order="size"))
    assert sz > em


def test_canned_double_buffering_hides_everything():
    hlo = canned_schedule_hlo(n_buckets=13, double_buffering=True)
    assert dp_overlap_fraction(hlo) == 1.0


# ---------------------------------------------------------------------------
# scoring
# ---------------------------------------------------------------------------

def test_score_prefers_higher_overlap_at_equal_comm():
    topo = single_tier(8)
    cand = Candidate("flat", 4 << 20, "emission")
    lo = score_candidate(topo, cand, canned_schedule_hlo(13, "emission"),
                         GRAD_BYTES)
    hi = score_candidate(topo, cand, canned_schedule_hlo(13, "size"),
                         GRAD_BYTES)
    assert hi["overlap_fraction"] > lo["overlap_fraction"]
    assert hi["score"] < lo["score"]


def test_measured_table_overrides_the_model():
    topo = single_tier(8)
    cand = Candidate("flat", GRAD_BYTES)  # one bucket
    modeled = estimate_comm_us(topo, cand, GRAD_BYTES)
    overridden = estimate_comm_us(
        topo, cand, GRAD_BYTES,
        measured={("flat", GRAD_BYTES): 123.0})
    assert overridden == 123.0
    assert overridden != modeled
    # nearest size wins
    near = estimate_comm_us(
        topo, cand, GRAD_BYTES,
        measured={("flat", 1 << 10): 7.0, ("flat", GRAD_BYTES - 1): 9.0})
    assert near == 9.0


def test_auto_candidate_prices_each_bucket_at_its_best():
    topo = two_tier(4, 2)
    auto = estimate_comm_us(topo, Candidate("auto", 4 << 20), GRAD_BYTES)
    flat = estimate_comm_us(topo, Candidate("flat", 4 << 20), GRAD_BYTES)
    hier = estimate_comm_us(topo, Candidate("hierarchical", 4 << 20),
                            GRAD_BYTES)
    assert auto == min(flat, hier)


# ---------------------------------------------------------------------------
# the search
# ---------------------------------------------------------------------------

def test_tuner_beats_the_untuned_default_overlap():
    """THE acceptance bar: on the canned fixtures the winner's DL201
    overlap fraction is strictly higher than untuned flat's."""
    res = tune_canned(single_tier(8), GRAD_BYTES)
    assert res.improves_overlap
    assert res.plan.overlap_fraction > res.default["overlap_fraction"]
    # the default row really is the untuned configuration
    assert res.default["candidate"] == dataclasses.asdict(
        default_flat_candidate())


def test_tuner_is_deterministic():
    a = tune_canned(single_tier(8), GRAD_BYTES)
    b = tune_canned(single_tier(8), GRAD_BYTES)
    assert a.plan == b.plan
    assert a.rows == b.rows


def test_tuner_exploits_an_outer_tier():
    res = tune_canned(two_tier(4, 2), GRAD_BYTES)
    # across a slow DCN tier the winner must stop paying the flat ring
    assert res.plan.strategy in ("hierarchical", "auto", "synth")
    assert res.improves_overlap
    assert res.plan.fingerprint == two_tier(4, 2).fingerprint()
    assert res.plan.buckets  # per-bucket algorithm record is filled


def test_synth_beats_every_fixed_reducer_on_the_canned_fixture():
    """The PR's acceptance bar: on at least one canned fixture the
    winner is a SYNTHESIZED program whose DL201 overlap fraction is
    strictly above the best any fixed strategy achieves (the staged
    scatter pipeline issues its first collective one emission earlier)."""
    res = tune_canned(two_tier(4, 2), GRAD_BYTES)
    assert res.plan.strategy == "synth"
    assert res.plan.program is not None
    assert res.plan.buckets[0][0].startswith("synth:")
    best_fixed = max(r["overlap_fraction"] for r in res.rows
                     if r["candidate"]["strategy"] != "synth")
    assert res.plan.overlap_fraction > best_fixed
    assert res.improves_overlap


def test_candidate_grid_respects_opt_ins():
    flat_only = default_candidates(single_tier(8))
    assert {c.strategy for c in flat_only} == {"flat"}
    assert not any(c.double_buffering for c in flat_only)
    tiered = default_candidates(two_tier(4, 2))
    assert {c.strategy for c in tiered} == {"flat", "hierarchical",
                                            "auto", "synth"}
    assert all(c.program is not None for c in tiered
               if c.strategy == "synth")
    lossy = default_candidates(two_tier(4, 2), lossy=True)
    assert "quantized" in {c.strategy for c in lossy}
    assert any(c.strategy == "synth" and c.wire_format != "f32"
               for c in lossy)
    stale = default_candidates(single_tier(8), allow_stale=True)
    assert any(c.double_buffering for c in stale)


def test_tune_always_scores_the_default_for_comparison():
    only = [Candidate("flat", 1 << 20, "size")]
    res = tune(single_tier(8), GRAD_BYTES, canned_compile_fn(GRAD_BYTES),
               candidates=only)
    cands = [r["candidate"] for r in res.rows]
    assert dataclasses.asdict(default_flat_candidate()) in cands


def test_tune_explicit_pair_prefers_higher_overlap():
    lo = Candidate("flat", GRAD_BYTES)          # 1 bucket, frac 0.0
    hi = Candidate("flat", 1 << 20, "size")     # 51 buckets, near 1.0
    res = tune(single_tier(8), GRAD_BYTES, canned_compile_fn(GRAD_BYTES),
               candidates=[lo, hi])
    assert res.plan.bucket_bytes == 1 << 20
    assert res.plan.bucket_order == "size"


def test_compile_fn_may_skip_candidates():
    def partial(cand):
        if cand.bucket_order == "size":
            return None
        return canned_compile_fn(GRAD_BYTES)(cand)

    res = tune(single_tier(8), GRAD_BYTES, partial)
    assert all(r["candidate"]["bucket_order"] == "emission"
               for r in res.rows)


def test_tune_with_nothing_compiled_raises():
    with pytest.raises(ValueError):
        tune(single_tier(8), GRAD_BYTES, lambda cand: None)


def test_plan_round_trips_through_db_identically(tmp_path):
    res = tune_canned(single_tier(8), GRAD_BYTES)
    p = str(tmp_path / "db.json")
    db = ProfileDB(p)
    db.put_plan(res.plan)
    db.save()
    assert ProfileDB(p).plan_for(single_tier(8)) == res.plan
