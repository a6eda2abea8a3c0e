"""ServingReport: deterministic telemetry against a fake clock."""

import json
import math

import pytest

from chainermn_tpu.serving.reports import ServingReport, percentile


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_percentile_nearest_rank():
    xs = [0.1, 0.2, 0.3, 0.4]
    assert percentile(xs, 50) == 0.3          # round(0.5*3)=2
    assert percentile(xs, 99) == 0.4
    assert percentile(xs, 0) == 0.1
    assert math.isnan(percentile([], 50))


def test_ttft_and_token_cadence():
    clk = Clock()
    rep = ServingReport(time_fn=clk)
    rep.record_submit(0)
    clk.t += 0.050                     # 50 ms to first token
    rep.record_token(0)
    for _ in range(3):
        clk.t += 0.010                 # 10 ms cadence
        rep.record_token(0)
    rep.record_retire(0)
    s = rep.summary()
    assert s["requests"] == {"submitted": 1, "completed": 1, "aborted": 0}
    assert s["tokens_emitted"] == 4
    assert abs(s["ttft_ms"]["p50"] - 50.0) < 1e-6
    assert s["ttft_ms"]["n"] == 1
    assert abs(s["token_latency_ms"]["p99"] - 10.0) < 1e-6
    assert s["token_latency_ms"]["n"] == 3
    assert abs(s["wall_s"] - 0.080) < 1e-9
    assert abs(s["tokens_per_s"] - 4 / 0.080) < 1e-6


def test_abort_and_scheduler_samples():
    clk = Clock()
    rep = ServingReport(time_fn=clk)
    rep.record_submit(0)
    rep.record_submit(1)
    clk.t += 0.02
    rep.record_token(0)
    rep.record_step(queue_depth=1, occupancy=0.5)
    rep.record_step(queue_depth=0, occupancy=1.0)
    rep.record_retire(0)
    rep.record_retire(1, aborted=True)
    s = rep.summary()
    assert s["requests"]["aborted"] == 1
    assert s["queue_depth"]["max"] == 1
    assert abs(s["slot_occupancy"]["mean"] - 0.75) < 1e-9
    # the JSON face round-trips (tools/serve_lm.py --report writes it)
    assert json.loads(rep.json())["requests"]["submitted"] == 2


def test_empty_report_is_well_formed():
    s = ServingReport(time_fn=Clock()).summary()
    assert s["tokens_emitted"] == 0
    assert math.isnan(s["tokens_per_s"])
    assert math.isnan(s["ttft_ms"]["p50"])
    assert s["queue_depth"]["max"] == 0
    assert math.isnan(s["acceptance_rate"])
    assert math.isnan(s["tokens_per_dispatch"])


def test_speculative_counters_and_ratios():
    rep = ServingReport(time_fn=Clock())
    # full accept of k=4 (5 emitted: 4 drafts + bonus), then a round
    # rejected at the first draft (1 emitted: the corrected token)
    rep.record_spec_round(4, 4, 5)
    rep.record_spec_round(4, 0, 1)
    s = rep.summary()
    assert s["draft_tokens_proposed"] == 8
    assert s["draft_tokens_accepted"] == 4
    assert s["acceptance_rate"] == 0.5
    assert s["tokens_per_dispatch"] == 3.0


def test_speculative_counters_survive_the_wire():
    rep = ServingReport(time_fn=Clock())
    rep.record_submit(0)
    rep.record_token(0)
    rep.record_spec_round(3, 2, 3)
    wire = json.loads(json.dumps(rep.to_wire()))
    back = ServingReport.from_wire(wire)
    assert back.raw() == rep.raw()
    raw = back.raw()
    assert raw["draft_tokens_proposed"] == 3
    assert raw["draft_tokens_accepted"] == 2
    assert raw["spec_dispatches"] == 1
    assert raw["spec_tokens_emitted"] == 3


def test_a_dispatch_of_k_tokens_spreads_its_gap_over_them():
    """decode_k emits up to k tokens of a request at one instant: the
    dispatch's gap is spread over them, not recorded as 0, 0, 0, big."""
    clk = Clock()
    rep = ServingReport(time_fn=clk)
    rep.record_submit(0)
    clk.t += 0.050
    rep.record_tokens(0, 1)            # the prefill's first token
    clk.t += 0.400
    rep.record_tokens(0, 4)            # one decode_k dispatch
    clk.t += 0.300
    rep.record_tokens(0, 3)            # the last one, cut by the budget
    rep.record_retire(0)
    assert rep.tokens_emitted == 8
    assert rep.ttft_s == [pytest.approx(0.050)]
    assert rep.token_gap_s == pytest.approx([0.1] * 4 + [0.1] * 3)
    s = rep.summary()
    assert s["itl_ms"]["n"] == 7
    assert abs(s["itl_ms"]["p50"] - 100.0) < 1e-6
    assert abs(s["itl_ms"]["p99"] - 100.0) < 1e-6


def test_a_first_dispatch_of_several_tokens_gives_ttft_and_no_gap():
    clk = Clock()
    rep = ServingReport(time_fn=clk)
    rep.record_submit(5)
    clk.t += 0.2
    rep.record_tokens(5, 3)            # an adopted or speculative first pull
    assert rep.ttft_s == [pytest.approx(0.2)] and rep.token_gap_s == []
    assert rep.tokens_emitted == 3
    clk.t += 0.03
    rep.record_token(5)                # the one-token face is n == 1
    assert rep.token_gap_s == [pytest.approx(0.03)]


def test_queue_wait_is_submit_to_admit():
    clk = Clock()
    rep = ServingReport(time_fn=clk)
    rep.record_submit(0)
    rep.record_submit(1)
    clk.t += 0.5
    rep.record_admit(0)
    clk.t += 1.5
    rep.record_admit(1)
    rep.record_admit(7)                # adopted, never queued here: no sample
    assert rep.queue_wait_s == pytest.approx([0.5, 2.0])
    assert rep.raw()["queue_wait_s"] == rep.queue_wait_s
    s = rep.summary()
    assert s["queue_wait_ms"]["n"] == 2
    assert abs(s["queue_wait_ms"]["p99"] - 2000.0) < 1e-6
    assert math.isnan(ServingReport(time_fn=clk).summary()
                      ["queue_wait_ms"]["p50"])


def test_queue_wait_survives_the_wire_and_the_version_moved():
    from chainermn_tpu.serving.reports import RAW_KEYS, REPORT_WIRE_VERSION

    clk = Clock()
    rep = ServingReport(time_fn=clk)
    rep.record_submit(0)
    clk.t += 0.25
    rep.record_admit(0)
    rep.record_tokens(0, 2)
    wire = json.loads(json.dumps(rep.to_wire()))
    assert wire["version"] == REPORT_WIRE_VERSION == 3
    assert set(wire["raw"]) == set(RAW_KEYS)
    assert ServingReport.from_wire(wire).raw()["queue_wait_s"] == [0.25]
    old = json.loads(json.dumps(wire))
    del old["raw"]["queue_wait_s"]
    with pytest.raises(ValueError, match="queue_wait_s"):
        ServingReport.from_wire(old)


def test_the_engine_reports_a_gap_per_token_and_a_wait_per_admission():
    """Through a real engine under decode_k: no gap of a request is 0, and
    every admitted request left one queue wait."""
    import itertools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.serving.engine import Engine, EngineConfig

    model = TransformerLM(vocab=43, d_model=32, n_heads=4, n_layers=1,
                          d_ff=48, max_len=64, attention="reference",
                          pos_emb="rope")
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    ticks = itertools.count()
    eng = Engine(model, params, EngineConfig(
        n_slots=2, capacity=32, max_new_tokens=9, prefill_cohort=1,
        buckets=[8, 32], decode_k=4), time_fn=lambda: float(next(ticks)))
    rs = np.random.RandomState(0)
    for n in (3, 5, 4):
        eng.submit(rs.randint(0, 43, (n,)).astype(np.int32))
    eng.run_until_drained()
    rep = eng.report
    assert rep.tokens_emitted == 27 and len(rep.ttft_s) == 3
    assert len(rep.token_gap_s) == 27 - 3
    assert min(rep.token_gap_s) > 0
    assert len(rep.queue_wait_s) == 3 and rep.queue_wait_s == sorted(
        rep.queue_wait_s)
