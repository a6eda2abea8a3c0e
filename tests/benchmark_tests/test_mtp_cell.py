"""The self-drafting cell (``dsv3-serve-mtp-reasongen``) at toy widths on the
CPU: its configuration file against the catalog's keys, the round of 128 sizes
the real workload file gives, the driver end to end through ``run.measure``
untraced and with the recorded fixture as its trace, the comparison broken
underneath (a token, a page row, the module), the fp8 control failing the
limits the sound program passes, the module's seeded rule, the counts'
arithmetic against ISSUE 33's figures and each new reader on made-up facts.
No number a CPU run gives is a device metric."""
import argparse
import copy
import json

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.drivers import serve_closed as base
from benchmark.harness import line as line_mod
from benchmark.harness import phases, registry, runtime

from .test_drivers import fixture_for_trace  # noqa: F401  (a fixture)

CELL = "dsv3-serve-mtp-reasongen"
CONFIG = "deepseek-v3"
NEW = ("serve_mtp_accept_pct", "serve_mtp_tokens_per_round",
       "serve_mtp_draft_busy_pct", "serve_mtp_round_roofline")
TOY_LIMITS = {"served_logit_gap": 0.02, "served_logit_gap_largest": 0.5,
              "state_logit_rms": 0.02, "state_logit_rms_largest": 0.1,
              "draft_logit_rms": 0.02, "draft_logit_rms_largest": 0.1}
PEAKS = registry.load_peaks("TPU v5 lite")


def toy_cell():
    bench = registry.load_benchmark()
    cell = registry.cell_entry(bench, CELL)
    workload = copy.deepcopy(registry.load_json("workloads", CELL))
    config = copy.deepcopy(registry.load_config(bench, cell["config"]))
    config["as_run"].update(
        vocab=512, d_model=64, n_heads=4, d_head=16, d_nope=16, d_rope=8,
        kv_rank=32, q_rank=24, d_ff=128, n_experts=32, held_lo=0, held_hi=4,
        d_expert=32, d_shared=32, n_layers=3, max_len=160, mla_block=16,
        compute_dtype="float32", param_dtype="float32",
        pattern=[["mla", "dense"], ["mla", "moe"], ["mla", "moe"]])
    workload["traffic"].update(
        clients=6, prompt_len=dict(median=24, sigma=0.7, min=8, max=60),
        output_len=dict(min=20, max=60), ramp_iterations=8, greedy_every=2)
    workload["engine"].update(n_slots=4, capacity=160,
                              buckets=[16, 32, 64, 160], decode_k=3)
    workload["check"].update(reference_len=160, reference_out=64, q_block=16,
                             min_tokens=6, sample_requests=2, sample_live=3,
                             balance_tokens=64, balance_sequences=4,
                             limits=dict(TOY_LIMITS))
    workload["trace"]["seconds"] = 0.2
    return bench, cell, workload, config


@pytest.fixture(autouse=True)
def cpu_has_no_memory_counter(monkeypatch):
    monkeypatch.setattr(runtime, "memory_peak_bytes", lambda devices: 1 << 20)


def make_run(workload, config, cell, seed=11, trace=0, seconds=1.0):
    import jax

    return runtime.Run(
        t_process=0.0, args=argparse.Namespace(seed=seed, seconds=seconds,
                                               trace=trace),
        cell=cell, workload=workload, config=config, peaks=PEAKS,
        devices=jax.devices()[:1],
        scratch=str(registry.ROOT) + "/.bench_scratch")


def measure(trace_flag=0, seed=2 ** 31 + 3, **engine):
    import jax

    bench, cell, workload, config = toy_cell()
    workload["engine"].update(engine)
    code, text = bench_run.measure(
        argparse.Namespace(seed=seed, seconds=1.0, trace=trace_flag), bench,
        cell, workload, config, jax.devices()[:1], PEAKS)
    return code, (json.loads(text) if text else None), bench


# -- the files ----------------------------------------------------------------

def test_configuration_file_holds_the_catalogs_keys_and_states_the_cut():
    bench = registry.load_benchmark()
    entry = registry.config_entry(bench, CONFIG)
    data = registry.load_config(bench, CONFIG)
    pub, run = data["published"], data["as_run"]
    assert data["source"] == entry["source"] and data["source"].endswith(
        "deepseek-ai/DeepSeek-V3/blob/main/config.json")
    assert data["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"]
    for key in ("published", "as_run", "departures", "assumed", "padded",
                "deployment", "equations"):
        assert key in data
    for key, value in pub.items():          # changed only where reduced
        if key in data["reduced"]:
            assert data[key] != value
        else:
            assert data[key] == value, key
    assert (data["num_hidden_layers"], data["first_k_dense_replace"],
            data["n_routed_experts"], data["vocab_size"]) == (5, 1, 16, 16160)
    # the module is IN: the key that counts it is as published
    assert data["num_nextn_predict_layers"] == run["n_mtp"] == 1
    assert "num_nextn_predict_layers" not in data["reduced"]
    # no width differs from the published one
    assert (run["d_model"], run["n_heads"], run["d_ff"], run["d_expert"],
            run["d_shared"], run["top_k"], run["kv_rank"], run["q_rank"],
            run["d_nope"], run["d_rope"], run["d_head"], run["n_experts"],
            run["n_group"], run["topk_group"], run["routed_scale"],
            run["rope_theta"], run["rope_scaling"], run["norm_eps"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["n_shared_experts"] * pub["moe_intermediate_size"],
        pub["num_experts_per_tok"], pub["kv_lora_rank"], pub["q_lora_rank"],
        pub["qk_nope_head_dim"], pub["qk_rope_head_dim"], pub["v_head_dim"],
        pub["n_routed_experts"], pub["n_group"], pub["topk_group"],
        pub["routed_scaling_factor"], pub["rope_theta"], pub["rope_scaling"],
        pub["rms_norm_eps"])
    # the cut: a sixteenth of the experts, an eighth of the vocabulary (the
    # guide's floors), one dense layer and four expert layers
    assert (run["held_lo"], run["held_hi"]) == (0, 16)
    assert run["held_hi"] * 16 == pub["n_routed_experts"]
    assert run["vocab"] * 8 == pub["vocab_size"] == 129280
    assert run["n_layers"] == len(run["pattern"]) == 5
    assert run["pattern"] == [["mla", "dense"]] + [["mla", "moe"]] * 4
    assert run["mla_gate"] is False and run["hc_mult"] == 1
    wl = registry.load_json("workloads", CELL)
    assert run["max_len"] == wl["engine"]["capacity"] == 1536 + 1536 + 256
    assert "16 chips" in data["deployment"]
    assert any("ep_size 1" in d and "CHECKPOINT" in d
               for d in data["departures"])
    assert len(entry["why"]) <= 200 and "MTP" in entry["why"]


def test_cell_declares_the_serving_metrics_and_its_own():
    bench = registry.load_benchmark()
    cell = registry.cell_entry(bench, CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert "1/16" in cell["why"] and len(cell["why"]) <= 200
    e2e = {m["name"] for m in line_mod.declared(bench, CELL, 0)}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"]: m for m in line_mod.declared(bench, CELL, 1)}
    assert set(NEW) | {
        "serve_device_idle_pct", "serve_iter_host_ms", "serve_occupancy_pct",
        "serve_prefill_dispatch_ms", "serve_admit_host_ms",
        "serve_decode_enqueue_host_ms", "serve_emit_host_ms",
        "serve_queue_age_s", "serve_admitted_per_iter",
        "serve_prefill_pad_pct", "serve_grouped_swiglu_roofline",
        "serve_moe_experts_touched_pct", "serve_moe_load_max_over_mean",
        "serve_moe_pairs_held_pct"} <= set(per_layer)
    for name in NEW:
        m = per_layer[name]
        assert CELL in m["workloads"] and m["moves"] == "serve_tokens_per_s"
        reader = registry.load_module("metrics", name)
        assert (reader.UNIT, reader.SOURCE, reader.LAYER, reader.MOVES) == (
            m["unit"], m["source"], m["layer"], m["moves"])
    # the other cells report nothing of this one's, and this one not the
    # one-query latent roofline
    assert "serve_latent_decode_roofline" not in per_layer
    for other in ("sc2-3b-serve-batchgen", "ling3-flash-serve-reasongen",
                  "xing4-serve-longdoc"):
        names = {m["name"] for m in line_mod.declared(bench, other, 1)}
        assert not names & set(NEW)
    # the cell's entries stand in their lists, in the order they were added,
    # wherever later configurations' entries stand
    assert CONFIG in [c["name"] for c in bench["configs"]]
    assert CELL in [w["name"] for w in bench["workloads"]]
    assert [m["name"] for m in bench["per_layer"]
            if m["name"] in NEW] == list(NEW)


def test_the_round_of_128_sizes_is_the_issues_traffic():
    wl = registry.load_json("workloads", CELL)
    tr, eng = wl["traffic"], wl["engine"]
    sizes = base.round_of_sizes(tr)
    prompts = sorted(p for p, _, _ in sizes)
    assert len(sizes) == tr["clients"] == eng["n_slots"] == 128
    assert prompts[0] == 128 and prompts[-1] == 1536       # the clips
    assert prompts[63] < 512 < prompts[64]                 # the median
    outs = sorted(o for _, o, _ in sizes)
    assert outs[0] == 512 and outs[-1] == 1536 and len(set(outs)) == 128
    assert sum(g for _, _, g in sizes) == 16               # every 8th greedy
    assert (tr["temperature"], tr["top_k"]) == (1.0, 0)
    # the self-drafted round's margin: prompt + max_new + 1 <= capacity
    assert all(p + o + 1 <= eng["capacity"] for p, o, _ in sizes)
    assert sizes == base.round_of_sizes(tr)            # sizes_seed, no --seed
    compiled = [b for b in eng["buckets"] if b < eng["capacity"]]
    assert len(compiled) == wl["check"]["buckets_used"]
    assert all(any(lo < p <= b for p in prompts)
               for lo, b in zip([0] + compiled, compiled))
    assert eng["self_draft"] is True and eng["decode_k"] == 8
    assert wl["check"]["reference_len"] == eng["capacity"]
    assert wl["check"]["reference_len"] % wl["check"]["q_block"] == 0
    assert set(wl["check"]["limits"]) == set(TOY_LIMITS)


def loop_model(tr, eng, seed, iterations, accept=0.404):
    """The closed loop's bookkeeping alone, no engine: ``Engine._admit``'s
    rule (the queue's leading same-bucket run, ``prefill_cohort`` at most,
    into free slots, one cohort an iteration), then ``decode_k`` rounds of
    1 + Bernoulli(``accept``) tokens a sampled row (a greedy row's seeded
    draft is never right), a finished row's client submitting the round's
    next sizes. Returns (queue depth before each iteration's admission, rows
    live after each iteration)."""
    rs = np.random.RandomState(seed)
    sizes = base.round_of_sizes(tr)
    buckets = sorted(eng["buckets"])
    bucket = lambda p: next(b for b in buckets if p <= b)
    queue, rows, free, j = list(sizes), [], eng["n_slots"], len(sizes)
    depth, live = [], []
    for _ in range(iterations):
        depth.append(len(queue))
        cohort = 0
        if queue and free:
            b = bucket(queue[0][0])
            while (queue and free and cohort < eng["prefill_cohort"]
                   and bucket(queue[0][0]) == b):
                _, n, greedy = queue.pop(0)
                free, cohort = free - 1, cohort + 1
                rows.append([n - 1, 0.0 if greedy else accept])
        ended = 0
        for row in rows:
            for hit in rs.random_sample(eng["decode_k"]) < row[1]:
                if row[0] > 0:
                    row[0] -= 1 + (hit and row[0] > 1)
            ended += row[0] <= 0
        rows = [r for r in rows if r[0] > 0]
        free += ended
        for _ in range(ended):
            queue.append(sizes[j % len(sizes)])
            j += 1
        live.append(len(rows))
    return depth, live


@pytest.mark.parametrize("accept", [0.38, 0.404, 0.43])
def test_the_window_lies_where_requests_still_queue(accept):
    """The ramp's reason (``traffic.ramp_note``): while requests queue, the
    cohorts an iteration admits are the fixed round's, whatever the seed's
    acceptance did to the rows; a window of 70 iterations must close before
    the queue first runs short, and open on slots three quarters full."""
    wl = registry.load_json("workloads", CELL)
    tr, eng = wl["traffic"], wl["engine"]
    ramp, window = tr["ramp_iterations"], 70
    for seed in range(8):
        depth, live = loop_model(tr, eng, seed, ramp + window + 40, accept)
        short = next(i for i, d in enumerate(depth)
                     if d < eng["prefill_cohort"])
        assert short >= ramp + window, (seed, short)
        assert min(live[ramp:ramp + window]) >= 0.75 * eng["n_slots"]
        assert np.mean(live[ramp:ramp + window]) >= 0.85 * eng["n_slots"]


# -- the driver ---------------------------------------------------------------

def test_cell_runs_end_to_end_untraced():
    code, line, bench = measure()
    assert code == 0 and line["correct"] is True
    declared = line_mod.declared(bench, CELL, 0)
    line_mod.check(line, declared, False)
    assert set(line["metrics"]) == {m["name"] for m in declared}
    assert line["attempted"] > 0 and line["failed"] == 0


def test_cell_runs_with_self_drafting_off_for_the_builders_comparison():
    """The same weights one token a step: the scratch copy of the workload
    file the builder measures 'without the module' with."""
    code, line, _ = measure(self_draft=False)
    assert code == 0 and line["correct"] is True


def test_cell_runs_end_to_end_with_the_fixture_as_its_trace(
        fixture_for_trace):  # noqa: F811
    """The fixture's device lines are another program's and there is no
    trace file to read scopes from, so the readers of the device trace find
    no module, kernel or scope of this cell and return nothing; every
    counter read from the program's own spans is there."""
    bench, cell, workload, config = toy_cell()
    workload["trace"]["seconds"] = 1.0
    declared = line_mod.declared(bench, CELL, 1)
    drv = registry.load_module("drivers", workload["driver"])
    outcome = drv.run(make_run(workload, config, cell, trace=1, seconds=2.5))
    assert all(c["ok"] for c in outcome["checks"]), outcome["checks"]
    checks = {c["name"]: c for c in outcome["checks"]}
    assert checks["decode_k_traces"]["value"] == 1
    assert checks["prefill_buckets_compiled"]["value"] == 3
    assert checks["streams_over_budget"]["value"] == 0
    assert checks["cursors_off_prompt_plus_emitted_less_1"]["value"] == []
    assert outcome["failed"] == 0 and outcome["facts"]["scopes_s"] is None
    values = bench_run.read_metrics(declared, outcome["facts"])
    missing = {k for k, v in values.items() if v is None}
    assert missing <= {"serve_mtp_draft_busy_pct", "serve_mtp_round_roofline",
                       "serve_grouped_swiglu_roofline",
                       "serve_prefill_dispatch_ms", "serve_iter_host_ms"}
    assert 0 < values["serve_mtp_accept_pct"] < 100
    assert 1.0 < values["serve_mtp_tokens_per_round"] < 2.0
    assert 0 < values["serve_moe_experts_touched_pct"] <= 100
    assert 0 < values["serve_moe_pairs_held_pct"] < 50     # 4 of 32 held
    table = phases.table_line(outcome["facts"]["program_rows"])
    for attr in ("drafts_verified", "drafts_accepted", "tokens_emitted",
                 "rounds", "filled_columns", "experts_touched",
                 "mtp_experts_touched", "mtp_pairs_held"):
        assert attr in table


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from chainermn_tpu.serving import engine as engine_mod

    real_emit = engine_mod.Engine._emit

    def emit(self, req, token):
        return real_emit(self, req, (int(token) + 7) % 512)

    monkeypatch.setattr(engine_mod.Engine, "_emit", emit)
    code, line, _ = measure()
    assert code == 0 and line["correct"] is False


def test_a_rejected_drafts_row_left_in_the_page_is_not_correct(monkeypatch):
    """The fault the round's discipline exists to prevent: the cursor moves
    two whatever was accepted, so a rejected draft's latent stays in every
    page as if it were the stream's."""
    from chainermn_tpu.serving import state_cache

    real = state_cache.acceptance_scan

    def scan(*a):
        out, keys, rem, alive, m = real(*a)
        return out, keys, rem, alive, m * 0 + 2

    monkeypatch.setattr(state_cache, "acceptance_scan", scan)
    code, line, _ = measure()
    assert code == 0 and line["correct"] is False


def test_a_wrong_module_fails_the_draft_limit_alone(monkeypatch, capsys):
    """A module that reads its hidden state a position late only drafts
    worse: streams, main logits and every counter stay sound. The draft's
    own comparison is what catches it."""
    from chainermn_tpu.models import hybrid as model_mod

    real = model_mod.MTPModule.__call__

    def late(self, hidden, emb_next, *a, **kw):
        import jax.numpy as jnp
        return real(self, jnp.roll(hidden, 1, axis=1), emb_next, *a, **kw)

    monkeypatch.setattr(model_mod.MTPModule, "__call__", late)
    code, line, _ = measure()
    assert code == 0 and line["correct"] is False
    said = capsys.readouterr().out
    bad = [l.split(":")[0] for l in said.splitlines()
           if l.startswith("check ") and l.endswith("NOT OK")]
    assert bad and all("draft_logit_rms" in b for b in bad), bad


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 9])
def test_control_in_fp8_fails_the_limits_the_program_passes(seed):
    bench, cell, workload, config = toy_cell()
    drv = registry.load_module("drivers", "serve_closed_mtp")
    run = make_run(workload, config, cell, seed=seed)
    gaps = next(drv.calibrate(run, [seed], {seed}))
    print(gaps)
    assert gaps["served_gap"] <= TOY_LIMITS["served_logit_gap"] \
        < gaps["control_gap"], gaps
    assert gaps["state_rms"] <= TOY_LIMITS["state_logit_rms"] \
        < gaps["control_rms"], gaps
    assert gaps["draft_rms"] <= TOY_LIMITS["draft_logit_rms"] \
        < gaps["control_draft_rms"], gaps
    assert gaps["control_draft_rms"] > 3 * gaps["draft_rms"]
    assert gaps["live_rows"] >= 1 and 0 < gaps["acceptance"] < 1
    assert all(8 <= n < 160 for n in gaps["positions"])


def test_the_modules_leaves_follow_the_plain_rules_and_one_layer_equals_the_tree():
    import jax.numpy as jnp

    drv = registry.load_module("drivers", "serve_closed_mtp")
    _, cell, workload, config = toy_cell()
    cfg = config["as_run"]
    model, spec = drv.model_and_spec(cfg, jnp.float32)
    assert model.n_mtp == 1
    tree = drv.make_params(7, spec, cfg, jnp.float32)
    flat = lambda t: sorted(drv.weights.flatten(t).items())
    d = cfg["d_model"]
    eh = np.asarray(tree["mtp_0"]["eh_proj"]["kernel"])
    assert eh.shape == (2 * d, d)
    # no leaf of the module is shaped for drafting: the projection is a
    # fan-in-scaled kernel over its 2d inputs, neither half passed through
    std = lambda t: float(np.std(np.asarray(t)))
    assert std(eh[:d]) == pytest.approx((2 * d) ** -0.5, rel=0.1)
    assert std(eh[d:]) == pytest.approx((2 * d) ** -0.5, rel=0.1)
    assert abs(float(np.mean(np.diag(eh[d:])))) < 5 * (2 * d) ** -0.5 / d ** 0.5
    # and the module block's leaves are drawn as a main expert block's
    mb, b1 = tree["mtp_0"]["block"], tree["block_1"]
    for pick in (lambda b: b["mla"]["o_proj"]["kernel"],
                 lambda b: b["moe"]["w_down"],
                 lambda b: b["shared"]["down"]["kernel"],
                 lambda b: b["moe"]["w_gate"]):
        assert std(pick(mb)) == pytest.approx(std(pick(b1)), rel=0.1)
    # the reference's own maker gives the same leaves, the bias handed in
    run = make_run(workload, config, cell, seed=7)
    leaves = drv.Leaves(spec, {})
    n = cfg["n_layers"]
    for layer, want in ((2, tree["block_2"]), (n, tree["mtp_0"])):
        mine = drv.layer_maker(run, leaves, layer)(
            np.uint32(7), np.int32(layer), jnp.full((32,), 0.25))
        for (path, a), (_, b) in zip(flat(mine), flat(want)):
            if path[-1] == "router_bias":
                assert np.asarray(a).tolist() == [0.25] * 32
            else:       # made inside another program: an ulp may differ
                np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    other = drv.make_params(8, spec, cfg, jnp.float32)
    for (path, x), (_, y) in zip(flat(tree), flat(other)):
        assert not np.array_equal(x, y), path       # every leaf from --seed
    model.apply({"params": tree}, np.zeros((1, 8), np.int32))


def test_balanced_biases_cover_every_expert_layer_and_the_module():
    import jax.numpy as jnp

    drv = registry.load_module("drivers", "serve_closed_mtp")
    _, cell, workload, config = toy_cell()
    cfg = config["as_run"]
    _, spec = drv.model_and_spec(cfg, jnp.float32)
    biases = drv.balanced_biases(make_run(workload, config, cell, seed=3),
                                 spec)
    assert sorted(biases) == [1, 2, 3]          # two expert layers, module
    for b in biases.values():
        b = np.asarray(b)
        assert b.shape == (32,) and b.dtype == np.float32
        assert 0 < np.abs(b).max() < 1.0


# -- the counts and the readers ------------------------------------------------

def test_counts_match_the_issues_arithmetic():
    bench = registry.load_benchmark()
    cfg = registry.load_config(bench, CONFIG)["as_run"]
    c = registry.load_module("counts", "mtp_round")
    assert c.mla_params(cfg) == pytest.approx(187.1e6, rel=1e-3)
    assert c.expert_params(cfg) == pytest.approx(44.04e6, rel=1e-3)
    assert c.outside_experts(cfg, "moe") == pytest.approx(233.0e6, rel=1e-3)
    assert c.outside_experts(cfg, "moe") + 16 * c.expert_params(cfg) == (
        pytest.approx(937.6e6, rel=1e-3))
    assert c.outside_experts(cfg, "dense") == pytest.approx(583.5e6,
                                                            rel=1e-3)
    assert 2 * cfg["vocab"] * cfg["d_model"] == pytest.approx(231.7e6,
                                                              rel=1e-3)
    assert c.module_outside_experts(cfg) + 16 * c.expert_params(cfg) == (
        pytest.approx(1040.4e6, rel=1e-3))
    assert c.all_params(cfg) == pytest.approx(5.61e9, rel=2e-3)
    assert 2 * c.all_params(cfg) == pytest.approx(11.21e9, rel=2e-3)
    assert c.page_width(cfg) == 640 and c.latent_layers(cfg) == 6
    assert c.page_bytes_per_column(cfg) == 7680
    assert 128 * 3328 * c.page_bytes_per_column(cfg) == pytest.approx(
        3.27e9, rel=2e-3)
    # a round of 128 live rows at a mean fill of 1,800 that reaches every
    # held expert: 10.98 GB of weights, 1.8 GB of page columns
    touched = 5 * 16
    weights_read = c.round_bytes(cfg, touched, 0)
    assert weights_read == pytest.approx(10.98e9, rel=2e-3)
    assert weights_read / PEAKS["hbm_bytes_per_s"] == pytest.approx(
        13.4e-3, rel=5e-3)
    assert c.round_bytes(cfg, touched, 128 * 1800) - weights_read == (
        pytest.approx(1.77e9, rel=1e-2))
    assert c.attention_flops_per_column(cfg) == 2176
    # two queries against one 1,280 B row: 435 flop/B, over the chip's 240
    assert 2 * 128 * 2176 / 1280 == pytest.approx(435, rel=1e-3)
    assert PEAKS["bf16_flops_per_s"] / PEAKS["hbm_bytes_per_s"] == (
        pytest.approx(240, rel=5e-3))
    # the attention of such a round with every draft accepted: 0.74 TFLOP
    flops = c.round_flops(cfg, 128, 256, 128 * 1800, 0)
    matrices = c.round_flops(cfg, 128, 256, 0, 0)
    assert flops - matrices == pytest.approx(0.77e12, rel=1e-2)
    # the round's bound is its bytes: bandwidth-bound weights beside
    # compute-bound attention
    pairs = 256 * 8 * 4 / 16 + 256 * 8 / 16
    least, bound = c.least_seconds(
        c.round_flops(cfg, 128, 256, 128 * 1800, pairs),
        c.round_bytes(cfg, touched, 128 * 1800), PEAKS)
    assert bound == "memory" and least == pytest.approx(15.6e-3, rel=2e-2)


def made_up_facts():
    """One traced sub-window of two iterations: a decode dispatch of 8 rounds
    over 100 live rows each."""
    from chainermn_tpu.tracing import Row

    bench = registry.load_benchmark()
    workload = copy.deepcopy(registry.load_json("workloads", CELL))
    workload["engine"]["decode_k"] = 8      # the made-up dispatches' rounds
    config = registry.load_config(bench, CONFIG)
    rows, rid = [], 0
    for it in range(2):
        t = float(it)
        step = Row(rid + 1, None, "engine.step", t, t + 0.9, {})
        rows += [step,
                 Row(rid + 2, step.id, "engine.decode.enqueue", t + 0.5,
                     t + 0.6, dict(
                         live=100, filled_columns=100 * 1500, rounds=8,
                         drafts_verified=780, drafts_accepted=273,
                         tokens_emitted=1070, experts_touched=8 * 4 * 16,
                         mtp_experts_touched=8 * 16,
                         pairs_held=8 * 4 * 100, mtp_pairs_held=8 * 70,
                         pairs_routed=8 * 4 * 1600))]
        rid += 2
    return {"kind": "serve", "program_rows": rows, "peaks": PEAKS,
            "workload": workload, "config": config,
            "scopes_s": {"mtp_draft": 0.2, "mtp_draft/mla_absorbed": 0.05,
                         "mtp_accept/sample": 0.03, "mla_absorbed": 0.4,
                         "(none)": 0.5},
            "trace": {"busy_s": 1.4, "window_s": 2.0,
                      "module_runs_s": {"jit__decode_k": [0.19, 0.2, 0.24]},
                      "op_family_s": {}, "op_family_calls": {}}}


def test_each_new_reader_on_made_up_facts():
    facts = made_up_facts()
    read = lambda name: registry.load_module("metrics", name).read(facts)
    assert read("serve_mtp_accept_pct") == pytest.approx(35.0)
    assert read("serve_mtp_tokens_per_round") == pytest.approx(
        1070 / (100 * 8))
    assert read("serve_mtp_draft_busy_pct") == pytest.approx(
        100 * 0.28 / 1.4)
    c = registry.load_module("counts", "mtp_round")
    cfg = facts["config"]["as_run"]
    bytes_ = c.round_bytes(cfg, 5 * 16, 100 * 1500)
    flops = c.round_flops(cfg, 100, 1070 / 8, 100 * 1500, 4 * 100 + 70)
    least = max(bytes_ / PEAKS["hbm_bytes_per_s"],
                flops / PEAKS["bf16_flops_per_s"])
    assert read("serve_mtp_round_roofline") == pytest.approx(
        100 * least / (0.2 / 8))
    assert 0 < read("serve_mtp_round_roofline") < 100
    # filled columns only: the same round over full pages would count more
    assert c.round_bytes(cfg, 80, 100 * 3328) > bytes_


def test_readers_return_nothing_on_a_program_without_the_counters():
    """A program with the spans but without this PR's attributes or scopes
    (the parent), and one with no spans at all. Neither raises."""
    facts = made_up_facts()

    class NoSpans:
        def named(self, name):
            return []

    bare = [r._replace(attrs={"live": 3}) for r in facts["program_rows"]]
    for rows in (None, [], bare):
        f = dict(facts, program_rows=rows, scopes_s=None, spans=NoSpans(),
                 trace=dict(facts["trace"], module_runs_s={}))
        for name in NEW:
            reader = registry.load_module("metrics", name)
            try:
                got = reader.read(f)
            except LookupError:
                got = None      # phases.iterations: no engine.step at all
            assert got is None, (name, rows)
    # scopes of another program: the busy share finds nothing of its own
    f = dict(facts, scopes_s={"mla_absorbed": 0.4, "(none)": 0.5})
    assert registry.load_module(
        "metrics", "serve_mtp_draft_busy_pct").read(f) is None
