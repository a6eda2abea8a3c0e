"""The long-document cell (``xing4-serve-longdoc``) at toy widths on the CPU:
its configuration file against the catalog's keys, the round of 12 sizes the
real workload file gives, the driver end to end through ``run.measure``
untraced and with the recorded fixture as its trace, the comparison broken
underneath, the fp8 control failing the limits the sound program passes, this
configuration's seeded rule, the counts' arithmetic and each new reader on
made-up facts. No number a CPU run gives is a device metric."""
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

CELL = "xing4-serve-longdoc"
CONFIG = "xing4.0-29b-a4b"
NEW = ("serve_latent_prefill_roofline", "serve_latent_decode_roofline",
       "serve_mhc_busy_pct", "serve_prefill_tokens_per_iter")
TOY_LIMITS = {"served_logit_gap": 0.02, "state_logit_rms": 0.02,
              "state_logit_rms_worst_slot": 0.05}
PEAKS = registry.load_peaks("TPU v5 lite")


def toy_cell():
    bench = registry.load_benchmark()
    cell = registry.cell_entry(bench, CELL)
    workload = copy.deepcopy(registry.load_json("workloads", CELL))
    config = copy.deepcopy(registry.load_config(bench, cell["config"]))
    config["as_run"].update(
        vocab=512, d_model=64, n_heads=4, d_head=16, d_nope=16, d_rope=8,
        kv_rank=32, q_rank=24, d_ff=128, n_experts=16, held_lo=0, held_hi=16,
        d_expert=32, d_shared=32, n_layers=3, max_len=160, mla_block=16,
        compute_dtype="float32", param_dtype="float32",
        pattern=[["mla", "dense"], ["mla", "moe"], ["mla", "moe"]])
    workload["traffic"].update(
        clients=5, prompt_len=dict(median=40, sigma=0.4, min=20, max=90),
        output_len=dict(min=20, max=44), ramp_iterations=12)
    workload["engine"].update(n_slots=4, capacity=160, buckets=[160],
                              prefill_chunk=16, decode_k=4)
    workload["check"].update(reference_len=160, reference_out=48, q_block=16,
                             min_tokens=6, sample_requests=2, sample_live=2,
                             balance_tokens=64, live_dispatches=3,
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


def measure(trace_flag=0, seed=2 ** 31 + 3):
    import jax

    bench, cell, workload, config = toy_cell()
    code, text = bench_run.measure(
        argparse.Namespace(seed=seed, seconds=1.0, trace=trace_flag), bench,
        cell, workload, config, jax.devices()[:1], PEAKS)
    return code, (json.loads(text) if text else None), bench


def test_configuration_file_holds_the_catalogs_keys_and_states_the_cut():
    bench = registry.load_benchmark()
    entry = registry.config_entry(bench, CONFIG)
    data = registry.load_config(bench, CONFIG)
    pub, run = data["published"], data["as_run"]
    assert data["source"] == entry["source"] and "Xing4.0-29B-A4B" in (
        data["source"])
    assert data["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace",
        "num_nextn_predict_layers"]
    for key in ("published", "as_run", "departures", "assumed", "padded",
                "deployment", "equations"):
        assert key in data
    for key, value in pub.items():          # changed only where reduced
        if key in data["reduced"]:
            assert data[key] != value
        else:
            assert data[key] == value, key
    assert (data["num_hidden_layers"], data["first_k_dense_replace"],
            data["num_nextn_predict_layers"]) == (7, 1, 0)
    # no width differs from the published one
    assert (run["d_model"], run["n_heads"], run["d_ff"], run["d_expert"],
            run["d_shared"], run["top_k"], run["kv_rank"], run["q_rank"],
            run["d_nope"], run["d_rope"], run["d_head"], run["n_experts"],
            run["n_group"], run["topk_group"], run["routed_scale"],
            run["rope_theta"], run["rope_scaling"], run["vocab"],
            run["hc_mult"], run["hc_sinkhorn_iters"], run["hc_eps"],
            run["hc_clamp"], -run["hc_clamp"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["n_shared_experts"] * pub["moe_intermediate_size"],
        pub["num_experts_per_tok"], pub["kv_lora_rank"], pub["q_lora_rank"],
        pub["qk_nope_head_dim"], pub["qk_rope_head_dim"], pub["v_head_dim"],
        pub["n_routed_experts"], pub["n_group"], pub["topk_group"],
        pub["routed_scaling_factor"], pub["rope_theta"], pub["rope_scaling"],
        pub["vocab_size"], pub["hc_mult"], pub["hc_sinkhorn_iters"],
        pub["hc_eps"], pub["mhc_h_res_clamp_max"],
        pub["mhc_h_res_clamp_min"])
    # the cut: every expert and vocabulary row held, one dense layer, six
    # expert layers, every mixer latent attention
    assert (run["held_lo"], run["held_hi"]) == (0, pub["n_routed_experts"])
    assert run["n_layers"] == len(run["pattern"]) == 7
    assert run["pattern"] == [["mla", "dense"]] + [["mla", "moe"]] * 6
    assert run["mla_gate"] is False
    wl = registry.load_json("workloads", CELL)
    assert run["max_len"] == wl["engine"]["capacity"] == 32768 + 256


def test_cell_declares_the_serving_metrics_and_its_own():
    bench = registry.load_benchmark()
    cell = registry.cell_entry(bench, CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    e2e = {m["name"] for m in line_mod.declared(bench, CELL, 0)}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"]: m for m in line_mod.declared(bench, CELL, 1)}
    assert set(NEW) | {
        "serve_device_idle_pct", "serve_iter_host_ms", "serve_occupancy_pct",
        "serve_prefill_dispatch_ms", "serve_admit_host_ms",
        "serve_decode_enqueue_host_ms", "serve_emit_host_ms",
        "serve_queue_age_s", "serve_admitted_per_iter",
        "serve_prefill_pad_pct", "serve_grouped_swiglu_roofline",
        "serve_moe_experts_touched_pct",
        "serve_moe_load_max_over_mean"} <= set(per_layer)
    for name in NEW:
        m = per_layer[name]
        assert CELL in m["workloads"] and m["moves"] == "serve_tokens_per_s"
        reader = registry.load_module("metrics", name)
        assert (reader.UNIT, reader.SOURCE, reader.LAYER, reader.MOVES) == (
            m["unit"], m["source"], m["layer"], m["moves"])
    # the other cells report nothing of this one's
    for other in ("sc2-3b-serve-batchgen", "ling3-flash-serve-reasongen"):
        names = {m["name"] for m in line_mod.declared(bench, other, 1)}
        assert not names & set(NEW)


def test_the_round_of_twelve_sizes_is_the_issues_traffic():
    wl = registry.load_json("workloads", CELL)
    tr, eng = wl["traffic"], wl["engine"]
    sizes = base.round_of_sizes(tr)
    prompts = sorted(p for p, _, _ in sizes)
    assert len(sizes) == tr["clients"] == eng["n_slots"] == 12
    assert 8192 <= prompts[0] and prompts[-1] <= 32768
    assert prompts[5] < 16384 < prompts[6]          # the median between
    assert prompts[0] < 9000 and prompts[-1] > 30000    # the tails reach
    outs = sorted(o for _, o, _ in sizes)
    assert outs[0] == 64 and outs[-1] == 256 and len(set(outs)) == 12
    assert sum(g for _, _, g in sizes) == 6            # every 2nd greedy
    assert all(p + o <= eng["capacity"] for p, o, _ in sizes)
    assert sizes == base.round_of_sizes(tr)            # sizes_seed, no --seed
    # a prompt is 4 to 16 chunks; the last chunk of each is partly padding
    chunks = [-(-p // eng["prefill_chunk"]) for p in prompts]
    assert min(chunks) >= 4 and max(chunks) <= 16
    assert (eng["prefill_cohort"], eng["decode_k"], eng["token_budget"]) == (
        1, 8, None)


def test_cell_runs_end_to_end_untraced():
    code, line, bench = measure()
    assert code == 0 and line["correct"] is True
    declared = line_mod.declared(bench, CELL, 0)
    line_mod.check(line, declared, False)
    assert set(line["metrics"]) == {m["name"] for m in declared}
    assert line["attempted"] > 0 and line["failed"] == 0


def test_cell_runs_end_to_end_with_the_fixture_as_its_trace(
        fixture_for_trace):  # noqa: F811
    """The fixture's device lines are another program's and there is no
    trace file to read scopes from, so the readers of the device trace find
    no module, kernel or scope of this cell and return nothing; every
    counter read from the program's own spans is there."""
    bench, cell, workload, config = toy_cell()
    # a sub-window of 1 s from 0.75 s on: whole iterations inside it even
    # when the other workers load the machine
    workload["trace"]["seconds"] = 1.0
    declared = line_mod.declared(bench, CELL, 1)
    drv = registry.load_module("drivers", workload["driver"])
    outcome = drv.run(make_run(workload, config, cell, trace=1, seconds=2.5))
    assert all(c["ok"] for c in outcome["checks"]), outcome["checks"]
    checks = {c["name"]: c for c in outcome["checks"]}
    assert checks["chunk_programs"]["value"] == [[[1, 16], 1]]
    assert checks["decode_k_traces"]["value"] == 1
    assert outcome["failed"] == 0 and outcome["facts"]["scopes_s"] is None
    values = bench_run.read_metrics(declared, outcome["facts"])
    missing = {k for k, v in values.items() if v is None}
    assert missing <= {"serve_latent_prefill_roofline",
                       "serve_latent_decode_roofline", "serve_mhc_busy_pct",
                       "serve_grouped_swiglu_roofline",
                       "serve_prefill_dispatch_ms",   # no jit__pc run there
                       # read between the FIXTURE's own window marks: on a
                       # loaded machine no whole iteration fits between them
                       "serve_iter_host_ms"}
    assert 0 < values["serve_prefill_tokens_per_iter"] <= 16
    assert 0 <= values["serve_prefill_pad_pct"] < 100
    assert 0 < values["serve_moe_experts_touched_pct"] <= 100
    table = phases.table_line(outcome["facts"]["program_rows"])
    for attr in ("attended_pairs", "start_tokens", "filled_columns",
                 "prompt_tokens", "experts_touched"):
        assert attr in table
    # what the chunk spans counted is what the prompts' sizes give
    rows = outcome["facts"]["program_rows"]
    count = registry.load_module("counts", "latent_prefill")
    for r in rows:
        if r.name == "engine.admit" and r.attrs.get("prompt_tokens"):
            v = r.attrs["prompt_tokens"]
            assert r.attrs["attended_pairs"] == count.chunk_pairs(
                r.attrs["start_tokens"], v)
            assert r.attrs["start_tokens"] % 16 == 0 and 0 < v <= 16


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from chainermn_tpu.serving import engine as engine_mod

    real_emit = engine_mod.Engine._emit

    def emit(self, req, token):
        return real_emit(self, req, (int(token) + 7) % 512)

    monkeypatch.setattr(engine_mod.Engine, "_emit", emit)
    code, line, _ = measure()
    assert code == 0 and line["correct"] is False


def test_a_chunk_written_a_column_off_is_not_correct(monkeypatch):
    """The fault the cell exists to catch: a chunk whose latents land one
    column from where its queries look. The page's later readers — the next
    chunks and every decode step — see the wrong history."""
    from chainermn_tpu.models import hybrid as model_mod

    real = model_mod._write_window

    def shifted(page, chunk, pos, n, slots):
        return real(page, chunk, pos + (pos > 0), n, slots)

    monkeypatch.setattr(model_mod, "_write_window", shifted)
    code, line, _ = measure()
    assert code == 0 and line["correct"] is False


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 9])
def test_control_in_fp8_fails_both_limits_the_program_passes(seed):
    bench, cell, workload, config = toy_cell()
    drv = registry.load_module("drivers", "serve_closed_longdoc")
    run = make_run(workload, config, cell, seed=seed)
    gaps = next(drv.calibrate(run, [seed], {seed}))
    print(gaps)
    assert gaps["served_gap"] <= TOY_LIMITS["served_logit_gap"] \
        < gaps["control_gap"], gaps
    assert gaps["state_rms"] <= TOY_LIMITS["state_logit_rms"] \
        < gaps["control_rms"], gaps
    assert gaps["control_rms"] > 3 * gaps["state_rms"]
    assert 0 <= gaps["near_tie_share"] < 0.5 and gaps["live_rows"] >= 1
    assert all(20 <= n < 160 for n in gaps["positions"])


def test_seeded_weights_lead_b_res_by_its_diagonal_and_one_layer_equals_the_tree():
    import jax.numpy as jnp

    drv = registry.load_module("drivers", "serve_closed_longdoc")
    _, _, _, config = toy_cell()
    cfg = config["as_run"]
    model, spec = drv.model_and_spec(cfg, jnp.float32)
    tree = drv.make_params(7, spec, cfg["n_layers"], jnp.float32)
    again = drv.seeded(drv.hybrid.make_block(7, spec, 2, cfg["n_layers"],
                                             jnp.float32))
    flat = lambda t: sorted(drv.weights.flatten(t).items())
    assert all(np.array_equal(a, b) for (_, a), (_, b) in zip(
        flat(tree["block_2"]), flat(again)))
    # the reference's own maker gives the same leaves, the bias handed in
    _, cell, workload, config = toy_cell()
    make = drv.block_maker(make_run(workload, config, cell),
                           drv.Leaves(spec, {}), ("mla", "moe"), 2)
    mine = make(np.uint32(7), np.int32(2), jnp.full((16,), 0.25))
    for (path, a), (_, b) in zip(flat(mine), flat(again)):
        if path == ("moe", "router_bias"):
            assert np.asarray(a).tolist() == [0.25] * 16
        else:           # made inside another program: an ulp may differ
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    other = drv.make_params(8, spec, cfg["n_layers"], jnp.float32)
    for (path, x), (_, y) in zip(flat(tree), flat(other)):
        assert not np.array_equal(x, y), path       # every leaf from --seed
    for name in ("hc_mix", "hc_ffn"):
        b_res = np.asarray(tree["block_1"][name]["b_res"])
        assert b_res.shape == (4, 4)
        assert np.diag(b_res).mean() > 1.0 > np.abs(
            b_res - np.diag(np.diag(b_res))).mean()
        assert np.asarray(tree["block_1"][name]["phi"]).shape == (256, 24)
    # fan-in d (64), not E (16): the entries' spread is 64^-1/2
    assert np.std(np.asarray(tree["block_1"]["moe"]["w_gate"])
                  ) == pytest.approx(0.125, rel=0.1)
    model.apply({"params": tree}, np.zeros((1, 8), np.int32))


def test_counts_match_the_issues_arithmetic():
    bench = registry.load_benchmark()
    cfg = registry.load_config(bench, CONFIG)["as_run"]
    d = registry.load_module("counts", "latent_decode")
    p = registry.load_module("counts", "latent_prefill")
    assert d.mla_mixer_params(cfg) == pytest.approx(28.41e6, rel=1e-3)
    assert 2 * d.map_params(cfg) == pytest.approx(0.69e6, rel=5e-3)
    assert d.expert_params(cfg) == pytest.approx(11.01e6, rel=1e-3)
    assert d.all_weight_bytes(cfg) == pytest.approx(11.07e9, rel=1e-3)
    assert d.latent_bytes_per_column(cfg) == 7 * 576 * 2 == 8064
    assert 12 * 33024 * d.latent_bytes_per_column(cfg) == pytest.approx(
        3.20e9, rel=2e-3)
    # a step that reaches 30 experts a layer over 12 slots of 16k columns
    step = d.decode_step_bytes(cfg, 6 * 30, 12 * 16384)
    assert step == pytest.approx(
        d.non_expert_weight_bytes(cfg) + 180 * 22.02e6 + 1.585e9, rel=1e-3)
    assert p.flops_per_pair_and_head(cfg) == 640 and p.mla_layers(cfg) == 7
    assert p.matrix_flops_per_token(cfg) == pytest.approx(1.27e9, rel=5e-3)
    assert 32 * 640 == 20480            # flop a context token and layer
    assert p.chunk_pairs(0, 2048) == 2048 * 2049 // 2
    assert p.chunk_pairs(4096, 2048) == 2048 * 4096 + 2048 * 2049 // 2
    # a whole 16,384-token prompt in 8 chunks: the causal triangle
    pairs = sum(p.chunk_pairs(s, 2048) for s in range(0, 16384, 2048))
    assert pairs == 16384 * 16385 // 2
    assert p.attention_flops(pairs, cfg) == pytest.approx(
        7 * 32 * 640 * 16384 ** 2 / 2, rel=1e-3)


def made_up_facts():
    """One traced sub-window of two iterations: a chunk of 2,048 queries at
    cursor 4,096 each, a decode dispatch of 8 steps over 3 live slots."""
    from chainermn_tpu.tracing import Row

    bench, _, workload, config = (registry.load_benchmark(), None,
                                  registry.load_json("workloads", CELL),
                                  registry.load_config(
                                      registry.load_benchmark(), CONFIG))
    rows, rid = [], 0
    for it in range(2):
        t = float(it)
        step = Row(rid + 1, None, "engine.step", t, t + 0.9, {})
        rows += [step,
                 Row(rid + 2, step.id, "engine.admit", t, t + 0.1, dict(
                     admitted=0, rows=1, prompt_tokens=2048, padded_tokens=0,
                     start_tokens=4096,
                     attended_pairs=2048 * 4096 + 2048 * 2049 // 2)),
                 Row(rid + 3, step.id, "engine.decode.enqueue", t + 0.5,
                     t + 0.6, dict(live=3, filled_columns=3 * 20000,
                                   experts_touched=8 * 6 * 10))]
        rid += 3
    pairs = 2 * (2048 * 4096 + 2048 * 2049 // 2)
    least_s = pairs * 7 * 32 * 640 / PEAKS["bf16_flops_per_s"]
    facts = {"kind": "serve", "program_rows": rows, "peaks": PEAKS,
             "workload": workload, "config": config,
             "scopes_s": {"mla_chunk": 4 * least_s, "mhc_mix": 0.05,
                          "mhc_mix/moe_route": 0.01, "(none)": 0.5},
             "trace": {"busy_s": 1.2, "window_s": 2.0,
                       "module_runs_s": {"jit__decode_k": [0.16, 0.2, 0.24]},
                       "op_family_s": {}, "op_family_calls": {}}}
    return facts


def test_each_new_reader_on_made_up_facts():
    facts = made_up_facts()
    read = lambda name: registry.load_module("metrics", name).read(facts)
    assert read("serve_latent_prefill_roofline") == pytest.approx(25.0)
    assert read("serve_mhc_busy_pct") == pytest.approx(100 * 0.06 / 1.2)
    assert read("serve_prefill_tokens_per_iter") == 2048
    d = registry.load_module("counts", "latent_decode")
    cfg = facts["config"]["as_run"]
    least = d.decode_step_bytes(cfg, 60, 60000) / PEAKS["hbm_bytes_per_s"]
    assert read("serve_latent_decode_roofline") == pytest.approx(
        100 * least / (0.2 / 8))
    # filled columns only: the same step over full pages would count 6.6x
    assert d.decode_step_bytes(cfg, 60, 3 * 33024) > d.decode_step_bytes(
        cfg, 60, 60000)
    assert 0 < read("serve_latent_decode_roofline") < 100


def test_readers_return_nothing_on_a_program_without_the_counters():
    """A program with the spans but without this PR's attributes or scopes,
    and one with no spans at all. Neither raises."""
    facts = made_up_facts()

    class NoSpans:
        def named(self, name):
            return []

    bare = [r._replace(attrs={}) for r in facts["program_rows"]]
    for rows in (None, [], bare):
        f = dict(facts, program_rows=rows, scopes_s=None, spans=NoSpans(),
                 trace=dict(facts["trace"], module_runs_s={}))
        for name in NEW[:3]:
            reader = registry.load_module("metrics", name)
            try:
                got = reader.read(f)
            except LookupError:
                got = None      # phases.iterations: no engine.step at all
            assert got is None, (name, rows)


def test_balanced_bias_evens_the_load_and_both_sides_get_the_same_arrays():
    """``noaux_tc``'s rule on fixed scores: from a router that sends most
    pairs to a few experts to every expert within a few pairs of the mean;
    the driver's biases are one array a layer, in the program's tree and in
    the reference's hands alike."""
    import jax.numpy as jnp

    from benchmark.references import xing_mhc as ref

    rs = np.random.RandomState(0)
    y = jnp.asarray(rs.randn(512, 32) + 2.0 * rs.randn(32), jnp.float32)
    p = {"router": jnp.asarray(rs.randn(32, 16) / 32 ** 0.5, jnp.float32),
         "router_bias": jnp.zeros((16,), jnp.float32)}
    cfg = dict(top_k=4, routed_scale=2.0)
    load = lambda p: np.bincount(
        np.asarray(ref.route(y, p, cfg)[0]).ravel(), minlength=16)
    before = load(p)
    after = load(dict(p, router_bias=ref.balance_bias(y, p, cfg)))
    assert before.max() > 2.5 * before.mean() == 2.5 * 128
    assert after.max() <= 1.15 * 128 and after.min() >= 0.85 * 128
    drv = registry.load_module("drivers", "serve_closed_longdoc")
    _, cell, workload, config = toy_cell()
    run = make_run(workload, config, cell, seed=3)
    engine, leaves = drv.build_engine(run)
    assert sorted(leaves.biases) == [1, 2]
    for i, bias in leaves.biases.items():
        got = engine.steps.params[f"block_{i}"]["moe"]["router_bias"]
        assert got.dtype == jnp.float32 and np.array_equal(got, bias)
        assert np.abs(np.asarray(bias)).max() > 0.03     # moved off 0.02 N
    again = drv.balanced_biases(run, leaves.spec)
    assert all(np.array_equal(again[i], leaves.biases[i]) for i in again)


def test_one_slot_off_fails_the_worst_slot_and_not_the_quartile(monkeypatch):
    """A fault in ONE slot's page: every row captured from that slot is off,
    the quartile over all live rows still finds the other slot's clean rows,
    the worst slot's own quartile does not, and the run is not correct."""
    bench, cell, workload, config = toy_cell()
    # short prompts and long answers: several slots decode side by side
    workload["traffic"].update(
        prompt_len=dict(median=24, sigma=0.2, min=20, max=30),
        output_len=dict(min=60, max=100))
    workload["check"].update(live_dispatches=4, reference_out=104)
    drv = registry.load_module("drivers", workload["driver"])
    real = drv.live_captures

    def one_slot_off(run, loop):
        # where the window closes hangs on the machine's speed: go on, as
        # ``settle`` does, until two slots decode with answer left for every
        # capture
        ahead = lambda r: len(r.tokens) >= 2 and (
            r.max_new_tokens - len(r.tokens) > 16)
        for _ in range(200):
            if sum(map(ahead, loop.engine.active.values())) >= 2:
                break
            loop.iterate()
        caps = real(run, loop)
        first = caps[0][0]
        assert sum(r is first for r, _, _ in caps) >= 4
        assert any(r is not first for r, _, _ in caps)
        return [(r, n, np.roll(g, 1) if r is first else g)
                for r, n, g in caps]

    monkeypatch.setattr(drv, "live_captures", one_slot_off)
    checks = {c["name"]: c for c in drv.run(
        make_run(workload, config, cell, seed=13, seconds=1.5))["checks"]}
    assert checks["state_logit_rms"]["ok"], checks
    assert not checks["state_logit_rms_worst_slot"]["ok"], checks
    assert checks["state_logit_rms_worst_slot"]["value"] > 1.0
