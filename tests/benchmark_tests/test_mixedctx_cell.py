"""The mixed-context cell (``laguna-xs2-serve-mixedctx``) at toy widths on the
CPU: its configuration file against the catalog's row key by key, the
parameter and byte counts of the cut recomputed, the round of 20 sizes the
real workload file gives, what the cell declares, the driver end to end
through ``run.measure`` untraced and with the recorded fixture as its trace,
the comparison broken underneath (a ring written a column off, a window layer
that attends the whole cache), both controls failing the limits the sound
program passes, the counts' arithmetic and each new reader on made-up facts.
No number a CPU run gives is a device metric."""
import argparse
import copy
import json

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.harness import line as line_mod
from benchmark.harness import phases, registry, runtime

from .test_drivers import fixture_for_trace  # noqa: F401  (a fixture)

CELL = "laguna-xs2-serve-mixedctx"
CONFIG = "laguna-xs.2"
DRIVER = "serve_closed_mixedctx"
NEW = ("serve_gqa_prefill_roofline", "serve_gqa_decode_roofline",
       "serve_window_wrapped_pct", "serve_page_columns_read_pct")
TOY_LIMITS = {"served_logit_gap": 0.02, "state_logit_rms": 0.02,
              "state_logit_rms_worst_slot": 0.05}
PEAKS = registry.load_peaks("TPU v5 lite")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(autouse=True)
def chunk_blocks_of_a_toy_window(monkeypatch):
    """A chunk call walks the toy page in blocks of 16 columns (512 in the
    module): several blocks a page, as at the cell's sizes."""
    from chainermn_tpu.ops import kv_attention

    monkeypatch.setattr(kv_attention, "CHUNK_BLOCK", 16)


def toy_cell():
    bench = registry.load_benchmark()
    cell = registry.cell_entry(bench, CELL)
    workload = copy.deepcopy(registry.load_json("workloads", CELL))
    config = copy.deepcopy(registry.load_config(bench, cell["config"]))
    config["as_run"].update(
        vocab=512, d_model=64, n_heads=6, d_head=16, n_kv_heads=2,
        gqa_heads=6, swa_heads=8, window=16, d_ff=128,
        n_experts=16, held_lo=0, held_hi=16, d_expert=32, d_shared=32,
        top_k=4, max_len=192, compute_dtype="float32",
        param_dtype="float32")
    config["as_run"]["gqa_scaling"]["original_max_position_embeddings"] = 32
    workload["traffic"].update(
        clients=5, ramp_iterations=12, output_len=dict(min=20, max=44),
        prompt_len=dict(
            long=dict(median=60, sigma=0.4, min=30, max=120),
            short=dict(median=8, sigma=0.3, min=5, max=12), short_every=4))
    workload["engine"].update(n_slots=4, capacity=192, buckets=[192],
                              prefill_chunk=48, decode_k=4)
    workload["check"].update(reference_lens=[64, 192], reference_out=48,
                             q_block=16, min_tokens=6, sample_requests=3,
                             sample_live=3, balance_tokens=64,
                             live_dispatches=3, limits=dict(TOY_LIMITS))
    workload["trace"]["seconds"] = 0.2
    return bench, cell, workload, config


@pytest.fixture(autouse=True)
def cpu_has_no_memory_counter(monkeypatch):
    monkeypatch.setattr(runtime, "memory_peak_bytes", lambda devices: 1 << 20)


def make_run(workload, config, cell, seed=11, trace=0, seconds=1.5):
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
        argparse.Namespace(seed=seed, seconds=1.5, trace=trace_flag), bench,
        cell, workload, config, jax.devices()[:1], PEAKS)
    return code, (json.loads(text) if text else None), bench


def catalog_row():
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Laguna-XS.2":
                return row
    raise LookupError("the catalog has no row Laguna-XS.2")


def test_configuration_file_holds_the_catalogs_row_and_states_the_cut():
    bench = registry.load_benchmark()
    entry = registry.config_entry(bench, CONFIG)
    data = registry.load_config(bench, CONFIG)
    row = catalog_row()
    pub, run = data["published"], data["as_run"]
    assert pub == row["config"]
    assert data["source"] == entry["source"] == row["source_url"]
    assert data["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert len(entry["why"]) <= 200 and "256 experts" in entry["why"]
    for key in ("published", "as_run", "departures", "assumed", "padded",
                "deployment", "equations"):
        assert key in data
    for key, value in pub.items():          # changed only where reduced
        if key in data["reduced"]:
            assert data[key] != value
        else:
            assert data[key] == value, key
    assert data["num_hidden_layers"] == run["n_layers"] == 5
    # no width, count of experts, vocabulary row, window or rotary rule
    # differs from the published one
    rope = pub["rope_parameters"]
    full, slide = rope["full_attention"], rope["sliding_attention"]
    assert (run["d_model"], run["d_head"], run["n_kv_heads"], run["d_ff"],
            run["d_expert"], run["d_shared"], run["top_k"], run["n_experts"],
            run["routed_scale"], run["vocab"], run["window"],
            run["norm_eps"], run["attn_gate"]) == (
        pub["hidden_size"], pub["head_dim"], pub["num_key_value_heads"],
        pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["shared_expert_intermediate_size"], pub["num_experts_per_tok"],
        pub["num_experts"], pub["moe_routed_scaling_factor"],
        pub["vocab_size"], pub["sliding_window"], pub["rms_norm_eps"],
        pub["gating"])
    assert (run["gqa_theta"], run["gqa_rotary"], run["swa_theta"],
            run["swa_rotary"], run["swa_scaling"]) == (
        full["rope_theta"], full["partial_rotary_factor"],
        slide["rope_theta"], slide["partial_rotary_factor"], None)
    assert slide["rope_type"] == "default"
    for key, value in run["gqa_scaling"].items():
        assert full[key] == value, key
    # the pattern is the first five published layers' and the head counts
    # theirs; every expert is held
    kinds = {"full_attention": "gqa", "sliding_attention": "swa"}
    feeds = {"dense": "dense", "sparse": "moe"}
    assert run["pattern"] == [
        [kinds[a], feeds[f]] for a, f in zip(pub["layer_types"][:5],
                                             pub["mlp_layer_types"][:5])]
    heads = {"gqa": run["gqa_heads"], "swa": run["swa_heads"]}
    assert [heads[m] for m, _ in run["pattern"]] == (
        pub["num_attention_heads_per_layer"][:5]) == [48, 64, 64, 64, 48]
    assert (run["held_lo"], run["held_hi"]) == (0, 256)
    assert set(data["assumed"]) >= {"gating", "routing",
                                    "no_qk_norm_and_rotary_layout"}
    wl = registry.load_json("workloads", CELL)
    assert run["max_len"] == wl["engine"]["capacity"] == 32768
    assert "larger than a 40-layer" in " ".join(data["departures"])


def test_parameter_and_byte_counts_of_the_cut():
    """ISSUE 39 section 2, recomputed twice: by the count functions from the
    sizes, and from the shapes the model itself declares."""
    import jax

    bench = registry.load_benchmark()
    cfg = registry.load_config(bench, CONFIG)["as_run"]
    d = registry.load_module("counts", "gqa_decode")
    drv = registry.load_module("drivers", DRIVER)
    m = 1e6
    assert d.layer_params(cfg, "gqa", "dense") == pytest.approx(79.8 * m,
                                                                rel=1e-3)
    assert d.layer_params(cfg, "swa", "moe") == pytest.approx(846.9 * m,
                                                              rel=1e-3)
    assert d.layer_params(cfg, "gqa", "moe") == pytest.approx(838.4 * m,
                                                              rel=1e-3)
    assert 256 * d.expert_params(cfg) == pytest.approx(805.3 * m, rel=1e-3)
    assert d.attention_params(cfg, 64) == pytest.approx(37.9 * m, rel=2e-3)
    assert d.model_params(cfg) == pytest.approx(3870 * m, rel=1e-3)
    assert 2 * d.model_params(cfg) == pytest.approx(7.74e9, rel=1e-3)
    model, spec = drv.model_and_spec(cfg, np.dtype("float32"))
    assert sum(int(np.prod(s)) for s in spec.values()) == d.model_params(cfg)
    # a token costs 4 KB a layer; a slot 2 pages and 3 rings
    assert d.column_bytes(cfg) == 4096
    assert d.slot_bytes(cfg, 32768) == pytest.approx(274.7e6, rel=1e-3)
    assert 20 * d.slot_bytes(cfg, 32768) == pytest.approx(5.49e9, rel=1e-3)
    assert 2 * d.model_params(cfg) + 20 * d.slot_bytes(cfg, 32768) == (
        pytest.approx(13.23e9, rel=1e-3))
    # what the cache manager declares is that, and a capacity-long page on
    # every layer would not fit beside the weights
    from chainermn_tpu.serving.state_cache import init_state_cache
    cache = jax.eval_shape(lambda: init_state_cache(
        model.clone(dtype=np.dtype("bfloat16")), 20, 32768))
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(cache))
    assert held == 20 * d.slot_bytes(cfg, 32768) + 20 * 4
    assert 20 * 5 * 32768 * 4096 == pytest.approx(13.4e9, rel=2e-3)


def test_cell_declares_the_serving_metrics_and_its_own():
    bench = registry.load_benchmark()
    cell = registry.cell_entry(bench, CELL)
    assert cell == {
        "name": CELL, "config": CONFIG, "traffic": "serve-mixedctx",
        "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "512-column rings" in cell["why"]
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
        "serve_moe_pairs_held_pct", "setup_programs_lowered",
        "setup_cache_misses", "setup_trace_lower_s",
        "setup_backend_compile_s", "setup_first_run_s",
        "setup_build_s"} <= set(per_layer)
    for name in NEW:
        m = per_layer[name]
        assert CELL in m["workloads"] and m["moves"] == "serve_tokens_per_s"
        reader = registry.load_module("metrics", name)
        assert (reader.UNIT, reader.SOURCE, reader.LAYER, reader.MOVES) == (
            m["unit"], m["source"], m["layer"], m["moves"])
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    assert layers["serve_gqa_prefill_roofline"] == layers[
        "serve_latent_prefill_roofline"] == "kernels"
    assert layers["serve_gqa_decode_roofline"] == layers[
        "serve_latent_decode_roofline"]
    assert layers["serve_window_wrapped_pct"] == layers[
        "serve_page_columns_read_pct"] == layers["serve_state_installed_mb"]
    # the other cells report nothing of this one's
    for other in ("sc2-3b-serve-batchgen", "ling3-flash-serve-reasongen",
                  "xing4-serve-longdoc", "dsv3-serve-mtp-reasongen"):
        names = {m["name"] for m in line_mod.declared(bench, other, 1)}
        assert not names & set(NEW)
    # found by name, not by place: the driver takes new entries at the END of
    # a list only, so a test that pins a list's tail fails with the next PR
    # that appends (and, standing under the benchmark's paths, cannot be
    # edited by it)
    assert CONFIG in {c["name"] for c in bench["configs"]}
    assert CELL in {w["name"] for w in bench["workloads"]}
    assert [m["name"] for m in bench["per_layer"]
            if m["name"] in NEW] == list(NEW)


def test_the_round_of_twenty_sizes_is_the_issues_traffic():
    wl = registry.load_json("workloads", CELL)
    tr, eng = wl["traffic"], wl["engine"]
    drv = registry.load_module("drivers", DRIVER)
    sizes = drv.round_of_sizes(tr)
    assert len(sizes) == tr["clients"] == eng["n_slots"] == 20
    kinds = ["short" if p < 512 else "long" for p, _, _ in sizes]
    # 3 of 4 long, interleaved in the round's order and not grouped
    assert kinds == (["long"] * 3 + ["short"]) * 5
    long_ = sorted(p for p, _, _ in sizes if p >= 512)
    short = sorted(p for p, _, _ in sizes if p < 512)
    assert len(long_) == 15 and len(short) == 5
    assert 4096 <= long_[0] < 5500 and 28000 < long_[-1] <= 30720
    assert long_[7] == 12288                           # the median
    assert 64 <= short[0] < 160 and 400 < short[-1] <= 480
    assert short[2] == 256
    assert tr["prompt_len"]["long"] == {
        "distribution": "lognormal", "median": 12288, "sigma": 0.5,
        "min": 4096, "max": 30720}
    assert tr["prompt_len"]["short"] == {
        "distribution": "lognormal", "median": 256, "sigma": 0.5,
        "min": 64, "max": 480}
    outs = sorted(o for _, o, _ in sizes)
    assert outs[0] == 256 and outs[-1] == 1024 and len(set(outs)) == 20
    # every second request of the round greedy, the odd places: 10 of 20,
    # the five short ones among them, so that both kinds are compared
    assert [g for _, _, g in sizes] == [False, True] * 10
    assert sum(g for p, _, g in sizes if p >= 512) == 5
    assert sum(g for p, _, g in sizes if p < 512) == 5
    assert (tr["temperature"], tr["top_k"], tr["greedy_every"]) == (0.8, 50,
                                                                    2)
    assert all(p + o <= eng["capacity"] for p, o, _ in sizes)
    assert sizes == drv.round_of_sizes(tr)             # sizes_seed, no --seed
    # a short prompt has not wrapped its ring when it starts to decode and
    # the longest answers wrap it; a long prompt is 2 to 15 chunks
    assert max(short) < 512 < min(short) + 1024
    chunks = [-(-p // eng["prefill_chunk"]) for p in long_]
    assert min(chunks) >= 2 and max(chunks) <= 15
    assert sum(chunks) + len(short) == 113              # chunks a round
    assert (eng["n_slots"], eng["capacity"], eng["prefill_chunk"],
            eng["prefill_cohort"], eng["decode_k"], eng["token_budget"],
            eng["cache_dtype"]) == (20, 32768, 2048, 1, 8, None, "bfloat16")
    assert wl["trace"]["modules"]["prefill"] == "jit__pc"


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
    no module or scope of this cell and return nothing; every counter read
    from the program's own spans is there."""
    bench, cell, workload, config = toy_cell()
    workload["trace"]["seconds"] = 1.0
    declared = line_mod.declared(bench, CELL, 1)
    drv = registry.load_module("drivers", DRIVER)
    outcome = drv.run(make_run(workload, config, cell, trace=1, seconds=2.5))
    assert all(c["ok"] for c in outcome["checks"]), outcome["checks"]
    checks = {c["name"]: c for c in outcome["checks"]}
    assert checks["chunk_programs"]["value"] == [[[1, 48], 1]]
    assert checks["decode_k_traces"]["value"] == 1
    assert checks["wrapped_short_requests_compared"]["value"] >= 1
    assert outcome["failed"] == 0 and outcome["facts"]["scopes_s"] is None
    values = bench_run.read_metrics(declared, outcome["facts"])
    missing = {k for k, v in values.items() if v is None}
    assert missing <= {"serve_gqa_prefill_roofline",
                       "serve_gqa_decode_roofline",
                       "serve_grouped_swiglu_roofline",
                       "serve_prefill_dispatch_ms", "serve_iter_host_ms"}
    assert 0 < values["serve_window_wrapped_pct"] <= 100
    # the toy page is one decode block: a live row reads all 192 columns
    assert values["serve_page_columns_read_pct"] > 100
    assert values["serve_moe_pairs_held_pct"] == pytest.approx(100.0)
    table = phases.table_line(outcome["facts"]["program_rows"])
    for attr in ("start_tokens", "prompt_tokens", "filled_columns",
                 "attn_rows_live", "attn_rows_wrapped", "attn_page_columns",
                 "attn_ring_columns", "attn_fill_columns"):
        assert attr in table


def test_a_ring_written_a_column_off_is_not_correct(monkeypatch):
    """The fault the cell exists to catch: a chunk whose keys land one ring
    column from where the later queries look."""
    from chainermn_tpu.ops import kv_attention

    real = kv_attention.write_ring

    def shifted(ring, chunk, pos, n, slots=None):
        return real(ring, chunk, pos + (pos > 0), n, slots)

    monkeypatch.setattr(kv_attention, "write_ring", shifted)
    code, line, _ = measure()
    assert code == 0 and line["correct"] is False


def test_a_window_layer_that_attends_the_whole_ring_history_is_not_correct(
        monkeypatch):
    """A decode step that reads the ring past the window: columns that hold
    no position yet are not masked (the ring's zeros join the softmax)."""
    import jax.numpy as jnp
    from chainermn_tpu.ops import kv_attention

    monkeypatch.setattr(kv_attention, "ring_positions",
                        lambda pos, window: jnp.zeros(
                            (pos.shape[0], window), jnp.int32))
    code, line, _ = measure()
    assert code == 0 and line["correct"] is False


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 9])
def test_both_controls_fail_limits_the_program_passes(seed):
    bench, cell, workload, config = toy_cell()
    drv = registry.load_module("drivers", DRIVER)
    run = make_run(workload, config, cell, seed=seed)
    gaps = next(drv.calibrate(run, [seed], {seed}))
    print(gaps)
    assert gaps["served_gap"] <= TOY_LIMITS["served_logit_gap"] \
        < gaps["control_gap"], gaps
    assert gaps["state_rms"] <= TOY_LIMITS["state_logit_rms"] \
        < gaps["control_rms"], gaps
    assert gaps["control_rms"] > 3 * gaps["state_rms"]
    # the reference without the window is another model past the window
    assert gaps["no_window_rms"] > TOY_LIMITS["state_logit_rms"]
    assert gaps["no_window_gap"] > TOY_LIMITS["served_logit_gap"]
    assert gaps["live_rows"] >= 1 and gaps["wrapped_short"] >= 1
    # the control in one slot's place alone, a reading for each live slot
    assert len(gaps["control_one_slot"]) == len(
        gaps["control_rms_by_request"]) == len(gaps["state_rms_by_request"])
    assert all(slot >= gaps["state_rms_slot"]
               for _, slot in gaps["control_one_slot"]), gaps


def test_counts_on_a_hand_worked_example():
    bench = registry.load_benchmark()
    cfg = registry.load_config(bench, CONFIG)["as_run"]
    p = registry.load_module("counts", "gqa_prefill")
    d = registry.load_module("counts", "gqa_decode")
    # a chunk of 4 queries at cursor 2 with a window of 3: the queries see
    # 3, 3, 3, 3 keys in the window and 3, 4, 5, 6 on a full layer
    assert p.chunk_pairs(2, 4) == 3 + 4 + 5 + 6
    assert p.window_pairs(2, 4, 3) == 12
    # at cursor 0 the first two queries are still inside the first window
    assert p.window_pairs(0, 4, 3) == 1 + 2 + 3 + 3
    assert p.window_pairs(0, 2, 8) == p.chunk_pairs(0, 2) == 3
    assert p.window_pairs(4096, 2048, 512) == 2048 * 512
    assert p.window_pairs(0, 2048, 512) == 2048 * 512 - 512 * 511 // 2
    assert p.layer_heads(cfg) == (2 * 48, 3 * 64)
    # one 2,048-token chunk at a fill of 6,144: 0.6 TFLOP on the two full
    # layers, 0.1 on the three window layers (0.9 if they were full)
    full = 4 * 128 * 96 * p.chunk_pairs(6144, 2048)
    ring = 4 * 128 * 192 * 2048 * 512
    assert p.attention_flops([(6144, 2048)], cfg) == full + ring
    assert full == pytest.approx(0.72e12, rel=0.02)
    assert ring == pytest.approx(0.103e12, rel=0.01)
    assert 4 * 128 * 192 * p.chunk_pairs(6144, 2048) == pytest.approx(
        1.44e12, rel=0.01)
    # a decode step: 17 live rows at a fill of 12,500, all wrapped, 105 of
    # 256 experts touched a layer
    step = d.decode_step_bytes(cfg, 4 * 105, 17 * 12500, 17 * 512, 17)
    assert step == d.non_expert_weight_bytes(cfg) + 420 * 2 * 3145728 + (
        4096 * (2 * 17 * 12500 + 3 * 17 * 512 + 5 * 17))
    assert d.non_expert_weight_bytes(cfg) == pytest.approx(0.886e9, rel=0.01)
    assert 420 * 2 * d.expert_params(cfg) == pytest.approx(2.64e9, rel=0.01)
    assert 4096 * 2 * 17 * 12500 == pytest.approx(1.74e9, rel=0.01)
    assert 4096 * 3 * 17 * 512 == pytest.approx(0.107e9, rel=0.01)


def made_up_facts():
    """One traced sub-window of two iterations: a chunk of 2,048 queries at
    cursor 4,096 each, a decode dispatch of 8 steps over 3 live rows, 2 of
    them wrapped, at fills of 20,000, 9,000 and 300."""
    from chainermn_tpu.tracing import Row

    bench = registry.load_benchmark()
    workload = registry.load_json("workloads", CELL)
    config = registry.load_config(bench, CONFIG)
    fill = 20000 + 9000 + 300
    rows, rid = [], 0
    for it in range(2):
        t = float(it)
        step = Row(rid + 1, None, "engine.step", t, t + 0.9, {})
        rows += [step,
                 Row(rid + 2, step.id, "engine.admit", t, t + 0.1, dict(
                     admitted=0, rows=1, prompt_tokens=2048, padded_tokens=0,
                     start_tokens=4096)),
                 Row(rid + 3, step.id, "engine.decode.enqueue", t + 0.5,
                     t + 0.6, dict(
                         live=3, filled_columns=fill, experts_touched=8 * 4 * 20,
                         attn_rows_live=8 * 3, attn_rows_wrapped=8 * 2,
                         attn_fill_columns=8 * fill,
                         attn_page_columns=8 * 2 * (10 + 5 + 1) * 2048,
                         attn_ring_columns=8 * 3 * 20 * 512))]
        rid += 3
    p = registry.load_module("counts", "gqa_prefill")
    least_s = p.attention_flops([(4096, 2048)] * 2, config["as_run"]) / (
        PEAKS["bf16_flops_per_s"])
    facts = {"kind": "serve", "program_rows": rows, "peaks": PEAKS,
             "workload": workload, "config": config,
             "scopes_s": {"gqa_chunk": 3 * least_s, "swa_chunk": least_s,
                          "gqa_decode": 0.05, "moe_experts": 0.3,
                          "(none)": 0.5},
             "trace": {"busy_s": 1.2, "window_s": 2.0,
                       "module_runs_s": {"jit__decode_k": [0.16, 0.2, 0.24]},
                       "op_family_s": {}, "op_family_calls": {}}}
    return facts


def test_each_new_reader_on_made_up_facts():
    facts = made_up_facts()
    read = lambda name: registry.load_module("metrics", name).read(facts)
    assert read("serve_gqa_prefill_roofline") == pytest.approx(25.0)
    assert read("serve_window_wrapped_pct") == pytest.approx(100 * 2 / 3)
    fill = 29300
    assert read("serve_page_columns_read_pct") == pytest.approx(
        100 * (2 * 16 * 2048 + 3 * 20 * 512) / (5 * fill))
    d = registry.load_module("counts", "gqa_decode")
    cfg = facts["config"]["as_run"]
    least = d.decode_step_bytes(cfg, 80, fill, 2 * 512, 3) / (
        PEAKS["hbm_bytes_per_s"])
    assert read("serve_gqa_decode_roofline") == pytest.approx(
        100 * least / (0.2 / 8))
    assert 0 < read("serve_gqa_decode_roofline") < 100


def test_readers_return_nothing_on_a_program_without_the_counters():
    """A program with the spans but without this PR's attributes or scopes
    (the parent), and one with no spans at all. Neither raises."""
    facts = made_up_facts()

    class NoSpans:
        def named(self, name):
            return []

    bare = [r._replace(attrs={k: v for k, v in r.attrs.items()
                              if not k.startswith("attn_")})
            for r in facts["program_rows"]]
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


def test_balanced_bias_evens_the_load_and_both_sides_get_the_same_arrays():
    import jax

    from benchmark.references import laguna_mixed as ref

    bench, cell, workload, config = toy_cell()
    drv = registry.load_module("drivers", DRIVER)
    run = make_run(workload, config, cell, seed=3)
    engine, leaves = drv.build_engine(run)
    assert sorted(leaves.biases) == [1, 2, 3, 4]
    for i, bias in leaves.biases.items():
        got = engine.steps.params[f"block_{i}"]["moe"]["router_bias"]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(bias))
    # one layer made alone equals the same layer of the whole tree
    cfg = config["as_run"]
    make = drv.block_maker(run, leaves, ("swa", "moe"), 2)
    from benchmark.harness import weights
    blk = jax.jit(make)(weights.seed_word(run.seed), 2, leaves.biases[2])
    flat = jax.tree_util.tree_leaves_with_path(blk)
    whole = engine.steps.params["block_2"]
    for path, leaf in flat:
        node = whole
        for k in path:
            node = node[k.key]
        np.testing.assert_allclose(np.asarray(leaf), np.asarray(node),
                                   rtol=1e-6)
    assert set(ref.canonical_layer(blk)) >= {"wq", "wg", "router_bias"}
    assert cfg["held_hi"] - cfg["held_lo"] == cfg["n_experts"]
