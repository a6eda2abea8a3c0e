"""The hybrid cell (``ling3-flash-serve-reasongen``) at toy widths on the CPU:
its configuration file against the catalog's keys, the driver end to end
through ``run.measure`` untraced and with the recorded fixture as its trace,
the comparison broken underneath, the fp8 control failing the limits the
sound program passes, the seeded weight rules, the router bias run to rest
(``references/balance.py``), the counts and the new readers. No number a CPU
run gives is a device metric."""
import argparse
import copy
import functools
import json

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.harness import line as line_mod
from benchmark.harness import phases, registry, runtime, weights
from benchmark.references import balance
from benchmark.references import ling_hybrid as ref

from .test_drivers import fixture_for_trace  # noqa: F401  (a fixture)

CELL = "ling3-flash-serve-reasongen"
TOY_LIMITS = {"served_logit_gap": 0.02, "served_logit_gap_largest": 0.1,
              "state_logit_rms": 0.02, "state_logit_rms_largest": 0.05}


def toy_cell():
    bench = registry.load_benchmark()
    cell = registry.cell_entry(bench, CELL)
    workload = copy.deepcopy(registry.load_json("workloads", CELL))
    config = copy.deepcopy(registry.load_config(bench, cell["config"]))
    config["as_run"].update(
        vocab=512, d_model=64, n_heads=4, d_head=16, d_nope=16, d_rope=8,
        kv_rank=32, d_ff=128, n_experts=32, held_lo=0, held_hi=8,
        d_expert=32, d_shared=32, top_k=4, n_group=4, topk_group=2,
        n_layers=4, max_len=128, compute_dtype="float32",
        param_dtype="float32",
        pattern=[["kda", "dense"], ["kda", "moe"], ["mla", "moe"],
                 ["kda", "moe"]])
    workload["traffic"].update(
        clients=6, prompt_len=dict(median=20, sigma=0.8, min=8, max=64),
        output_len=dict(min=9, max=30), ramp_iterations=5)
    workload["engine"].update(n_slots=4, capacity=128, buckets=[32, 64, 128],
                              decode_k=4, prefill_cohort=2)
    workload["check"].update(reference_len=96, reference_out=32,
                             min_tokens=9, sample_requests=2, sample_live=2,
                             balance_tokens=512, balance_sequences=8,
                             limits=dict(TOY_LIMITS))
    # half of the 1 s window: on a loaded machine an iteration takes 0.1 s,
    # and a reader of spans needs one that lies whole inside the sub-window
    workload["trace"]["seconds"] = 0.5
    return bench, cell, workload, config


@pytest.fixture(autouse=True)
def cpu_has_no_memory_counter(monkeypatch):
    monkeypatch.setattr(runtime, "memory_peak_bytes", lambda devices: 1 << 20)


def measure(trace_flag=0, seed=2 ** 31 + 3):
    import jax

    bench, cell, workload, config = toy_cell()
    code, text = bench_run.measure(
        argparse.Namespace(seed=seed, seconds=1.0, trace=trace_flag), bench,
        cell, workload, config, jax.devices()[:1],
        registry.load_peaks("TPU v5 lite"))
    return code, (json.loads(text) if text else None), bench


def toy_run(seed):
    """(the cell's driver, an untraced toy run of it on ``seed``)."""
    import jax

    bench, cell, workload, config = toy_cell()
    drv = registry.load_module("drivers", workload["driver"])
    return drv, runtime.Run(
        t_process=0.0, args=argparse.Namespace(seed=seed, seconds=1.0,
                                               trace=0),
        cell=cell, workload=workload, config=config,
        peaks=registry.load_peaks("TPU v5 lite"), devices=jax.devices()[:1],
        scratch=str(registry.ROOT) + "/.bench_scratch")


def test_configuration_file_holds_the_catalogs_keys_and_states_the_cut():
    bench = registry.load_benchmark()
    entry = registry.config_entry(bench, "ling-3.0-flash-vl")
    data = registry.load_config(bench, "ling-3.0-flash-vl")
    pub, run = data["published"], data["as_run"]
    assert data["source"] == entry["source"]
    assert data["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size"]
    for key in ("published", "as_run", "departures", "assumed", "padded",
                "deployment"):
        assert key in data
    # every published key at the top level, changed only where reduced
    for key, value in pub.items():
        if key in data["reduced"]:
            assert data[key] != value
        else:
            assert data[key] == value, key
    # no width differs from the published one
    assert (run["d_model"], run["n_heads"], run["d_head"], run["d_ff"],
            run["d_expert"], run["d_shared"], run["top_k"], run["kv_rank"],
            run["d_nope"], run["d_rope"], run["n_experts"], run["n_group"],
            run["topk_group"], run["routed_scale"], run["rope_theta"],
            run["conv_kernel"], run["kda_lower_bound"]) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["head_dim"],
        pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["moe_shared_expert_intermediate_size"],
        pub["num_experts_per_tok"], pub["kv_lora_rank"],
        pub["qk_nope_head_dim"], pub["qk_rope_head_dim"], pub["num_experts"],
        pub["n_group"], pub["topk_group"], pub["routed_scaling_factor"],
        pub["rope_theta"], pub["short_conv_kernel_size"],
        pub["kda_lower_bound"])
    # the cut: the guide's floors and what the top-level keys say
    assert data["num_hidden_layers"] == run["n_layers"] == len(run["pattern"])
    assert run["held_hi"] - run["held_lo"] == data["num_experts"] == 128
    assert run["vocab"] == data["vocab_size"] >= pub["vocab_size"] // 8
    mixers = [m for m, _ in run["pattern"]]
    assert mixers.count("mla") == 1 and mixers.count("kda") == 6
    assert [f for _, f in run["pattern"]].count("dense") == 1
    lo, hi = run["published_layers"]
    assert run["pattern"] == [
        ["mla" if (i + 1) % pub["layer_group_size"] == 0 else "kda",
         "dense" if i < pub["first_k_dense_replace"] else "moe"]
        for i in range(lo, hi + 1)]


def test_cell_declares_the_serving_metrics_and_its_own():
    bench = registry.load_benchmark()
    e2e = {m["name"] for m in line_mod.declared(bench, CELL, 0)}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in line_mod.declared(bench, CELL, 1)}
    assert {"serve_hybrid_decode_roofline", "serve_grouped_swiglu_roofline",
            "serve_moe_experts_touched_pct", "serve_moe_load_max_over_mean",
            "serve_moe_pairs_held_pct", "serve_state_installed_mb",
            "serve_device_idle_pct", "serve_iter_host_ms",
            "serve_occupancy_pct", "serve_prefill_dispatch_ms",
            "serve_admit_host_ms", "serve_queue_age_s"} <= per_layer
    assert "serve_decode_roofline" not in per_layer
    old = {m["name"] for m in line_mod.declared(bench,
                                                "sc2-3b-serve-batchgen", 1)}
    assert not any("moe" in n or "hybrid" in n for n in old)


def test_cell_runs_end_to_end_untraced(capsys):
    code, line, bench = measure()
    err = capsys.readouterr().err.strip().splitlines()
    assert code == 0 and line["correct"] is True
    declared = line_mod.declared(bench, CELL, 0)
    line_mod.check(line, declared, False)
    assert set(line["metrics"]) == {m["name"] for m in declared}
    assert line["attempted"] > 0 and line["failed"] == 0
    # every number compared, beside its limit, under the line's last key
    assert list(line)[-1] == "compared"
    assert set(TOY_LIMITS) < set(line["compared"])
    for name, limit in TOY_LIMITS.items():
        assert line["compared"][name]["limit"] == limit
        assert 0 <= line["compared"][name]["value"] <= limit
    # and as the last lines on standard error
    last = err[-len(line["compared"]):]
    assert [l.split()[1].rstrip(":") for l in last] == list(line["compared"])
    assert all(l.startswith("compared ") and l.endswith(" ok") for l in last)


def test_cell_runs_end_to_end_with_the_fixture_as_its_trace(
        fixture_for_trace):  # noqa: F811
    """The fixture's device lines are another program's, so the two roofline
    readers find no module or kernel of this cell and return nothing (as on
    a program without them); the line is then refused for those two alone,
    and every counter read from the program's own spans is there."""
    import jax

    bench, cell, workload, config = toy_cell()
    declared = line_mod.declared(bench, CELL, 1)
    drv = registry.load_module("drivers", workload["driver"])
    run = runtime.Run(
        t_process=0.0, args=argparse.Namespace(seed=11, seconds=1.0, trace=1),
        cell=cell, workload=workload, config=config,
        peaks=registry.load_peaks("TPU v5 lite"), devices=jax.devices()[:1],
        scratch=str(registry.ROOT) + "/.bench_scratch")
    outcome = drv.run(run)
    assert all(c["ok"] for c in outcome["checks"]), outcome["checks"]
    values = bench_run.read_metrics(declared, outcome["facts"])
    missing = {k for k, v in values.items() if v is None}
    assert missing <= {"serve_hybrid_decode_roofline",
                       "serve_grouped_swiglu_roofline"}
    assert 0 < values["serve_moe_experts_touched_pct"] <= 100
    assert 0 < values["serve_moe_pairs_held_pct"] <= 100
    assert values["serve_moe_load_max_over_mean"] >= 1
    assert values["serve_state_installed_mb"] > 0
    table = phases.table_line(outcome["facts"]["program_rows"])
    for attr in ("experts_touched", "pairs_held", "pairs_routed",
                 "expert_load_max", "expert_load_mean", "state_bytes"):
        assert attr in table


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from chainermn_tpu.serving import engine as engine_mod

    real_emit = engine_mod.Engine._emit

    def emit(self, req, token):
        return real_emit(self, req, (int(token) + 7) % 512)

    monkeypatch.setattr(engine_mod.Engine, "_emit", emit)
    code, line, _ = measure()
    assert code == 0 and line["correct"] is False


def test_a_state_that_integrates_padding_is_not_correct(monkeypatch):
    """The fault the cell exists to catch: a bucketed prefill that lets the
    recurrence run over the padding. Tokens may survive it; the logits do
    not."""
    import jax.numpy as jnp

    from chainermn_tpu.serving import state_cache

    real = state_cache._apply

    def padded_lengths(dm, params, cache, tokens, lengths, live):
        if tokens.shape[1] > 1:     # prefill: every row as long as its bucket
            logits, cache, route = real(
                dm, params, cache, tokens,
                jnp.full_like(lengths, tokens.shape[1]), live)
            return logits, {**cache, "idx": jnp.where(
                live, lengths, cache["idx"])}, route
        return real(dm, params, cache, tokens, lengths, live)

    monkeypatch.setattr(state_cache, "_apply", padded_lengths)
    code, line, _ = measure()
    assert code == 0 and line["correct"] is False


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 9])
def test_control_in_fp8_fails_both_limits_the_program_passes(seed):
    drv, run = toy_run(seed)
    gaps = next(drv.calibrate(run, [seed], {seed}))
    print(gaps)
    assert gaps["served_gap"] <= TOY_LIMITS["served_logit_gap"] \
        < gaps["control_gap"], gaps
    assert gaps["state_rms"] <= TOY_LIMITS["state_logit_rms"] \
        < gaps["control_rms"], gaps
    assert gaps["control_rms"] > 3 * gaps["state_rms"]
    assert 0 <= gaps["near_tie_share"] < 0.5 and gaps["live_rows"] >= 1


def test_seeded_weights_follow_their_rules_and_one_layer_equals_the_tree():
    import jax.numpy as jnp

    drv = registry.load_module("drivers", "serve_closed_hybrid")
    _, _, _, config = toy_cell()
    cfg = config["as_run"]
    model, spec = drv.model_and_spec(cfg, jnp.float32)
    tree = drv.make_params(7, spec, cfg["n_layers"], jnp.float32)
    again = drv.make_block(7, spec, 3, cfg["n_layers"], jnp.float32)
    assert all(np.array_equal(a, b) for a, b in zip(
        *map(lambda t: [x for _, x in sorted(
            drv.weights.flatten(t).items())], (tree["block_3"], again))))
    other = drv.make_block(7, spec, 1, cfg["n_layers"], jnp.float32)
    assert not np.array_equal(other["moe"]["w_gate"], again["moe"]["w_gate"])
    moe, kda = tree["block_1"]["moe"], tree["block_1"]["kda"]
    # fan-in d (64), not E (8): the entries' spread is 64^-1/2
    assert np.std(np.asarray(moe["w_gate"])) == pytest.approx(0.125, rel=0.1)
    assert np.std(np.asarray(moe["w_down"])) == pytest.approx(
        32 ** -0.5, rel=0.1)
    assert 0.01 < np.std(np.asarray(moe["router_bias"])) < 0.03
    z = np.exp(np.asarray(kda["a_log"]))[:, None] * (
        np.random.RandomState(0).randn(4, 16)
        + np.asarray(kda["dt_bias"]).reshape(4, 16))
    alpha = np.exp(-5.0 / (1.0 + np.exp(-z)))
    assert 0.85 < alpha.min() and alpha.max() < 0.9999
    # the model takes the tree as it is
    model.apply({"params": tree}, np.zeros((1, 8), np.int32))


def test_every_leaf_follows_the_seed():
    """``--seed`` makes every weight (ISSUE 27), the blocks' too: nothing in
    the workload file keys a leaf."""
    import jax.numpy as jnp

    drv = registry.load_module("drivers", "serve_closed_hybrid")
    _, _, workload, config = toy_cell()
    assert "block_seed" not in workload["traffic"]
    cfg = config["as_run"]
    _, spec = drv.model_and_spec(cfg, jnp.float32)
    flat = lambda t: sorted(drv.weights.flatten(t).items())
    a, b, again = (drv.make_params(seed, spec, cfg["n_layers"], jnp.float32)
                   for seed in (7, 8, 7))
    for (path, x), (_, y), (_, z) in zip(flat(a), flat(b), flat(again)):
        assert np.array_equal(x, z) and not np.array_equal(x, y), path


def test_one_live_row_off_fails_the_largest_and_not_the_quartile(monkeypatch):
    """A fault that hits a few slots only: the quartile over the live rows
    does not see it, the largest row does, and the run is not correct."""
    import jax.numpy as jnp

    drv, run = toy_run(11)
    real = drv.after_window

    def one_row_off(run, engine, spec, win, **kw):
        slot, _ = max(engine.active.items(),
                      key=lambda sr: len(sr[1].tokens))   # always sampled
        logits = engine.steps.last_decode_logits
        engine.steps.last_decode_logits = logits.at[slot].set(
            jnp.roll(logits[slot], 1))
        return real(run, engine, spec, win, **kw)

    monkeypatch.setattr(drv, "after_window", one_row_off)
    checks = {c["name"]: c for c in drv.run(run)["checks"]}
    assert checks["state_logit_rms"]["ok"], checks
    assert not checks["state_logit_rms_largest"]["ok"], checks
    assert checks["state_logit_rms_largest"]["value"] > 1.0


def test_counts_match_the_issues_arithmetic():
    bench = registry.load_benchmark()
    cfg = registry.load_config(bench, "ling-3.0-flash-vl")["as_run"]
    c = registry.load_module("counts", "hybrid_decode")
    assert c.kda_mixer_params(cfg) == pytest.approx(63.0e6, rel=2e-3)
    assert c.mla_mixer_params(cfg) == pytest.approx(31.9e6, rel=3e-3)
    assert 128 * c.expert_params(cfg) == pytest.approx(755.0e6, rel=1e-4)
    assert c.expert_bytes(cfg) == pytest.approx(11.8e6, rel=2e-3)
    assert 128 * c.state_bytes_per_slot(cfg) == pytest.approx(1.667e9,
                                                              rel=1e-3)
    assert 128 * 2048 * c.latent_bytes_per_token(cfg) == pytest.approx(
        0.302e9, rel=1e-3)
    total = (c.non_expert_weight_bytes(cfg) + 6 * 128 * c.expert_bytes(cfg)
             + 2 * cfg["vocab"] * cfg["d_model"])      # + the embedding
    assert total == pytest.approx(10.46e9, rel=2e-3)
    step = c.decode_step_bytes(cfg, 6 * 111, 128, 128 * 500)
    assert step == pytest.approx(1.2e9 + 7.86e9 + 3.33e9 + 0.074e9, rel=0.01)
    flops, bytes_ = c.grouped_swiglu(256, 111, cfg)
    assert c.least_seconds(flops, bytes_, registry.load_peaks(
        "TPU v5 lite"))[1] == "memory"


def test_readers_return_nothing_on_a_program_without_the_counters():
    """The parent of this PR has the spans but not the attributes; a program
    older still has no spans. Neither raises."""
    bench, _, workload, config = toy_cell()

    class NoSpans:
        def named(self, name):
            return []

    for rows in (None, []):
        facts = {"kind": "serve", "trace": None, "trace_span": None,
                 "program_rows": rows, "workload": workload,
                 "config": config, "filled": [], "spans": NoSpans(),
                 "peaks": registry.load_peaks("TPU v5 lite")}
        for name in ("serve_hybrid_decode_roofline",
                     "serve_grouped_swiglu_roofline",
                     "serve_moe_experts_touched_pct",
                     "serve_moe_load_max_over_mean",
                     "serve_moe_pairs_held_pct", "serve_state_installed_mb"):
            reader = registry.load_module("metrics", name)
            try:
                got = reader.read(facts)
            except LookupError:
                got = None      # phases.iterations: no engine.step at all
            assert got is None, name


def test_reference_route_margin_and_fake_fp8():
    import jax.numpy as jnp

    x = jnp.asarray([1.0, 1.03, 1.07, 3.3, -0.3, 1e-3, 0.0])
    assert np.asarray(ref.fake_fp8(x)).tolist() == pytest.approx(
        [1.0, 1.0, 1.125, 3.25, -0.3125, 0.0009765625, 0.0])
    rs = np.random.RandomState(0)
    y = jnp.asarray(rs.randn(50, 16), jnp.float32)
    p = {"router": jnp.asarray(rs.randn(16, 32), jnp.float32),
         "router_bias": jnp.zeros((32,), jnp.float32)}
    cfg = dict(n_group=4, topk_group=2, top_k=4, routed_scale=1.0)
    margin = np.asarray(ref.route_margin(y, p, cfg))
    assert margin.shape == (50,) and (margin >= 0).all()
    chosen, w, scores = ref.route(y, p, cfg)
    scores, chosen = np.asarray(scores), np.asarray(chosen)
    for t in range(50):
        runner_up = scores[t, chosen[t]].min() - margin[t]
        rest = np.delete(scores[t], chosen[t])
        assert np.isclose(rest, runner_up, atol=1e-6).any()
        assert runner_up <= scores[t, chosen[t]].min()


# -- the router bias at rest (PR 43) -------------------------------------------
BALANCE_SEEDS = [5, 6, 7, 2 ** 31 + 9]


@functools.lru_cache(maxsize=None)
def toy_leaves(seed):
    """(driver, run, model, leaves) of a seed, balanced once a process."""
    drv, run = toy_run(seed)
    return (drv, run) + drv.seeded_leaves(run)


def probe_routing(drv, run, leaves, biases):
    """{expert layer: (chosen [T, k], biased scores [T, E])} of the
    REFERENCE on the cell's probe, the layers' router biases ``biases[i]``
    (the rule's 0.02 N noise where a layer has none)."""
    import jax
    import jax.numpy as jnp

    cfg, chk = run.config["as_run"], run.workload["check"]
    rcfg, n = drv.ref_cfg(cfg), cfg["n_layers"]
    seed = weights.seed_word(run.seed)
    toks = balance.probe_tokens(seed, cfg["vocab"], chk["balance_tokens"],
                                chk["balance_sequences"])
    rest = ref.canonical_rest(drv.make_rest(run.seed, leaves.spec, n,
                                            jnp.float32))
    x, out = ref.embed(jnp.asarray(toks), rest), {}
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(tuple(k) for k in cfg["pattern"]):
            p = ref.canonical_layer(drv.layer_maker(
                leaves.spec, i, n, jnp.float32)(seed, i, biases.get(i)))
            if kind[1] == "moe":
                y = ref.ffn_input(x, p, kind, rcfg)
                chosen, _, scores = ref.route(y.reshape(-1, y.shape[-1]), p,
                                              rcfg)
                out[i] = (np.asarray(chosen),
                          np.asarray(scores + p["router_bias"]))
            x = ref.block(x, p, kind, rcfg)
    return out


def loads(routing, n_experts):
    return {i: np.bincount(chosen.ravel(), minlength=n_experts)
            for i, (chosen, _) in routing.items()}


def test_program_and_reference_are_handed_the_same_bias_arrays():
    import jax.numpy as jnp

    drv, run, model, leaves = toy_leaves(5)
    cfg = run.config["as_run"]
    moe = [i for i, (_, f) in enumerate(cfg["pattern"]) if f == "moe"]
    assert sorted(leaves.biases) == moe
    engine = drv.build_engine(run, model, leaves)
    noise = drv.make_params(5, leaves.spec, cfg["n_layers"], jnp.float32)
    for i in moe:
        bias = leaves.biases[i]
        assert bias.dtype == jnp.float32 and bias.shape == (cfg["n_experts"],)
        served = engine.steps.params[f"block_{i}"]["moe"]["router_bias"]
        assert served.dtype == jnp.float32
        assert np.array_equal(np.asarray(served), np.asarray(bias))
        # what ``reference_gaps`` builds its layer from
        blk = drv.layer_maker(leaves.spec, i, cfg["n_layers"], jnp.float32)(
            weights.seed_word(5), i, bias)
        assert blk["moe"]["router_bias"] is bias
        assert not np.array_equal(
            np.asarray(bias), np.asarray(noise[f"block_{i}"]["moe"]
                                         ["router_bias"]))
    # a dense layer has no such leaf and takes none
    dense = drv.layer_maker(leaves.spec, 0, cfg["n_layers"], jnp.float32)(
        weights.seed_word(5), 0, leaves.biases[moe[0]])
    assert "moe" not in dense


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 9])
def test_balanced_load_is_even_on_the_probe_where_the_noise_bias_is_not(seed):
    drv, run, _, leaves = toy_leaves(seed)
    e = run.config["as_run"]["n_experts"]
    over = lambda load: load.max() / load.mean()
    rest = {i: over(l) for i, l in loads(
        probe_routing(drv, run, leaves, leaves.biases), e).items()}
    noise = {i: over(l) for i, l in loads(
        probe_routing(drv, run, leaves, {}), e).items()}
    assert all(v < 1.2 for v in rest.values()), rest       # the issue: < 2
    assert all(noise[i] > 1.5 * rest[i] for i in rest), (rest, noise)
    assert max(noise.values()) > 2.0, noise


@pytest.mark.parametrize("seed", BALANCE_SEEDS)
def test_share_of_pairs_on_the_held_experts_is_held_over_all(seed):
    drv, run, _, leaves = toy_leaves(seed)
    cfg = run.config["as_run"]
    want = (cfg["held_hi"] - cfg["held_lo"]) / cfg["n_experts"]
    for i, load in loads(probe_routing(drv, run, leaves, leaves.biases),
                         cfg["n_experts"]).items():
        held = load[cfg["held_lo"]:cfg["held_hi"]].sum() / load.sum()
        assert abs(held - want) < 0.01, (i, held)


def test_balanced_choice_stays_inside_the_kept_groups():
    drv, run, _, leaves = toy_leaves(5)
    cfg = run.config["as_run"]
    ng, keep, e = cfg["n_group"], cfg["topk_group"], cfg["n_experts"]
    for i, (chosen, biased) in probe_routing(drv, run, leaves,
                                             leaves.biases).items():
        t = biased.shape[0]
        two_best = np.sort(biased.reshape(t, ng, e // ng), -1)[..., -2:]
        kept = np.argsort(-two_best.sum(-1), -1, kind="stable")[:, :keep]
        groups = chosen // (e // ng)
        assert (groups[:, :, None] == kept[:, None, :]).any(-1).all(), i
        assert chosen.shape == (t, cfg["top_k"])
        assert all(len(set(row)) == cfg["top_k"] for row in chosen)


@pytest.mark.parametrize("module", ["xing_mhc", "deepseek_mtp",
                                    "laguna_mixed"])
def test_the_one_rule_equals_each_older_copy_bit_for_bit(module):
    """The three later cells' references carry copies of ``balance_bias``
    written against their own routers (PR 43 left them: their files stay as
    they are). On the same tokens the one rule, handed such a module, comes
    to rest at the same bits."""
    import jax
    import jax.numpy as jnp

    old = registry.load_module("references", module)
    rs = np.random.RandomState(3)
    y = jnp.asarray(rs.randn(96, 16) + 0.5, jnp.float32)
    p = {"router": jnp.asarray(rs.randn(16, 32), jnp.float32),
         "router_bias": jnp.asarray(0.02 * rs.randn(32), jnp.float32)}
    cfg = dict(n_group=4, topk_group=2, top_k=4, routed_scale=2.5)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(
            lambda y, p: old.balance_bias(y, p, cfg))(y, p))
        got = np.asarray(jax.jit(
            lambda y, p: balance.balance_bias(old, y, p, cfg))(y, p))
    assert np.array_equal(got, want)
    assert not np.array_equal(got, np.asarray(p["router_bias"]))
