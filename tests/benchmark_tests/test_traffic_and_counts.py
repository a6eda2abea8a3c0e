"""The closed-loop generator's sizes from a seed, and the operation counts
against hand arithmetic."""
import collections

import numpy as np
import pytest

from benchmark.harness import registry, stats


@pytest.fixture(scope="module")
def serve():
    return registry.load_module("drivers", "serve_closed")


@pytest.fixture(scope="module")
def traffic():
    return registry.load_json("workloads", "sc2-3b-serve-batchgen")["traffic"]


def test_round_of_sizes_follows_the_workload_file(serve, traffic):
    sizes = serve.round_of_sizes(traffic)
    prompts = sorted(p for p, _, _ in sizes)
    outs = sorted(n for _, n, _ in sizes)
    assert len(sizes) == 64
    assert prompts[0] >= 64 and prompts[-1] <= 1024
    assert 170 <= stats.median(prompts) <= 215           # log-normal, 192
    assert sum(p > 256 for p in prompts) >= 16           # both buckets used
    assert (outs[0], outs[-1]) == (32, 160)
    assert 90 <= sum(outs) / 64 <= 102
    assert sum(g for _, _, g in sizes) == 32             # every 2nd greedy
    assert sizes == serve.round_of_sizes(traffic)        # no seed in it
    assert max(p + n for p, n, _ in sizes) <= 1280       # the reference's pad


@pytest.mark.parametrize("seed", [0, 12345, 2 ** 31 + 7])
def test_every_seed_serves_the_same_sizes_in_the_same_order(serve, traffic,
                                                            seed):
    def stream(s, n=128):
        t = serve.Traffic(s, traffic, 49152)
        return [t.next() for _ in range(n)]

    a, b = stream(seed), stream(seed + 1)
    key = lambda r: (r["prompt"].size, r["max_new_tokens"],
                     "temperature" in r)
    assert [key(r) for r in a] == [key(r) for r in b]
    assert collections.Counter(map(key, a[:64])) == collections.Counter(
        map(key, a[64:]))
    assert not np.array_equal(a[0]["prompt"], b[0]["prompt"])
    again = stream(seed)
    assert all(np.array_equal(x["prompt"], y["prompt"]) and x["seed"] == y["seed"]
               for x, y in zip(a, again))
    assert all(0 <= r["prompt"].min() and r["prompt"].max() < 49152
               for r in a)
    sampled = [r for r in a if "temperature" in r]
    assert len(sampled) == 64 and sampled[0]["top_k"] == 50


def test_percentile_never_returns_nan():
    with pytest.raises(ValueError):
        stats.percentile([], 95)
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile(list(range(1, 101)), 95) == 95
    assert stats.median([1, 2, 3, 4]) == 2


def test_training_operations_per_token_by_hand():
    count = registry.load_module("counts", "train_model")
    pub = registry.load_config(registry.load_benchmark(),
                               "gpt2-medium")["published"]
    assert count.matmul_params(pub) == 353_453_056
    # 24 layers x 3 (forward + backward) x 2 L d, causal half counted once
    assert count.attention_flops_per_token(pub, 1024) == 150_994_944
    assert count.flops_per_token(pub, 1024) == 2_271_713_280     # 2.27 GFLOP


def test_flash_counts_by_hand():
    flash = registry.load_module("counts", "flash")
    f, b = flash.forward(8, 16, 16, 1024, 1024, 64)
    assert f == 4 * 8 * 16 * 1024 * 1024 * 64 / 2
    assert b == 2 * 4 * (8 * 16 * 1024 * 64)
    fb, bb = flash.backward(8, 16, 16, 1024, 1024, 64)
    assert fb == 2.5 * f and bb == 2 * b
    peaks = registry.load_peaks("TPU v5 lite")
    seconds, bound = flash.least_seconds(f, b, peaks)
    assert bound == "compute" and seconds == pytest.approx(f / 197e12)
    assert flash.least_seconds(1.0, 819e9, peaks) == (1.0, "memory")


def test_fused_ce_counts_by_hand():
    ce = registry.load_module("counts", "fused_ce")
    n, d, v = 8192, 1024, 51200
    assert ce.call("fused_ce_fwd", n, d, v)[0] == 2 * n * d * v
    assert ce.call("fused_ce_dh", n, d, v)[0] == 4 * n * d * v
    assert ce.call("fused_ce_dw", n, d, v)[0] == 4 * n * d * v


def test_decode_bytes_by_hand():
    dec = registry.load_module("counts", "decode")
    cfg = registry.load_config(registry.load_benchmark(),
                               "starcoder2-3b")["as_run"]
    per_layer = 3072 * 3072 * 2 + 2 * 3072 * 256 + 2 * 3072 * 12288
    assert dec.weight_bytes(cfg) == 2 * (30 * per_layer + 3072 * 49152)
    assert 6.0e9 < dec.weight_bytes(cfg) < 6.2e9            # "6.1 GB"
    assert dec.cache_bytes_per_token(cfg) == 2 * 30 * 2 * 256
    # 64 slots x 2048 rows: the 4.0 GB of pages the cell's why states
    assert 64 * 2048 * dec.cache_bytes_per_token(cfg) == pytest.approx(
        4.03e9, rel=0.01)
