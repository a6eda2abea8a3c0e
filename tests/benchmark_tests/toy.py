"""Toy-width copies of the benchmark's cells for the CPU mesh: the real
configuration and workload files, with sizes cut so that a run takes
seconds. Nothing here describes a TPU."""
import argparse
import copy

from benchmark.harness import registry

TOY_LIMITS = {
    # set at these toy widths from a dozen seeds (see test_drivers.py)
    "train": {"loss_gap": 0.003, "grad_norm_gap": 0.008, "delta_norm_gap": 0.006},
    "serve": {"served_logit_gap": 0.05},
}


def toy_cell(name):
    bench = registry.load_benchmark()
    cell = registry.cell_entry(bench, name)
    workload = copy.deepcopy(registry.load_json("workloads", name))
    config = copy.deepcopy(registry.load_config(bench, cell["config"]))
    if workload["driver"] == "train_dp":
        config["as_run"].update(vocab=2048, d_model=64, n_heads=4,
                                n_kv_heads=4, n_layers=2, d_ff=128,
                                max_len=128)
        config["published"].update(vocab_size=2000, n_embd=64, n_layer=2,
                                   n_inner=128)
        workload["traffic"].update(seq_len=128, batch_per_chip=2,
                                   dataset_batches=8)
        workload["check"]["limits"] = dict(TOY_LIMITS["train"])
        workload["trace"]["seconds"] = 0.2
    else:
        config["as_run"].update(vocab=512, d_model=64, n_heads=4,
                                n_kv_heads=2, n_layers=2, d_ff=128,
                                max_len=128)
        workload["traffic"].update(
            clients=4, prompt_len=dict(median=20, sigma=0.8, min=8, max=64),
            output_len=dict(min=5, max=12), ramp_iterations=6, greedy_every=2)
        workload["engine"].update(n_slots=4, capacity=128,
                                  buckets=[32, 64, 128],
                                  attention="reference")
        workload["check"].update(reference_len=96, reference_out=16,
                                 min_tokens=5, sample_requests=2,
                                 limits=dict(TOY_LIMITS["serve"]))
        workload["trace"]["seconds"] = 0.2
    return bench, cell, workload, config


def toy_args(seed=7, seconds=1.0, trace=0):
    return argparse.Namespace(seed=seed, seconds=seconds, trace=trace)
