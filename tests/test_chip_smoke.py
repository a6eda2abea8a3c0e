"""chip_smoke.py's two legs at toy widths on the 8-device CPU mesh.

Same code the chip runs — the entry points, the trainer loop, the
engine, every check — with the Pallas kernels interpreted and
``main()``'s device assertions left out. Guards the smoke itself: a PR
that breaks a leg's wiring fails here before it spends chip time."""

import subprocess
import sys

import jax

import chip_smoke

TOY = dict(vocab=2048, d_model=32, n_heads=2, n_layers=2, d_ff=64,
           max_len=64)


def test_both_legs_at_toy_widths():
    model, params, train = chip_smoke.train_leg(
        TOY, seq_len=64, batch_per_chip=1, steps=4, lr=3e-3)
    assert train["devices"] == jax.device_count() == 8
    assert train["steps"] == 4 and train["last_loss"] < train["first_loss"]
    assert train["mosaic_kernels"] == []        # interpreted on the CPU
    assert len(train["batch_devices"]) == len(train["opt_state_devices"]) == 8

    # two engines, each on its own device, behind the router
    serve = chip_smoke.serve_leg(model, params, devices=jax.devices()[:2],
                                 capacity=64, n_slots=2)
    assert serve["front_door"] == "fleet.Router"
    assert serve["decode_k_traces"] == [1, 1]
    assert [p["pages_on"] for p in serve["placement"]] == [[0], [1]]
    assert [p["params_on"] for p in serve["placement"]] == [[0], [1]]
    assert serve["prefill_attention"] == "flash"


def test_main_refuses_a_cpu():
    """``main()`` names the platform it found, prints no result line and
    exits non-zero when JAX has no TPU."""
    proc = subprocess.run([sys.executable, chip_smoke.__file__],
                          capture_output=True, text=True, timeout=300,
                          env={"JAX_PLATFORMS": "cpu", "PATH": ""})
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout
