"""Block autotuner: off-TPU fallback, memoization, and model wiring."""

import numpy as np

import jax
import jax.numpy as jnp

from chainermn_tpu.ops.autotune import _CACHE, tune_flash_blocks


def test_off_tpu_returns_defaults_and_caches():
    _CACHE.clear()
    blocks = tune_flash_blocks(2, 512, 4, 64)
    assert blocks == (1024, 1024)  # interpreter timing would be noise
    assert len(_CACHE) == 1
    assert tune_flash_blocks(2, 512, 4, 64) == blocks
    assert len(_CACHE) == 1


def test_attention_blocks_plumb_through_lm():
    """TransformerLM(attention_blocks=...) reaches the kernel (a working
    forward with non-default, odd-fitting blocks proves the plumbing)."""
    from chainermn_tpu.models.transformer import TransformerLM

    model = TransformerLM(vocab=32, d_model=32, n_heads=2, n_layers=1,
                          d_ff=32, max_len=64, attention="flash",
                          attention_blocks=(32, 32))
    tok = np.random.RandomState(0).randint(0, 32, (2, 64)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(tok))["params"]
    out = model.apply({"params": params}, jnp.asarray(tok))
    assert out.shape == (2, 64, 32)
    assert np.isfinite(np.asarray(out)).all()


def test_on_chip_sweep_with_no_working_candidate_raises(monkeypatch):
    """'Not on a chip' returns the defaults; 'every candidate failed on
    the chip' must not look the same — it raises, naming each failure."""
    import sys

    import pytest

    import chainermn_tpu.ops.autotune as autotune

    # the package re-exports the FUNCTION under the submodule's name
    fa = sys.modules["chainermn_tpu.ops.flash_attention"]

    def refuse(*a, **kw):
        raise ValueError("Mosaic says no")

    _CACHE.clear()
    monkeypatch.setattr(autotune, "on_tpu", lambda: True)
    monkeypatch.setattr(fa, "flash_attention", refuse)
    with pytest.raises(RuntimeError, match="every candidate failed"):
        tune_flash_blocks(1, 128, 2, 32, include_backward=False,
                          candidates=((128, 128), (64, 128)))
    assert not _CACHE        # a failed sweep is not memoized as a result
