"""On-device block-size autotuning for the flash attention kernels.

The tuned defaults in `ops/flash_attention.py` (1024, 1024) were measured
on v5e at d=128; other head dims, sequence lengths, or TPU generations
can prefer different tiles (BASELINE.md's sweep saw 2x spread). This
sweeps candidate (block_q, block_k) pairs with the REAL kernels on the
current default device and returns the fastest — profile-and-iterate as
a one-call utility.

Results are memoized per (shape, dtype, causal, window) key for the
process lifetime. Off a TPU nothing is timed and the defaults come back
(interpreter timings would be meaningless); on a TPU a sweep in which
no candidate compiles and runs is an error, never the defaults.
"""

from __future__ import annotations

import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from chainermn_tpu.utils import on_tpu

_CACHE: dict = {}

_CANDIDATES = ((128, 256), (256, 512), (512, 512), (512, 1024),
               (1024, 512), (1024, 1024))


def tune_flash_blocks(batch: int, seq_len: int, heads: int, head_dim: int,
                      kv_heads: Optional[int] = None,
                      dtype=jnp.bfloat16, causal: bool = True,
                      window: Optional[int] = None,
                      include_backward: bool = True,
                      candidates=_CANDIDATES,
                      iters: int = 3) -> Tuple[int, int]:
    """Return the fastest (block_q, block_k) for this attention shape.

    Times `flash_attention` (forward, or full value-and-grad when
    ``include_backward``) for each candidate on the default backend and
    memoizes. Use the result as the ``block_q``/``block_k`` arguments or
    `TransformerBlock`'s ``attention_blocks``.
    """
    from chainermn_tpu.ops.flash_attention import (DEFAULT_BLOCKS,
                                                   _fit_block,
                                                   _padded_len,
                                                   _window_cap,
                                                   flash_attention)

    key = (batch, seq_len, heads, head_dim, kv_heads, str(dtype), causal,
           window, include_backward)
    if key in _CACHE:
        return _CACHE[key]
    if not on_tpu():
        _CACHE[key] = DEFAULT_BLOCKS  # not on a chip: nothing measured
        return _CACHE[key]

    hkv = kv_heads or heads
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (batch, seq_len, heads, head_dim), dtype)
    k = jax.random.normal(ks[1], (batch, seq_len, hkv, head_dim), dtype)
    v = jax.random.normal(ks[2], (batch, seq_len, hkv, head_dim), dtype)

    best, best_dt, failed = None, float("inf"), {}
    # the kernel clamps blocks to divisors of L (_fit_block) and a window
    # caps block_k: candidates mapping to the same effective pair alias
    # the same compiled kernel — dedup so each is timed once (short
    # sequences, e.g. L=512, collapse several candidates)
    seen = set()
    deduped = []
    for bq, bk in candidates:
        # mirror the kernel wrapper's composition exactly:
        # window-cap → pad-to-legal-length → clamp-to-divisor
        bkc = _window_cap(bk, window)
        eff = (_fit_block(bq, _padded_len(bq, seq_len)),
               _fit_block(bkc, _padded_len(bkc, seq_len)))
        if eff not in seen:
            seen.add(eff)
            deduped.append((bq, bk))
    for bq, bk in deduped:
        def loss(q, k, v, bq=bq, bk=bk):
            out = flash_attention(q, k, v, causal, None, bq, bk, None,
                                  None, window)
            return jnp.sum(out.astype(jnp.float32)) * 1e-3

        fn = (jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
              if include_backward else jax.jit(loss))
        try:
            jax.block_until_ready(fn(q, k, v))  # compile + first run
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(q, k, v)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / iters
        except Exception as e:
            # candidate illegal for this shape (VMEM, layout): recorded,
            # and reported if no candidate is left standing
            failed[(bq, bk)] = e
            continue
        if dt < best_dt:
            best, best_dt = (bq, bk), dt
    if best is None:
        raise RuntimeError(
            "tune_flash_blocks: every candidate failed on the chip: "
            + "; ".join(f"{c}: {type(e).__name__}: {str(e)[:200]}"
                        for c, e in failed.items())
        ) from next(iter(failed.values()))
    _CACHE[key] = best
    return best
