#!/usr/bin/env python
"""Flash-attention BACKWARD block sweep at the LM bench shape.

The (1024, 1024) defaults were tuned on the FORWARD kernel (BASELINE.md
§flash); the backward kernels hold 4 live [bq, bk] f32 intermediates
(s, p, dp, ds) instead of 2 and may prefer different tiles. Times the
full vjp (fwd+bwd) AND fwd-only per config, scan-amortized inside one
jit (memory: ~7.5 ms per async dispatch, ~100 ms per sync — see
BASELINE.md methodology), warm 3 executions.

Usage: python tools/bench_flash_bwd.py [B H L D [K]]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax

    from chainermn_tpu.ops.flash_attention import flash_attention
    from chainermn_tpu.utils import use_compile_cache

    use_compile_cache()

    B = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    H = int(sys.argv[2]) if len(sys.argv) > 2 else 12
    L = int(sys.argv[3]) if len(sys.argv) > 3 else 2048
    D = int(sys.argv[4]) if len(sys.argv) > 4 else 64
    K = int(sys.argv[5]) if len(sys.argv) > 5 else 20

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(B, L, H, D) * 0.3, jnp.bfloat16)
    k = jnp.asarray(rs.randn(B, L, H, D) * 0.3, jnp.bfloat16)
    v = jnp.asarray(rs.randn(B, L, H, D) * 0.3, jnp.bfloat16)

    def timed(fn):
        """K carry-dependent iterations inside one jit; report s/iter."""
        def loop(q, k, v):
            def body(c, _):
                qq, kk, vv = c
                o = fn(qq, kk, vv)
                # carry dependence without changing magnitudes
                return (qq + 0.0 * o[0], kk, vv), ()
            (qq, _, _), _ = lax.scan(body, (q, k, v), None, length=K)
            return qq
        j = jax.jit(loop)
        for _ in range(3):
            r = j(q, k, v)
            float(jnp.sum(r[0, 0].astype(jnp.float32)))
        t0 = time.perf_counter()
        reps = 3
        for _ in range(reps):
            r = j(q, k, v)
            float(jnp.sum(r[0, 0].astype(jnp.float32)))
        return (time.perf_counter() - t0) / (reps * K)

    def grad_fn(fwd_blocks, bwd_blocks):
        def f(q, k, v):
            def loss(q, k, v):
                o = flash_attention(
                    q, k, v, True, None, fwd_blocks[0], fwd_blocks[1],
                    None, None, None, bwd_blocks)
                return jnp.sum(o.astype(jnp.float32) ** 2)
            g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            return g[0]
        return f

    def fwd_fn(blocks):
        return lambda q, k, v: flash_attention(
            q, k, v, True, None, blocks[0], blocks[1])

    results = []
    fwd_grid = [(1024, 1024), (512, 1024), (512, 512), (256, 1024)]
    for fb in fwd_grid:
        s = timed(fwd_fn(fb))
        results.append({"kind": "fwd", "blocks": fb, "ms": s * 1e3})
        print(json.dumps(results[-1]), flush=True)

    bwd_grid = [(1024, 1024), (512, 1024), (1024, 512), (512, 512),
                (256, 1024), (256, 512), (128, 1024), (2048, 512),
                (512, 2048), (256, 256)]
    best_fwd = min((r for r in results if r["kind"] == "fwd"),
                   key=lambda r: r["ms"])["blocks"]
    for bb in bwd_grid:
        s = timed(grad_fn(tuple(best_fwd), bb))
        results.append({"kind": "fwd+bwd", "fwd_blocks": best_fwd,
                        "bwd_blocks": bb, "ms": s * 1e3})
        print(json.dumps(results[-1]), flush=True)


if __name__ == "__main__":
    main()
