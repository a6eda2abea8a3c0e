"""Operations and bytes of one call of the flash attention kernels.

Forward: s = q.k^T and o = p.v, 2 matmuls of 2.lq.lk.dh flops a head, half of
them under a causal mask. Backward (fused or split): s again, dp = do.v^T,
dv = p^T.do, dq = ds.k, dk = ds^T.q: 5 matmuls, the recomputation of s
included because the algorithm has no stored s to read. Bytes are each
operand read once and each result written once, in the compute type.
"""


def forward(b, h, hkv, lq, lk, dh, itemsize=2, causal=True):
    flops = 4 * b * h * lq * lk * dh * (0.5 if causal else 1.0)
    bytes_ = itemsize * (2 * b * h * lq * dh + 2 * b * hkv * lk * dh)
    return flops, bytes_


def backward(b, h, hkv, lq, lk, dh, itemsize=2, causal=True):
    flops = 10 * b * h * lq * lk * dh * (0.5 if causal else 1.0)
    # reads q, k, v, o, do; writes dq, dk, dv
    bytes_ = itemsize * (4 * b * h * lq * dh + 4 * b * hkv * lk * dh)
    return flops, bytes_


def least_seconds(flops, bytes_, peaks):
    """(seconds, which bound applies)."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = bytes_ / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
