"""Operations and bytes of the fused head + cross-entropy kernels, per call,
on the vocabulary the kernel is run on (the padded one: the kernel cannot
skip a tile). ``fused_ce_fwd``: logits = h.W, 1 matmul. ``fused_ce_dh``:
logits again and dh = dlogits.W^T, 2. ``fused_ce_dw``: logits again and
dW = h^T.dlogits, 2. The logits never reach memory, so the recomputation is
the algorithm's, not waste. Each matmul is 2.n.d.v flops.
"""

MATMULS = {"fused_ce_fwd": 1, "fused_ce_dh": 2, "fused_ce_dw": 2}


def call(kernel, n_rows, d, v, itemsize=2):
    flops = MATMULS[kernel] * 2 * n_rows * d * v
    # h and W read once a call, one result the size of h or W written
    bytes_ = itemsize * (n_rows * d + d * v) + {
        "fused_ce_fwd": 12 * n_rows, "fused_ce_dh": itemsize * n_rows * d,
        "fused_ce_dw": itemsize * d * v}[kernel]
    return flops, bytes_
