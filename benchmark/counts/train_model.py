"""Operations a training step requires per token: forward and backward of
every matrix multiplication (6 per parameter) and of causal attention counted
once (the masked half is not work), no recomputation. On the PUBLISHED sizes:
padded vocabulary rows are the program's cost, not the model's."""


def matmul_params(pub):
    d, ff = pub["n_embd"], pub["n_inner"]
    return pub["n_layer"] * (4 * d * d + 2 * d * ff) + d * pub["vocab_size"]


def attention_flops_per_token(pub, seq_len):
    # forward: q.k^T and p.v are 2 x (2 L d) a token over the full square,
    # half of it under the causal mask; backward costs twice the forward
    return pub["n_layer"] * 3 * (2 * seq_len * pub["n_embd"])


def flops_per_token(pub, seq_len):
    return 6 * matmul_params(pub) + attention_flops_per_token(pub, seq_len)
