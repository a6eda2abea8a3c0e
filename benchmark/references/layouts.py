"""From the program's parameter tree to the reference's plain names.

This is knowledge of the program's tree layout (which leaf holds what), kept
as slices and reshapes: linear maps, so the same function carries gradients
and parameter changes over for the per-leaf comparison. It imports nothing of
the program.
"""
import jax.numpy as jnp


def canonical_layer(block):
    """One ``block_i`` subtree -> the reference's layer dict."""
    if "qkv_bhld" in block:                      # [d, 3, h, e], head-major
        w = block["qkv_bhld"]
        d = w.shape[0]
        wq, wk, wv = (w[:, t].reshape(d, -1) for t in range(3))
        wo = block["attn_out_bhld"].reshape(-1, d)
    elif "qkv" in block:                         # [d, 3d]
        wq, wk, wv = jnp.split(block["qkv"]["kernel"], 3, axis=1)
        wo = block["attn_out"]["kernel"]
    else:                                        # grouped heads
        wq = block["q_proj"]["kernel"]
        wk, wv = jnp.split(block["kv_proj"]["kernel"], 2, axis=1)
        wo = block["attn_out"]["kernel"]
    return {
        "ln1_s": block["LayerNorm_0"]["scale"],
        "ln1_b": block["LayerNorm_0"]["bias"],
        "wq": wq, "wk": wk, "wv": wv, "wo": wo,
        "ln2_s": block["LayerNorm_1"]["scale"],
        "ln2_b": block["LayerNorm_1"]["bias"],
        "w_in": block["ffn_in"]["kernel"], "b_in": block["ffn_in"]["bias"],
        "w_out": block["ffn_out"]["kernel"], "b_out": block["ffn_out"]["bias"],
    }


def canonical_rest(tree):
    out = {
        "emb": tree["tok_emb"]["embedding"],
        "lnf_s": tree["LayerNorm_0"]["scale"],
        "lnf_b": tree["LayerNorm_0"]["bias"],
        "head": tree["lm_head"]["kernel"],
    }
    if "pos_emb" in tree:
        out["pos"] = tree["pos_emb"]
    return out


def canonical_tree(tree, n_layers):
    """Whole program tree -> {"layers": stacked [n_layers, ...], "rest"}."""
    layers = [canonical_layer(tree[f"block_{i}"]) for i in range(n_layers)]
    stacked = {k: jnp.stack([l[k] for l in layers]) for k in layers[0]}
    return {"layers": stacked, "rest": canonical_rest(tree)}
