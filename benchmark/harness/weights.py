"""Seeded weights, made on the device by rule from a leaf's path and shape.

The benchmark makes the weights, in the program's tree layout, and hands them
to the program; the reference is given the same leaves, regenerated from the
seed (one layer at a time where the whole model in float32 would not fit).
Every leaf is keyed by ``(seed, layer id, crc32 of the path inside the
layer)``, so one layer can be made alone and equals the same layer of the
whole tree.

Rules: ``scale`` leaves are 1 + 0.02 N, ``bias`` leaves 0.02 N, embeddings and
position tables 0.02 N (GPT-2's initial range), every other leaf a kernel with
N(0, 1/fan_in) entries.
"""
import functools
import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np

_BLOCK = re.compile(r"^block_(\d+)$")


def flatten(tree, prefix=()):
    """Nested dict -> {path tuple: leaf}."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flatten(v, prefix + (k,)))
        else:
            out[prefix + (k,)] = v
    return out


def unflatten(flat):
    tree = {}
    for path, v in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = v
    return tree


def leaf_ids(path, n_layers):
    """(layer id, leaf id): block_i leaves get layer i, the others n_layers."""
    m = _BLOCK.match(path[0])
    if m:
        return int(m.group(1)), zlib.crc32("/".join(path[1:]).encode())
    return n_layers, zlib.crc32("/".join(path).encode())


def fan_in(path, shape):
    name = path[-1] if path[-1] != "kernel" else path[-2]
    if name.startswith("attn_out_bhld"):      # [heads, d_head, d_model]
        return shape[0] * shape[1]
    return shape[0]


def make_leaf(seed, layer_id, leaf_id, path, shape, dtype):
    """One leaf. ``layer_id`` may be traced; everything else is static."""
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(seed), layer_id),
        leaf_id & 0x7FFFFFFF)
    z = jax.random.normal(key, shape, jnp.float32)
    last = path[-1]
    if last == "scale":
        z = 1.0 + 0.02 * z
    elif last == "bias" or last in ("embedding", "pos_emb"):
        z = 0.02 * z
    else:
        z = z * (float(fan_in(path, shape)) ** -0.5)
    return z.astype(dtype)


def spec_of(tree):
    """{path: shape} of a tree of arrays or ShapeDtypeStructs."""
    return {p: tuple(v.shape) for p, v in flatten(tree).items()}


def make_tree(seed, spec, n_layers, dtype, sharding=None):
    """The whole tree in one jitted call, on the device, in ``dtype``."""
    paths = sorted(spec)

    @functools.partial(jax.jit, out_shardings=sharding)
    def build(seed):
        return unflatten({
            p: make_leaf(seed, *leaf_ids(p, n_layers), p, spec[p], dtype)
            for p in paths})

    return build(seed_word(seed))


def make_layer(seed, spec, layer, n_layers, dtype):
    """The leaves of ``block_<layer>`` alone (``layer`` may be traced), as
    {path inside the block: leaf}; the spec is read off block_0."""
    out = {}
    for p in sorted(spec):
        if p[0] != "block_0":
            continue
        _, leaf_id = leaf_ids(p, n_layers)
        out[p[1:]] = make_leaf(seed, layer, leaf_id, p, spec[p], dtype)
    return unflatten(out)


def make_rest(seed, spec, n_layers, dtype):
    """The leaves outside the blocks (embeddings, final norm, head)."""
    return unflatten({
        p: make_leaf(seed, *leaf_ids(p, n_layers), p, spec[p], dtype)
        for p in sorted(spec) if not _BLOCK.match(p[0])})


def seed_word(seed):
    """``--seed`` may pass 2**31: fold it into a uint32 for the PRNG."""
    return np.uint32(int(seed) % (1 << 32))
