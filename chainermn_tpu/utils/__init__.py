"""Small shared utilities."""

from __future__ import annotations

import os


def match_vma(tree, ref):
    """Make ``tree``'s leaves vary on the same manual mesh axes as ``ref``.

    Under shard_map's varying-axis tracking, freshly created constants
    (zeros carries, accumulators) are axis-invariant while scanned/looped
    data varies — lax.scan/fori_loop then reject the carry type mismatch.
    pcast-to-varying aligns them; no-op outside shard_map or when tracking
    is off.
    """
    import jax

    ref_vma = getattr(jax.typeof(ref), "vma", None)
    if not ref_vma:
        return tree

    def fix(l):
        need = tuple(ref_vma - jax.typeof(l).vma)
        return jax.lax.pcast(l, need, to="varying") if need else l

    return jax.tree_util.tree_map(fix, tree)


def on_tpu() -> bool:
    """THE spelling of "this process computes on a TPU". The Pallas
    kernels compile for the chip when it is true and run in the Pallas
    interpreter (CPU tests) when it is not; the tuners measure only when
    it is true. Initialises the backend."""
    import jax

    return jax.default_backend() == "tpu"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed place; returns
    the directory in use. ``$JAX_COMPILATION_CACHE_DIR`` wins — JAX reads
    it itself, so nothing is set here. Otherwise the cache lives in
    ``<checkout>/.jax_cache`` (gitignored): the path is part of the cache
    key's lookup, so it must not move between runs. Entry points
    (chip_smoke.py, tools/) call this once before compiling."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
