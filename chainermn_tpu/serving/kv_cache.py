"""Paged, ring-buffered KV cache + the compiled serving step pair.

The serving cache is the training model's own flax ``cache`` collection,
re-shaped for continuous batching: one PAGE per transformer block, each
page ``k``/``v`` of shape ``[n_slots, capacity, n_kv_heads, d_head]``
plus a per-slot ``idx`` cursor vector ``[n_slots]`` (the decode branch in
``models/transformer.py`` accepts either the scalar cursor ``generate()``
uses or this vector — every row then advances independently).

Ring semantics: the write position for token ``p`` of slot ``s`` is
``p % capacity``; once a slot's stream outgrows its page the oldest
tokens are overwritten and attention degrades to a ``capacity``-token
sliding window (the mask inverts the ring — see the ``kpos`` comment in
the decode branch). Prefer ``pos_emb='rope'`` for streams expected to
wrap (learned positions clip at ``max_len``).

Two compiled entry points, following the SNIPPETS Partitioner shape
(jit with explicit in/out shardings, donated cache buffers):

* ``prefill`` — a fixed-shape cohort ``[S, L_bucket]`` runs the one
  legal multi-token decode apply on a FRESH slab cache, then scatters
  the slab into the page at the cohort's slot ids (a sentinel id of
  ``n_slots`` drops padding rows — ``mode='drop'``). Returns each
  prompt's last-position logits (the first sampled token — TTFT).
* ``decode_step`` — one token for ALL ``n_slots`` slots at once, a
  single ``[n_slots, 1]`` apply against the paged cache. Constant
  shapes by construction: traced once, reused forever (the DL108
  trap this module exists to avoid).

On top of the pair, the multi-token dispatches the engine actually
serves with (ISSUE 10):

* ``decode_k`` — ``k`` decode steps under one ``jax.lax.scan`` with
  on-device sampling (``serving/sampling.py``) feeding each step's
  token to the next, plus per-slot EOS/budget stop masks. One host
  dispatch commits up to ``k`` tokens and transfers ``O(n_slots)``
  int32 ids (4 bytes/token) instead of ``O(n_slots × vocab)`` f32
  logits. Mid-prefill slots ride along PARKED: their cursors are
  pinned to the host-supplied fill level around the scan so decode
  garbage never walks them toward a ring wrap.
* ``prefill_chunk`` — a fixed ``[S, C]`` window of prompt tokens
  written incrementally at each slot's ``pos_offset`` cursor
  (``chunked_prefill=True`` model twin: the slab attends prefix +
  itself under an absolute-position mask). ONE compiled program for
  any prompt length — long prompts stream in without head-of-line
  blocking decode, and chunked == monolithic bitwise (same tokens,
  same cache bytes — tests/serving_tests/test_sampling.py).

Numerics contract (tested bitwise): with ``capacity`` ≥ the full stream
length and ``attention='reference'``, cached decode logits equal the
corresponding full-forward column BITWISE — the decode branch uses
squeezed-q contractions and the same-program prefill kernel to make the
cached path a re-association-free restatement of the training forward.

int8-block page mode (``kv_dtype='int8-block'``, ISSUE 20): pages live
at rest as blockwise int8 codes + f32 scales — the PR 8/11 EQuARX wire
codec moved into the cache itself, with block ``gcd(256, n_kv_heads ·
d_head)`` so every cache column is a whole number of blocks and an
exported slot's flattened codes/scales form a valid ``block_dequantize``
payload (fleet/handoff.py ships them verbatim, without requantizing).
Every compiled program dequantizes ONCE at dispatch entry
(:func:`unpack_cache`) and re-quantizes only the columns the dispatch
actually wrote (:func:`repack_cache`): requantization is not provably
idempotent (the re-derived scale can differ by 1 ulp), so untouched
columns must keep their exact resident bytes. By ``cache_bytes``'
count a slot's resident pages are under 1/3.5 of float32 pages'
(``tests/serving_tests/test_serving_contracts.py``; device memory: not
measured); accuracy is held to a calibrated logit-error bound
(``tests/serving_tests/test_kv_cache.py``) rather than bitwise parity, and
peak transient memory during a dispatch is the f32 working copy — the
win is the RESIDENT footprint between dispatches.
No mesh sharding and no ring wrap in this mode — the engine enforces
``prompt + max_new ≤ capacity`` at submit.

Declared state (ISSUE 27): a model that sets ``declares_cache``
(``models/hybrid.py``) has no K/V rows. It is served by
``serving/state_cache.py``'s :class:`StateServingStep`, a subclass that keeps
:class:`ServingStep`'s entry points, jit caches, trace counters and donation
and replaces what names K/V: the pages (the model's declared ``cache``
collection — any slot-major leaf, the root ``idx`` the cursor), the three
programs, the shardings and the slot export. ``state_cache.serving_step``
picks the class once, at construction; ``cache_spec`` and the K/V arithmetic
here stay for the models that have K/V.
"""

from __future__ import annotations

import contextlib
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chainermn_tpu import tracing
from chainermn_tpu.collectives.quantized import QUANT_BLOCK
from chainermn_tpu.models.transformer import bhld_to_blhd_params
from chainermn_tpu.ops.latent_attention import record_paths
from chainermn_tpu.ops.page_write import partitioned_pages
from chainermn_tpu.serving.sampling import sample_tokens

__all__ = ["init_cache", "cache_bytes", "cache_spec", "slot_bytes", "decode_apply",
           "prefill_apply", "decode_k_apply", "prefill_chunk_apply",
           "ServingStep", "KV_PAGE_DTYPES", "page_block", "unpack_cache",
           "repack_cache", "cache_is_quantized"]

#: page storage modes: f32 (resident = compute dtype, bitwise contract)
#: and int8-block (resident = blockwise int8 codes + f32 scales)
KV_PAGE_DTYPES = ("f32", "int8-block")


def _normalize_kv_dtype(kv_dtype: Optional[str]) -> str:
    mode = kv_dtype or "f32"
    if mode not in KV_PAGE_DTYPES:
        raise ValueError(
            f"kv_dtype {kv_dtype!r} not in {KV_PAGE_DTYPES}")
    return mode


def page_block(model) -> int:
    """int8-block block size for this model: ``gcd(256, n_kv_heads ·
    d_head)``. Dividing the per-token row length keeps every cache
    column a whole number of blocks, which is what makes the masked
    per-column requantize in :func:`repack_cache` exact and an exported
    slot's flattened codes/scales a valid ``block_dequantize`` payload
    at this block size (fleet/handoff.py ships them verbatim)."""
    spec = cache_spec(model)
    return math.gcd(QUANT_BLOCK, spec["n_kv_heads"] * spec["d_head"])


def _check_servable(model):
    if getattr(model, "declares_cache", False):
        raise ValueError(
            f"{type(model).__name__} declares its own cache: build its step "
            "with serving.state_cache.serving_step (StateServingStep), "
            "which takes the pages from what the model declares")
    if model.moe_experts_per_device > 0:
        raise ValueError(
            "serving does not support this MoE model: it has the "
            "Switch/GShard training layer "
            "(TransformerLM with moe_experts_per_device > 0, "
            "parallel/expert_parallel.py): its capacity factor drops "
            "tokens and its dispatch is an all-to-all inside shard_map, "
            "which the jit decode path has not. Expert models serve as "
            "models/hybrid.py's HybridLM, whose feed-forward is the "
            "dropless held-expert share of parallel/expert_share.py")
    if model.tp_axis is not None or getattr(model, "lm_head_tp", False):
        raise ValueError(
            "serving runs the jit decode path; tp_axis/lm_head_tp models "
            "serve without shard_map TP (clone with tp_axis=None, "
            "lm_head_tp=False and gather the weights — head-axis mesh "
            "sharding of the cache covers the TP layout instead)")


def cache_spec(model) -> Dict[str, int]:
    """The numbers the sizing math and page shapes derive from."""
    return dict(
        n_layers=model.n_layers,
        n_kv_heads=model.n_kv_heads or model.n_heads,
        d_head=model.d_model // model.n_heads,
    )


def cache_bytes(model, n_slots: int, capacity: int,
                dtype: Any = None, kv_dtype: Optional[str] = None) -> int:
    """Preallocated RESIDENT cache footprint: ``n_layers · n_slots ·
    capacity · 2 (K and V) · n_kv_heads · d_head · itemsize`` — the
    budget line in docs/serving.md's sizing table. In ``int8-block``
    mode the per-element cost is ``1 + 4/block`` bytes (codes + the
    amortized f32 scale), which is where the ≥3.5× slots-per-chip gain
    comes from."""
    spec = cache_spec(model)
    r = spec["n_kv_heads"] * spec["d_head"]
    cells = spec["n_layers"] * n_slots * capacity * 2 * r
    if _normalize_kv_dtype(kv_dtype) == "int8-block":
        return cells + cells // page_block(model) * 4
    itemsize = jnp.dtype(dtype or model.dtype).itemsize
    return cells * itemsize


def slot_bytes(cache) -> int:
    """Bytes one slot holds across every (slot-major) leaf of ``cache``."""
    return sum(a.dtype.itemsize * (a.size // a.shape[0])
               for a in jax.tree_util.tree_leaves(cache))


def init_cache(model, n_slots: int, capacity: int, dtype: Any = None,
               kv_dtype: Optional[str] = None):
    """Fresh zeroed pages: ``{"block_i": {"k", "v", "idx"}}`` with
    per-slot cursor vectors. The tree is exactly the flax ``cache``
    collection ``model.clone(decode=True)`` declares — supplied values
    override the declared ``max_len`` shapes, which is how ``capacity``
    decouples from ``model.max_len``.

    ``kv_dtype='int8-block'`` swaps each page's ``k``/``v`` leaves for
    ``k_q``/``v_q`` (int8 codes, same shape) + ``k_s``/``v_s`` (f32
    scales, one per block). Scales init to 1.0 — exactly what
    ``block_quantize`` emits for an all-zero block, so a fresh page is
    the quantization of a fresh f32 page."""
    spec = cache_spec(model)
    dt = dtype or model.dtype
    shape = (n_slots, capacity, spec["n_kv_heads"], spec["d_head"])
    if _normalize_kv_dtype(kv_dtype) == "int8-block":
        blk = page_block(model)
        s_shape = (n_slots, capacity,
                   spec["n_kv_heads"] * spec["d_head"] // blk)
        page = lambda: {
            "k_q": jnp.zeros(shape, jnp.int8),
            "k_s": jnp.ones(s_shape, jnp.float32),
            "v_q": jnp.zeros(shape, jnp.int8),
            "v_s": jnp.ones(s_shape, jnp.float32),
            "idx": jnp.zeros((n_slots,), jnp.int32),
        }
    else:
        page = lambda: {
            "k": jnp.zeros(shape, dt),
            "v": jnp.zeros(shape, dt),
            "idx": jnp.zeros((n_slots,), jnp.int32),
        }
    return {f"block_{i}": page() for i in range(spec["n_layers"])}


def _quant_rows(x, block: int):
    """Blockwise-quantize the trailing ``n_kv_heads × d_head`` row of
    ``x`` — the EXACT op sequence of ``collectives.quantized.
    block_quantize`` (same scale formula, same round/clip/astype order)
    applied per block, so flattened codes/scales are byte-identical to
    the wire codec's. Returns ``(codes int8, x.shape)``-shaped codes and
    ``[..., r/block]`` f32 scales."""
    shape = x.shape
    r = shape[-2] * shape[-1]
    b = x.reshape(shape[:-2] + (r // block, block))
    amax = jnp.max(jnp.abs(b), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(x.dtype)
    q = jnp.clip(jnp.round(b / scale[..., None]), -127, 127)
    return q.astype(jnp.int8).reshape(shape), scale


def _dequant_rows(q, scale):
    """Inverse of :func:`_quant_rows`, mirroring ``block_dequantize``'s
    ops (``codes.astype(f32) * scale.astype(f32)``)."""
    shape = q.shape
    blocks = scale.shape[-1]
    b = q.reshape(shape[:-2] + (blocks, -1)).astype(jnp.float32)
    return (b * scale[..., None].astype(jnp.float32)).reshape(shape)


def cache_is_quantized(cache) -> bool:
    """True when ``cache`` holds int8-block pages."""
    return "k_q" in cache["block_0"]


def unpack_cache(cache):
    """PURE: int8-block pages → the f32 ``{"k", "v", "idx"}`` view every
    apply function computes against; identity for f32 pages. Called
    once at dispatch entry — attention reads dequantized values, the
    resident tree between dispatches stays int8."""
    if not cache_is_quantized(cache):
        return cache
    return {name: {"k": _dequant_rows(page["k_q"], page["k_s"]),
                   "v": _dequant_rows(page["v_q"], page["v_s"]),
                   "idx": page["idx"]}
            for name, page in cache.items()}


def repack_cache(old, new, start, count):
    """PURE quantize-on-commit: fold the f32 view ``new`` (an apply
    function's output) back into the resident pages ``old``, re-
    quantizing ONLY the columns the dispatch wrote; identity (returns
    ``new``) for f32 pages.

    ``start`` int32 ``[n_slots]`` — each slot's first written column
    (absolute cursor; the ring position is ``start % capacity``);
    ``count`` — columns written per slot (scalar or ``[n_slots]``; 0
    marks a slot the dispatch did not touch). The mask is exact: a
    column outside its slot's written window keeps its resident bytes
    verbatim, because ``quantize(dequantize(q, s))`` can move the scale
    by 1 ulp — requantizing untouched data would both drift values and
    break the exported-bytes == ``block_quantize`` identity."""
    if not cache_is_quantized(old):
        return new
    n_slots, capacity = old["block_0"]["k_q"].shape[:2]
    start = jnp.asarray(start, jnp.int32)
    count = jnp.broadcast_to(jnp.asarray(count, jnp.int32), (n_slots,))
    blk = (old["block_0"]["k_q"].size
           // old["block_0"]["k_s"].size)
    cols = jnp.arange(capacity, dtype=jnp.int32)[None]
    written = ((cols - start[:, None]) % capacity) < count[:, None]
    out = {}
    for name, page in old.items():
        leaves = {"idx": new[name]["idx"]}
        for kv in ("k", "v"):
            q, s = _quant_rows(new[name][kv], blk)
            leaves[kv + "_q"] = jnp.where(
                written[..., None, None], q, page[kv + "_q"])
            leaves[kv + "_s"] = jnp.where(
                written[..., None], s, page[kv + "_s"])
        out[name] = leaves
    return out


def decode_apply(model, params, cache, tokens):
    """PURE one-token step for every slot: tokens int32 ``[n_slots]`` →
    (logits ``[n_slots, vocab]``, advanced cache). The per-slot cursor
    vector doubles as ``pos_offset`` so learned positional embeddings
    index each slot's own depth."""
    dm = model if model.decode else model.clone(decode=True)
    cursors = cache["block_0"]["idx"]
    logits, upd = dm.apply(
        {"params": params, "cache": cache}, tokens[:, None],
        pos_offset=cursors, mutable=["cache"])
    return logits[:, 0], upd["cache"]


def prefill_apply(model, params, cache, tokens, lengths, slot_ids):
    """PURE cohort prefill: tokens int32 ``[S, L]`` (right-padded),
    lengths ``[S]``, slot_ids ``[S]`` (sentinel ``n_slots`` = padding
    row, dropped by the scatter). Runs the slab forward on a fresh
    ``[S, L]`` cache, scatters K/V into the pages, sets the cursors to
    ``lengths``, and returns (last-real-position logits ``[S, vocab]``,
    new cache)."""
    dm = model if model.decode else model.clone(decode=True)
    s, l = tokens.shape
    capacity = cache["block_0"]["k"].shape[1]
    if l > capacity:
        raise ValueError(
            f"prefill bucket length {l} exceeds page capacity {capacity}")
    spec = cache_spec(model)
    slab0 = {
        f"block_{i}": {
            "k": jnp.zeros((s, l, spec["n_kv_heads"], spec["d_head"]),
                           cache["block_0"]["k"].dtype),
            "v": jnp.zeros((s, l, spec["n_kv_heads"], spec["d_head"]),
                           cache["block_0"]["v"].dtype),
            "idx": jnp.zeros((), jnp.int32),
        } for i in range(spec["n_layers"])
    }
    logits, upd = dm.apply(
        {"params": params, "cache": slab0}, tokens, pos_offset=0,
        mutable=["cache"])
    last = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
    sid = jnp.asarray(slot_ids, jnp.int32)
    new_cache = {}
    for name, page in cache.items():
        slab = upd["cache"][name]
        new_cache[name] = {
            # mode='drop': the sentinel slot id (== n_slots) is
            # out of bounds, so padding rows vanish instead of clobbering
            # a live slot
            "k": page["k"].at[sid, :l].set(slab["k"], mode="drop"),
            "v": page["v"].at[sid, :l].set(slab["v"], mode="drop"),
            "idx": page["idx"].at[sid].set(
                jnp.asarray(lengths, jnp.int32), mode="drop"),
        }
    return last, new_cache


def prefill_chunk_apply(model, params, cache, tokens, starts, valid,
                        slot_ids):
    """PURE chunk prefill against the PAGED cache: tokens int32
    ``[S, C]`` (right-padded), starts ``[S]`` (absolute write offsets =
    each slot's current fill), valid ``[S]`` (real tokens in this
    chunk), slot_ids ``[S]`` (sentinel ``n_slots`` = padding row).

    Gathers the cohort's pages, runs the chunk forward with
    ``chunked_prefill=True`` (the slab attends the cached prefix plus
    itself — models/transformer.py), scatters the chunk's K/V back at
    ``[start, start+valid)`` (padding columns and sentinel rows drop),
    advances the cursors to ``start + valid``, and returns
    (last-real-position logits ``[S, vocab]``, new cache). No-wrap
    contract: prompts must fit the page (``prompt_len <= capacity``) —
    the engine enforces it at submit.
    """
    dm = (model if (model.decode and model.chunked_prefill)
          else model.clone(decode=True, chunked_prefill=True))
    s, c = tokens.shape
    n_slots, capacity = cache["block_0"]["k"].shape[:2]
    if c > capacity:
        raise ValueError(
            f"prefill chunk length {c} exceeds page capacity {capacity}")
    sid = jnp.asarray(slot_ids, jnp.int32)
    gid = jnp.clip(sid, 0, n_slots - 1)   # sentinels borrow row 0 (reads
    #                                       only — their writes drop)
    starts = jnp.asarray(starts, jnp.int32)
    valid = jnp.asarray(valid, jnp.int32)
    sub = {name: {"k": page["k"][gid], "v": page["v"][gid], "idx": starts}
           for name, page in cache.items()}
    logits, upd = dm.apply(
        {"params": params, "cache": sub}, tokens, pos_offset=starts,
        mutable=["cache"])
    last = jnp.take_along_axis(
        logits, jnp.clip(valid - 1, 0, c - 1)[:, None, None], axis=1)[:, 0]
    rows_i = jnp.arange(s)[:, None]
    cols = starts[:, None] + jnp.arange(c)[None]
    # padding columns point past the page end → mode='drop' eats them,
    # exactly like the sentinel slot id on the row axis
    cols = jnp.where(jnp.arange(c)[None] < valid[:, None], cols, capacity)
    gather_cols = jnp.clip(cols, 0, capacity - 1)
    new_cache = {}
    for name, page in cache.items():
        uk = upd["cache"][name]["k"][rows_i, gather_cols]
        uv = upd["cache"][name]["v"][rows_i, gather_cols]
        new_cache[name] = {
            "k": page["k"].at[sid[:, None], cols].set(uk, mode="drop"),
            "v": page["v"].at[sid[:, None], cols].set(uv, mode="drop"),
            "idx": page["idx"].at[sid].set(starts + valid, mode="drop"),
        }
    return last, new_cache


def decode_k_apply(model, params, cache, tokens, keys, temps, top_ks,
                   eos_ids, remaining, live, park, k):
    """PURE multi-token decode: ``k`` grid steps under one scan, sampling
    on device each step and feeding the result to the next.

    tokens ``[n]`` int32 (each live slot's latest token); keys
    ``[n, 2]`` uint32 per-slot PRNG state; temps/top_ks ``[n]`` sampling
    knobs (sampling.py encoding); eos_ids ``[n]`` int32 (< 0 → no eos);
    remaining ``[n]`` int32 token budget; live ``[n]`` bool; park
    ``[n]`` int32 — the real fill level of each NON-live slot (mid-
    prefill slots especially), pinned around the scan so the k garbage
    steps those rows ride along for cannot advance their cursors into a
    ring wrap over real prefix tokens.

    Returns ``(toks [n, k] int32 — -1 where the slot was not live,
    last_logits [n, vocab] f32, keys, cache)``. The -1 convention lets
    the host pull ONE int32 array per dispatch: validity is in-band.
    """
    dm = model if model.decode else model.clone(decode=True)
    tokens = jnp.asarray(tokens, jnp.int32)
    live = jnp.asarray(live, bool)
    park = jnp.asarray(park, jnp.int32)
    remaining = jnp.asarray(remaining, jnp.int32)
    eos_ids = jnp.asarray(eos_ids, jnp.int32)
    temps = jnp.asarray(temps, jnp.float32)
    top_ks = jnp.asarray(top_ks, jnp.int32)

    def pin(c):
        return {name: {**page, "idx": jnp.where(live, page["idx"], park)}
                for name, page in c.items()}

    cache = pin(cache)
    zeros = jnp.zeros((tokens.shape[0], dm.vocab), jnp.float32)

    def body(carry, _):
        cache, tok, keys, rem, alive, _last = carry
        logits, cache = decode_apply(dm, params, cache, tok)
        nxt, keys2 = sample_tokens(logits, keys, temps, top_ks)
        # only rows that really sampled consume a key split — the
        # per-request stream position is independent of k and neighbours
        keys = jnp.where(alive[:, None], keys2, keys)
        valid = alive
        rem = rem - valid.astype(jnp.int32)
        hit_eos = (nxt == eos_ids) & (eos_ids >= 0)
        alive = alive & ~hit_eos & (rem > 0)
        tok = jnp.where(valid, nxt, tok)
        out = jnp.where(valid, nxt, jnp.int32(-1))
        return (cache, tok, keys, rem, alive, logits), out

    (cache, _, keys, _, _, last), toks = jax.lax.scan(
        body, (cache, tokens, keys, remaining, live, zeros), None,
        length=k)
    cache = pin(cache)   # non-live cursors back to their real fill
    return toks.T, last, keys, cache


def _forms(paths) -> Optional[str]:
    """The forms a traced program's dispatched calls of one kind took
    (``record_paths``), as one string; None where it has no such call."""
    return ",".join(sorted(set(paths))) or None


class ServingStep:
    """The compiled prefill/decode pair, owning the paged cache.

    ``decode()`` is jitted ONCE with the cache buffers donated (the page
    updates alias in place — no copy of the multi-GiB cache per token)
    and, when a ``mesh`` is given, explicit NamedShardings: K/V pages
    sharded on the head axis over ``axis`` (the TP layout the training
    mesh uses) whenever ``n_kv_heads`` divides, everything else
    replicated. ``prefill()`` compiles one program per (cohort, bucket)
    shape — bucket lengths are the engine's admission policy; the
    per-shape jit cache plus the trace counters below make recompiles
    observable (``test_serving_contracts.py`` asserts decode traces == 1).
    """

    def __init__(self, model, params, n_slots: int, capacity: int, *,
                 cache_dtype: Any = None, mesh=None, axis: Optional[str] = None,
                 donate: bool = True, kv_dtype: Optional[str] = None):
        self.kv_dtype = _normalize_kv_dtype(kv_dtype)
        if self.kv_dtype == "int8-block" and mesh is not None:
            raise ValueError(
                "kv_dtype='int8-block' does not compose with mesh-sharded "
                "pages: the blockwise scales span the head axis; serve "
                "int8 pages unsharded or keep f32 pages under the mesh")
        self.src_model = model   # caller's layout: load_params converts from it
        self.n_slots = int(n_slots)
        self.capacity = int(capacity)
        self.params = self._init_pages(model, params, cache_dtype)
        #: bytes one slot holds across all its leaves (engine.admit's
        #: ``state_bytes`` attribute counts installs in this unit)
        self.slot_bytes = slot_bytes(self.cache)
        self.decode_traces = 0
        self.decode_k_traces = 0
        self.prefill_traces: Dict[tuple, int] = {}
        self.prefill_chunk_traces: Dict[tuple, int] = {}
        #: which form the chunk program's latent attention took when it was
        #: traced ("kernel" or "loop:<reason>", models/hybrid.py::
        #: latent_chunk_attention); None before a chunk program exists and
        #: for a model that has no such call
        self.chunk_attention: Optional[str] = None
        #: the same for the ``decode_k`` program's latent attention
        #: (latent_decode_attention); None before such a program exists
        #: and for a model that has no such call
        self.decode_attention: Optional[str] = None
        #: the same for the ``decode_k`` program's recurrent state step
        #: ("kernel" or "xla:<reason>", models/hybrid.py::kda_decode_step)
        self.state_step: Optional[str] = None
        self._prefill_jits: Dict[tuple, Any] = {}
        self._prefill_sampled_jits: Dict[tuple, Any] = {}
        self._prefill_chunk_jits: Dict[tuple, Any] = {}
        self._decode_k_jits: Dict[int, Any] = {}
        self.last_decode_logits = None   # device [n_slots, vocab] —
        #                                  engine's lazy debug/parity hook
        self.last_decode_stats = None    # what the model's last decode_k
        #                                  counted on the device, {name:
        #                                  scalar} (StateServingStep)
        self._mesh = mesh
        self._axis = axis
        donate_args = (1,) if donate else ()

        def _decode(params, cache, tokens):
            self.decode_traces += 1      # trace-time only: counts compiles
            with self._page_write():
                return self._decode_program(params, cache, tokens)

        kw = {}
        if mesh is not None:
            repl, cache_sh = self._shardings(mesh, axis)
            kw = dict(in_shardings=(repl, cache_sh, repl),
                      out_shardings=(repl, cache_sh))
        self.params = self.place(self.params)
        self.cache = self.place(self.cache, pages=True)
        self._decode_jit = jax.jit(_decode, donate_argnums=donate_args,
                                   **kw)
        self._decoded = False    # ``decode`` has dispatched its program
        self._donate = donate_args

    @property
    def no_wrap(self) -> Optional[str]:
        """Why ``prompt + max_new_tokens`` may not pass the capacity (None:
        the ring wraps); ``Engine.submit`` raises with it."""
        return ("int8-block pages forbid ring wrap"
                if self.kv_dtype == "int8-block" else None)

    # -- what names K/V (StateServingStep replaces these) --------------------
    def _init_pages(self, model, params, cache_dtype):
        """Set ``model``, ``dm``, ``dm_chunk`` and ``cache``; return the
        parameters in the layout the programs take."""
        _check_servable(model)
        if model.qkv_layout == "bhld":
            params = bhld_to_blhd_params(model, params)
            model = model.clone(qkv_layout="blhd")
        self.model = model
        self.dm = model.clone(decode=True)
        self.dm_chunk = self.dm.clone(chunked_prefill=True)
        self.cache = init_cache(model, self.n_slots, self.capacity,
                                cache_dtype, kv_dtype=self.kv_dtype)
        return params

    def _decode_program(self, params, cache, tokens):
        f32c = unpack_cache(cache)
        start = f32c["block_0"]["idx"]
        logits, f32c = decode_apply(self.dm, params, f32c, tokens)
        return logits, repack_cache(cache, f32c, start, 1)

    def _prefill_program(self, params, cache, tokens, lengths, slot_ids):
        f32c = unpack_cache(cache)
        last, f32c = prefill_apply(self.dm, params, f32c, tokens, lengths,
                                   slot_ids)
        start, count = self._scatter_window(slot_ids, 0, tokens.shape[1])
        return last, repack_cache(cache, f32c, start, count)

    def _prefill_chunk_program(self, params, cache, tokens, starts, valid,
                               slot_ids):
        f32c = unpack_cache(cache)
        last, f32c = prefill_chunk_apply(
            self.dm_chunk, params, f32c, tokens, starts, valid, slot_ids)
        w_start, w_count = self._scatter_window(slot_ids, starts, valid)
        return last, repack_cache(cache, f32c, w_start, w_count)

    def _prefill_sampled_program(self, params, cache, tokens, lengths,
                                 slot_ids, keys, temps, top_ks):
        last, cache = self._prefill_program(params, cache, tokens, lengths,
                                            slot_ids)
        sid = jnp.asarray(slot_ids, jnp.int32)
        gid = jnp.clip(sid, 0, self.n_slots - 1)
        tok, newk = sample_tokens(last, keys[gid], temps[gid], top_ks[gid])
        # sentinel rows (sid == n_slots) drop out of the key scatter —
        # their splits never touch a live slot's stream
        keys = keys.at[sid].set(newk, mode="drop")
        return tok, keys, cache

    #: outputs of ``_decode_k_program`` after the cache (replicated)
    _decode_k_extra = 0

    def _decode_k_program(self, params, cache, tokens, keys, temps, top_ks,
                          eos_ids, remaining, live, park, k):
        f32c = unpack_cache(cache)
        # every row writes k columns from its PINNED cursor — live rows
        # from idx, ride-along rows from park (their garbage stays beyond
        # their real fill)
        start = jnp.where(jnp.asarray(live, bool), f32c["block_0"]["idx"],
                          jnp.asarray(park, jnp.int32))
        toks, last, keys, f32c = decode_k_apply(
            self.dm, params, f32c, tokens, keys, temps, top_ks, eos_ids,
            remaining, live, park, k)
        return toks, last, keys, repack_cache(cache, f32c, start, k)

    def _page_write(self):
        """Trace-time scope of the programs that may hold a kernel over a
        page (the one-token programs' write, ops/page_write.py; the chunk
        program's latent attention, ops/latent_attention.py): pages split
        over several devices keep the partitionable form (a kernel is one
        custom call, which the partitioner cannot split). One device, mesh
        or not, takes the kernel."""
        return partitioned_pages(
            self._mesh is not None and self._mesh.size > 1)

    @contextlib.contextmanager
    def _first_call(self, program: str, key):
        """Around the FIRST dispatch of a program key: a
        ``program.first_call`` lifecycle span (tracing.py) that ends when
        the pages the program returned are there, so that the key's compile
        rows and its executable's first run lie inside. ``key`` is the
        shape the program is compiled for."""
        shape = key if isinstance(key, tuple) else (key,)
        with tracing.lifecycle_span("program.first_call", program=program,
                                    key="x".join(map(str, shape))):
            yield
            jax.block_until_ready(self.cache)

    def place(self, tree, pages: bool = False):
        """Commit parameters, pages or per-slot state to this step's
        mesh; identity without one. They live there from the start:
        arguments committed elsewhere (a trainer's chip 0) would be copied
        across on every dispatch, and a tree that moves onto the mesh
        between two calls changes type and retraces the program."""
        if self._mesh is None:
            return tree
        repl, cache_sh = self._shardings(self._mesh, self._axis)
        return jax.device_put(tree, cache_sh if pages else repl)

    def _shardings(self, mesh, axis):
        from jax.sharding import NamedSharding, PartitionSpec as P

        axis = axis or mesh.axis_names[0]
        nax = mesh.shape[axis]
        hkv = cache_spec(self.model)["n_kv_heads"]
        kv_spec = P(None, None, axis, None) if hkv % nax == 0 else P()
        repl = NamedSharding(mesh, P())
        page = {"k": NamedSharding(mesh, kv_spec),
                "v": NamedSharding(mesh, kv_spec),
                "idx": repl}
        cache_sh = {name: dict(page) for name in self.cache}
        return repl, cache_sh

    def _scatter_window(self, slot_ids, starts, counts):
        """Per-SLOT (start, count) written-column windows for a cohort
        scatter — the ``repack_cache`` mask inputs. Sentinel rows
        (``sid == n_slots``) drop out, so their slots' counts stay 0
        and their resident bytes are untouched (mirroring the f32
        path's ``mode='drop'`` exactly)."""
        sid = jnp.asarray(slot_ids, jnp.int32)
        zeros = jnp.zeros((self.n_slots,), jnp.int32)
        start = zeros.at[sid].set(
            jnp.broadcast_to(jnp.asarray(starts, jnp.int32), sid.shape),
            mode="drop")
        count = zeros.at[sid].set(
            jnp.broadcast_to(jnp.asarray(counts, jnp.int32), sid.shape),
            mode="drop")
        return start, count

    def cache_bytes(self) -> int:
        if self.kv_dtype == "int8-block":
            return cache_bytes(self.model, self.n_slots, self.capacity,
                               kv_dtype=self.kv_dtype)
        return cache_bytes(self.model, self.n_slots, self.capacity,
                           self.cache["block_0"]["k"].dtype)

    def cursors(self):
        """Device→host pull of the per-slot fill levels (debug/report)."""
        return jax.device_get(self.cache["block_0"]["idx"])

    def decode(self, tokens):
        """One token for every slot: tokens int ``[n_slots]`` → logits
        ``[n_slots, vocab]`` (f32, on device). Retired/free slots carry
        any token id; their rows are garbage and MUST be ignored — row
        independence keeps them from perturbing live slots (tested
        bitwise)."""
        tokens = jnp.asarray(tokens, jnp.int32)
        first, self._decoded = not self._decoded, True
        with (self._first_call("decode", self.n_slots) if first
              else tracing.OFF):
            logits, self.cache = self._decode_jit(
                self.params, self.cache, tokens)
        return logits

    def prefill(self, tokens, lengths, slot_ids):
        """Cohort prefill (see :func:`prefill_apply`); compiled per
        (S, L) shape with the cache donated, counted in
        ``prefill_traces``."""
        tokens = jnp.asarray(tokens, jnp.int32)
        key = tokens.shape
        first = key not in self._prefill_jits
        if first:
            def _prefill(params, cache, tokens, lengths, slot_ids,
                         _key=key):
                self.prefill_traces[_key] = (
                    self.prefill_traces.get(_key, 0) + 1)
                return self._prefill_program(params, cache, tokens, lengths,
                                             slot_ids)

            kw = {}
            if self._mesh is not None:
                repl, cache_sh = self._shardings(self._mesh, self._axis)
                kw = dict(
                    in_shardings=(repl, cache_sh, repl, repl, repl),
                    out_shardings=(repl, cache_sh))
            self._prefill_jits[key] = jax.jit(
                _prefill, donate_argnums=self._donate, **kw)
        with self._first_call("prefill", key) if first else tracing.OFF:
            logits, self.cache = self._prefill_jits[key](
                self.params, self.cache, tokens,
                jnp.asarray(lengths, jnp.int32),
                jnp.asarray(slot_ids, jnp.int32))
        return logits

    def decode_k(self, tokens, keys, temps, top_ks, eos_ids, remaining,
                 live, park, k: int):
        """``k`` decode steps + on-device sampling in ONE dispatch (see
        :func:`decode_k_apply`). Compiled once per ``k`` with the cache
        donated — ``decode_k_traces`` counts compiles (the DL108
        invariant extends here: any traffic mix at fixed ``k`` runs one
        program). Returns ``(toks [n, k] int32 device, new keys)``;
        the step's final logits stay ON DEVICE in
        ``self.last_decode_logits`` until somebody actually reads them.
        """
        kk = int(k)
        first = kk not in self._decode_k_jits
        if first:
            def _decode_k(params, cache, tokens, keys, temps, top_ks,
                          eos_ids, remaining, live, park, _k=kk):
                self.decode_k_traces += 1   # trace-time only
                with self._page_write(), record_paths() as paths, \
                        record_paths("state_step") as state_paths:
                    out = self._decode_k_program(
                        params, cache, tokens, keys, temps, top_ks,
                        eos_ids, remaining, live, park, _k)
                self.decode_attention = _forms(paths)
                self.state_step = _forms(state_paths)
                return out

            kw = {}
            if self._mesh is not None:
                repl, cache_sh = self._shardings(self._mesh, self._axis)
                kw = dict(
                    in_shardings=(repl, cache_sh) + (repl,) * 8,
                    out_shardings=(repl, repl, repl, cache_sh)
                    + (repl,) * self._decode_k_extra)
            self._decode_k_jits[kk] = jax.jit(
                _decode_k, donate_argnums=self._donate, **kw)
        with self._first_call("decode_k", kk) if first else tracing.OFF:
            toks, last, keys, self.cache, *extra = self._decode_k_jits[kk](
                self.params, self.cache, jnp.asarray(tokens, jnp.int32),
                keys, jnp.asarray(temps, jnp.float32),
                jnp.asarray(top_ks, jnp.int32),
                jnp.asarray(eos_ids, jnp.int32),
                jnp.asarray(remaining, jnp.int32),
                jnp.asarray(live, bool), jnp.asarray(park, jnp.int32))
        self.last_decode_logits = last
        self._keep_decode_extra(*extra)
        return toks, keys

    def _keep_decode_extra(self, stats=None):
        """What ``_decode_k_program`` returned after the cache."""
        if stats is not None:
            self.last_decode_stats = stats

    def prefill_sampled(self, tokens, lengths, slot_ids, keys, temps,
                        top_ks):
        """Cohort prefill + on-device first-token sampling: one dispatch
        returns ``(tok [S] int32 device, new keys)`` instead of shipping
        ``[S, vocab]`` logits to the host. Greedy rows are bit-identical
        to ``np.argmax`` over :meth:`prefill`'s logits (sampling.py).
        Compiled per (S, L) shape, counted in ``prefill_traces`` under
        the same (S, L) keys as the logits path — one program per
        bucket either way (the DL108 trace-table assertions carry over
        unchanged)."""
        tokens = jnp.asarray(tokens, jnp.int32)
        key = tokens.shape
        first = key not in self._prefill_sampled_jits
        if first:
            def _pf(params, cache, tokens, lengths, slot_ids, keys,
                    temps, top_ks, _key=key):
                self.prefill_traces[_key] = (
                    self.prefill_traces.get(_key, 0) + 1)
                return self._prefill_sampled_program(
                    params, cache, tokens, lengths, slot_ids, keys, temps,
                    top_ks)

            kw = {}
            if self._mesh is not None:
                repl, cache_sh = self._shardings(self._mesh, self._axis)
                kw = dict(in_shardings=(repl, cache_sh) + (repl,) * 6,
                          out_shardings=(repl, repl, cache_sh))
            self._prefill_sampled_jits[key] = jax.jit(
                _pf, donate_argnums=self._donate, **kw)
        with (self._first_call("prefill_sampled", key) if first
              else tracing.OFF):
            tok, keys, self.cache = self._prefill_sampled_jits[key](
                self.params, self.cache, tokens,
                jnp.asarray(lengths, jnp.int32),
                jnp.asarray(slot_ids, jnp.int32), keys,
                jnp.asarray(temps, jnp.float32),
                jnp.asarray(top_ks, jnp.int32))
        return tok, keys

    def prefill_chunk(self, tokens, starts, valid, slot_ids, final, keys,
                      temps, top_ks):
        """One fixed-shape prompt chunk for up to S slots (see
        :func:`prefill_chunk_apply`), sampling the first token on device
        for rows whose chunk is ``final``. Returns ``(tok [S] int32
        device — -1 for non-final rows, new keys)``. ONE compiled
        program per (S, C) shape regardless of prompt length — counted
        in ``prefill_chunk_traces``."""
        tokens = jnp.asarray(tokens, jnp.int32)
        key = tokens.shape
        first = key not in self._prefill_chunk_jits
        if first:
            def _pc(params, cache, tokens, starts, valid, slot_ids,
                    final, keys, temps, top_ks, _key=key):
                self.prefill_chunk_traces[_key] = (
                    self.prefill_chunk_traces.get(_key, 0) + 1)
                with self._page_write(), record_paths() as paths:
                    last, cache = self._prefill_chunk_program(
                        params, cache, tokens, starts, valid, slot_ids)
                self.chunk_attention = _forms(paths)
                sid = jnp.asarray(slot_ids, jnp.int32)
                gid = jnp.clip(sid, 0, self.n_slots - 1)
                tok, newk = sample_tokens(last, keys[gid], temps[gid],
                                          top_ks[gid])
                # only a COMPLETING chunk consumes its slot's key split:
                # the stream position depends on tokens sampled, never
                # on how many chunks the prompt was carved into
                adv = final & (sid < self.n_slots)
                keys = keys.at[sid].set(
                    jnp.where(adv[:, None], newk, keys[gid]), mode="drop")
                tok = jnp.where(final, tok, jnp.int32(-1))
                return tok, keys, cache

            kw = {}
            if self._mesh is not None:
                repl, cache_sh = self._shardings(self._mesh, self._axis)
                kw = dict(in_shardings=(repl, cache_sh) + (repl,) * 8,
                          out_shardings=(repl, repl, cache_sh))
            self._prefill_chunk_jits[key] = jax.jit(
                _pc, donate_argnums=self._donate, **kw)
        with self._first_call("prefill_chunk", key) if first else tracing.OFF:
            tok, keys, self.cache = self._prefill_chunk_jits[key](
                self.params, self.cache, tokens,
                jnp.asarray(starts, jnp.int32),
                jnp.asarray(valid, jnp.int32),
                jnp.asarray(slot_ids, jnp.int32),
                jnp.asarray(final, bool), keys,
                jnp.asarray(temps, jnp.float32),
                jnp.asarray(top_ks, jnp.int32))
        return tok, keys

    def export_slot(self, slot: int, fill: int) -> Dict[str, Dict[str, Any]]:
        """Pull one slot's populated KV rows to the host: ``{"block_i":
        {"k", "v"}}`` with each leaf ``[fill, n_kv_heads, d_head]`` in
        the cache dtype — the prefill→decode handoff payload
        (fleet/handoff.py). ``fill`` must not exceed the page (a wrapped
        ring has overwritten its prefix; re-prefill instead).

        int8-block pages export RESIDENT form instead: ``{"k_q", "k_s",
        "v_q", "v_s"}`` per block, codes ``[fill, n_kv_heads, d_head]``
        int8 + scales ``[fill, r/block]`` f32. ``fill · r`` is always a
        whole number of ``page_block(model)``-sized blocks, so the
        flattened pair is a valid ``block_dequantize`` payload and
        handoff wire formats 2/4 ship it VERBATIM — no dequantize→
        requantize round trip, zero extra quantization error. (The
        scales are shipped rather than recomputed deliberately: XLA may
        fold the codec's ``amax/127`` divide differently inside a jitted
        commit than the eager wire codec does, so a recompute can be
        1 ulp off the resident bytes.)"""
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} outside [0, {self.n_slots})")
        if not 0 < fill <= self.capacity:
            raise ValueError(
                f"fill {fill} outside (0, capacity={self.capacity}] — a "
                "wrapped slot cannot be exported")
        # Export IS the host pull: handoff serialization runs once per
        # migration, outside the per-token decode loop, and the payload
        # must be host bytes by contract.
        return self._export_rows(slot, fill)

    def _export_rows(self, slot, fill):
        if self.kv_dtype == "int8-block":
            return {  # dlint: disable=DL121 — sanctioned migration pull
                name: {leaf: np.asarray(page[leaf][slot, :fill])
                       for leaf in ("k_q", "k_s", "v_q", "v_s")}
                for name, page in self.cache.items()}
        return {  # dlint: disable=DL121 — sanctioned migration pull
            name: {"k": np.asarray(page["k"][slot, :fill]),
                   "v": np.asarray(page["v"][slot, :fill])}
            for name, page in self.cache.items()}

    def import_slot(self, slot: int, pages, cursor: int) -> None:
        """Inverse of :meth:`export_slot`: write handed-off KV rows into
        ``slot`` and set its cursor to ``cursor``. Raw-format handoffs
        round-trip BITWISE (same dtype, no value transform), so decode
        from an imported slot equals decode on the exporting engine.

        ``pages`` may hold f32 ``{"k", "v"}`` rows or int8-resident
        ``{"k_q", "k_s", "v_q", "v_s"}`` rows, and either lands in
        either page mode: resident→int8 adopts the codes verbatim
        (BITWISE, zero extra quantization error), resident→f32
        dequantizes once, f32→int8 quantizes once (the same single
        quantization a local commit pays)."""
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} outside [0, {self.n_slots})")
        if not 0 < cursor <= self.capacity:
            raise ValueError(
                f"cursor {cursor} outside (0, capacity={self.capacity}]")
        if set(pages) != set(self.cache):
            raise ValueError(
                "handoff pages do not match this model's cache layout: "
                f"got {sorted(pages)}, want {sorted(self.cache)}")
        self._import_rows(slot, pages, cursor)

    def _import_rows(self, slot, pages, cursor):
        resident = "k_q" in next(iter(pages.values()))
        blk = page_block(self.model)
        new_cache = {}
        for name, page in self.cache.items():
            if resident:
                rows = {leaf: jnp.asarray(pages[name][leaf])
                        for leaf in ("k_q", "k_s", "v_q", "v_s")}
            else:
                rows = {"k": jnp.asarray(pages[name]["k"]),
                        "v": jnp.asarray(pages[name]["v"])}
                want = (cursor,) + self._row_shape()
                if rows["k"].shape != want or rows["v"].shape != want:
                    raise ValueError(
                        f"handoff rows for {name} have shape "
                        f"{rows['k'].shape}, want {want}")
            if self.kv_dtype == "int8-block":
                if not resident:
                    # f32 rows into int8 pages: ONE quantization — the
                    # same cost a local commit would have paid
                    rows["k_q"], rows["k_s"] = _quant_rows(rows["k"], blk)
                    rows["v_q"], rows["v_s"] = _quant_rows(rows["v"], blk)
                new_cache[name] = {
                    **{leaf: page[leaf].at[slot, :cursor].set(
                        jnp.asarray(rows[leaf], page[leaf].dtype))
                       for leaf in ("k_q", "k_s", "v_q", "v_s")},
                    "idx": page["idx"].at[slot].set(jnp.int32(cursor)),
                }
            else:
                if resident:
                    # int8-resident rows into f32 pages: dequantize once
                    rows["k"] = _dequant_rows(rows["k_q"], rows["k_s"])
                    rows["v"] = _dequant_rows(rows["v_q"], rows["v_s"])
                new_cache[name] = {
                    "k": page["k"].at[slot, :cursor].set(
                        jnp.asarray(rows["k"], page["k"].dtype)),
                    "v": page["v"].at[slot, :cursor].set(
                        jnp.asarray(rows["v"], page["v"].dtype)),
                    "idx": page["idx"].at[slot].set(jnp.int32(cursor)),
                }
        self.cache = new_cache

    def _row_shape(self):
        spec = cache_spec(self.model)
        return (spec["n_kv_heads"], spec["d_head"])

    def load_params(self, params):
        """Swap weights in place (warm restart / rolling update —
        serving/weights.py, fleet/rollout.py). ``params`` is in the
        CALLER's layout, the same one ``__init__`` received; a bhld
        source is converted exactly as construction did. No recompile:
        params are per-call arguments to every jitted program."""
        if self.src_model.qkv_layout == "bhld":
            params = bhld_to_blhd_params(self.src_model, params)
        self.params = self.place(params)

    def reset(self):
        """Zero every page and cursor (all slots freed)."""
        dt = (None if self.kv_dtype == "int8-block"
              else self.cache["block_0"]["k"].dtype)
        self.cache = self.place(init_cache(
            self.model, self.n_slots, self.capacity, dt,
            kv_dtype=self.kv_dtype), pages=True)
