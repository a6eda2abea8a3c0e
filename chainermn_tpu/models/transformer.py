"""Decoder-only Transformer LM — the long-context flagship.

Beyond-reference model family: the reference's sequence model is an LSTM
seq2seq (examples/seq2seq, SURVEY.md §2.6 records sequence parallelism as
absent upstream). This LM is where the rebuild's long-context machinery
composes into one model:

* **flash attention** (`ops.flash_attention`) — the Pallas fused kernel —
  as the default attention;
* **ring attention** (`parallel.ring_attention`) when the sequence axis is
  sharded over the mesh (``attention='ring'`` + ``seq_axis``): KV blocks
  rotate over the ICI ring via ``ppermute``, sequence length scales with
  the number of chips;
* **expert-parallel MoE FFN** (`parallel.ExpertParallelMLP`) when
  ``moe_experts_per_device > 0``: the FFN becomes a Switch layer with
  experts sharded over ``expert_axis``.

Plain usage (no sharded axes) is a standard pre-LN causal LM usable under
``pjit`` data parallelism; the sharded variants run under ``shard_map``.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from chainermn_tpu.ops.flash_attention import (DEFAULT_BLOCKS,
                                               flash_attention)
from chainermn_tpu.parallel.expert_parallel import ExpertParallelMLP
from chainermn_tpu.parallel.ring_attention import (
    local_attention_reference,
    ring_attention,
    ring_flash_attention,
)
from chainermn_tpu.parallel.tensor_parallel import (
    ColumnParallelDense,
    RowParallelDense,
    TensorParallelMLP,
    pmax_stop_gradient,
    vocab_parallel_cross_entropy,
)
from chainermn_tpu.parallel.ulysses import ulysses_attention
from chainermn_tpu.ops import latent_attention, page_attention
from chainermn_tpu.ops.page_write import write_rows
from chainermn_tpu.ops.rotary import apply_rope, apply_rope_bhld

__all__ = ["TransformerLM", "TransformerBlock", "generate",
           "lm_loss_with_aux", "tp_lm_loss", "bhld_to_blhd_params"]


def _grouped_cache_attention(q, kpage, vpage, row, window):
    """One query per row over the whole cache page, the page read once at
    the dtype it is stored in: the plain ``jax.numpy`` form of the decode
    step's attention, which reads every slot's CAPACITY whatever its fill.
    :func:`cache_decode_attention` takes it wherever the kernel that reads
    the filled blocks alone (``ops/page_attention.py::page_decode_fwd``)
    cannot serve, and it is that kernel's oracle: this docstring is the
    arithmetic both are held to.

    q ``[b, n_heads, d]``; kpage/vpage ``[b, cap, h_kv, d]`` (ring pages:
    slot j of a row holds the newest position ≡ j mod cap); row ``[b]``
    per-slot cursors or ``()`` — the position of the query. The query
    heads fold to ``[b, h_kv, n_heads // h_kv, d]`` and contract against
    the ``h_kv`` heads the page has: no widened K or V exists, and MHA is
    the ``r == 1`` case of the same code. Both contractions are matmuls
    with f32 accumulation; scores, mask and softmax are f32; p is rounded
    to the page's dtype for p·V as the flash kernel rounds it for prefill
    (ops/flash_attention.py ``_fa_kernel``), so prefill and decode of one
    model run at one precision. f32 pages (int8-block pages arrive
    unpacked to f32) keep f32 products: the MXU's default would round
    them to bf16. Returns ``[b, n_heads, d]`` f32.
    """
    b, cap, hkv, dh = kpage.shape
    exact = (jax.lax.Precision.HIGHEST if kpage.dtype == jnp.float32
             else None)
    qg = q.reshape(b, hkv, -1, dh).astype(kpage.dtype)
    s = jnp.einsum("bgrd,bkgd->bgrk", qg, kpage, precision=exact,
                   preferred_element_type=jnp.float32) * dh ** -0.5
    # the ring inversion and window of the reference branch, unchanged
    kpos = row[..., None] - (row[..., None] - jnp.arange(cap)) % cap
    visible = kpos >= 0
    if window is not None:
        visible &= kpos > row[..., None] - window
    vis = visible[:, None, None] if jnp.ndim(row) else visible
    p = jax.nn.softmax(jnp.where(vis, s, -jnp.inf), -1)
    att = jnp.einsum("bgrk,bkgd->bgrd", p.astype(vpage.dtype), vpage,
                     precision=exact, preferred_element_type=jnp.float32)
    return att.reshape(b, -1, dh)


def cache_decode_attention(q, kpage, vpage, row, window):
    """The decode step's attention of every model that did not ask for the
    reference: :func:`_grouped_cache_attention`'s arguments, result and
    arithmetic, in one of two forms chosen here at trace time by what the
    call shows (``ops/page_attention.py::decode_refusal``; the choice is
    noted for whoever traces the program, ``record_paths``, as ``"kernel"``
    or ``"xla:<reason>"`` — the serving step puts it on
    ``engine.decode.enqueue`` as ``decode_attention``):

    * on a TPU, with bfloat16 or float32 pages that one device holds, a
      cache row ``[h_kv, d_head]`` of whole memory tiles 128 lanes wide, a
      capacity in whole blocks and no window: ONE Pallas kernel a layer
      (``page_decode_fwd``) that reads, of each slot, the blocks of columns
      its cursor reaches and no others — a parked slot one block, a slot
      past the capacity all of them. Its softmax is online over the blocks,
      so it agrees with the other form within rounding, not bitwise;
    * else :func:`_grouped_cache_attention`, which reads every slot's
      capacity: off a TPU (every CPU test and example), ``d_head`` 64
      (``gpt2-medium``, ``chip_smoke.py``), one KV head in bfloat16 or 3, 6,
      12 of them, pages split over a mesh, an ``attention_window`` (no cell
      serves a windowed dense model). int8-block pages arrive here unpacked
      to float32 and are served as float32 pages are."""
    refusal = page_attention.decode_refusal(q, kpage, window)
    latent_attention.note_path(
        "kernel" if refusal is None else f"xla:{refusal}")
    if refusal is None:
        return page_attention.page_decode_fwd(q, kpage, vpage, row)
    return _grouped_cache_attention(q, kpage, vpage, row, window)


class TransformerBlock(nn.Module):
    """Pre-LN block: causal attention + (dense | MoE) FFN.

    ``decode=True`` PRECONDITION: a multi-token apply (l > 1) is a PREFILL
    and, by default, requires an EMPTY cache — it attends only within the
    slab, so any previously cached tokens would be silently ignored
    (``pos`` is traced and cannot be asserted). ``generate()`` follows
    this contract.

    ``chunked_prefill=True`` lifts that restriction for the serving
    layer: an l > 1 apply at pos > 0 writes the slab at its true cache
    positions and attends over the FULL cache (prefix + slab) under an
    absolute-position causal mask, so a prompt can stream in as
    fixed-size chunks (serving/kv_cache.py::prefill_chunk_apply). The
    chunked contract assumes NO ring wrap during prefill (prompt length
    <= capacity — cache slot j holds absolute position j); garbage
    beyond each row's fill level is masked out, not read.

    The one-token step (``l == 1``) attends in one of three ways.
    ``attention="reference"`` keeps its own branch, bitwise a row of the
    full forward (the tests' oracle). Every other model goes through
    :func:`cache_decode_attention`, which picks at trace time from what the
    call shows: ONE Pallas kernel a layer that reads the blocks of columns
    each slot's cursor reaches (on a TPU, cache rows of whole memory tiles,
    pages one device holds, no window), else
    :func:`_grouped_cache_attention` over the whole page. One arithmetic,
    results within rounding of each other (docs/serving.md §Numerics).
    """

    d_model: int
    n_heads: int
    d_ff: int
    n_kv_heads: Optional[int] = None   # < n_heads → GQA/MQA (flash path)
    dtype: Any = jnp.float32
    # 'flash' | 'ring' | 'ring_flash' | 'ulysses' | 'reference'
    attention: str = "flash"
    attention_window: Optional[int] = None  # sliding window (flash path)
    attention_blocks: Optional[tuple] = None  # (block_q, block_k) tune
    pos_emb: str = "learned"           # 'learned' (handled by the LM) | 'rope'
    rope_theta: float = 10000.0
    seq_axis: Optional[str] = None     # mesh axis for 'ring'
    tp_axis: Optional[str] = None      # Megatron-style intra-op TP axis
    moe_experts_per_device: int = 0
    expert_axis: str = "expert"
    capacity_factor: float = 1.25
    moe_top_k: int = 1                 # 1 = Switch, 2 = GShard top-2
    decode: bool = False               # single-token KV-cache decoding
    chunked_prefill: bool = False      # l > 1 decode applies may start at
    #                                    pos > 0 and attend prefix + slab
    #                                    (serving chunk path; see docstring)
    max_len: int = 2048                # cache capacity when decode=True
    qkv_layout: str = "blhd"           # 'bhld': head-major attention
    #                                    tensors end to end — projection
    #                                    einsums emit [B, H, L, D], the
    #                                    flash kernels consume it as a free
    #                                    reshape, and the ~15 ms/step of
    #                                    layout-pivot copies disappear
    #                                    (docs/lm_roofline.md §5; flash
    #                                    path only, no decode/tp)

    @nn.compact
    def __call__(self, x, pos_offset=0):
        b, l, d = x.shape
        dh = self.d_model // self.n_heads

        h = nn.LayerNorm(dtype=self.dtype)(x)
        hkv = self.n_kv_heads or self.n_heads
        if self.qkv_layout == "bhld":
            x = self._bhld_attention(x, h, b, l, d, dh, hkv, pos_offset)
            return self._ffn(x, b, l, d)
        n_heads, n_kv = self.n_heads, hkv  # per-shard head counts below
        if self.tp_axis is not None:
            # Megatron attention: heads sharded over the model axis —
            # column-parallel QKV (no collective), per-shard attention on
            # local heads, row-parallel out projection (one psum)
            if self.decode or self.moe_experts_per_device > 0:
                raise ValueError(
                    "tp_axis does not compose with decode or the MoE FFN")
            if self.attention not in ("flash", "reference"):
                raise ValueError(
                    "tp_axis supports the 'flash'/'reference' attention "
                    "paths")
            ntp = jax.lax.axis_size(self.tp_axis)
            if self.n_heads % ntp or hkv % ntp:
                raise ValueError(
                    f"heads ({self.n_heads}/{hkv}) must divide by the "
                    f"'{self.tp_axis}' axis size ({ntp})")
            n_heads, n_kv = self.n_heads // ntp, hkv // ntp
            q = ColumnParallelDense(self.d_model, self.tp_axis,
                                    use_bias=False, dtype=self.dtype,
                                    name="q_proj")(h)
            kv = ColumnParallelDense(2 * hkv * dh, self.tp_axis,
                                     use_bias=False, dtype=self.dtype,
                                     name="kv_proj")(h)
            k, v = jnp.split(kv, 2, axis=-1)
        elif hkv == self.n_heads:
            qkv = nn.Dense(3 * self.d_model, use_bias=False,
                           dtype=self.dtype, name="qkv")(h)
            q, k, v = jnp.split(qkv, 3, axis=-1)
        else:  # GQA/MQA: smaller KV projection
            if self.attention not in ("flash", "reference"):
                raise ValueError(
                    "n_kv_heads < n_heads is supported on the 'flash' and "
                    "'reference' attention paths")
            q = nn.Dense(self.d_model, use_bias=False, dtype=self.dtype,
                         name="q_proj")(h)
            kv = nn.Dense(2 * hkv * dh, use_bias=False, dtype=self.dtype,
                          name="kv_proj")(h)
            k, v = jnp.split(kv, 2, axis=-1)
        q = q.reshape(b, l, n_heads, dh)
        k = k.reshape(b, l, n_kv, dh)
        v = v.reshape(b, l, n_kv, dh)
        if self.decode:
            # KV-cache step: x is a slab of l NEW tokens starting at the
            # cache fill level — l == 1 is autoregressive decoding, l > 1
            # is PREFILL (the whole prompt in one forward pass instead of
            # one sequential step per prompt token). Attention is a
            # [l, cached] product with causal masking inside the slab.
            if self.moe_experts_per_device > 0:
                raise ValueError(
                    "decode does not support this block's MoE FFN: it is "
                    "the Switch/GShard TRAINING layer (parallel/"
                    "expert_parallel.py — capacity factor, dropped "
                    "overflow, all-to-all), which no decode path runs. "
                    "Expert models decode and serve as models/hybrid.py's "
                    "HybridLM, whose feed-forward keeps every token "
                    "(parallel/expert_share.py)")
            ck = self.variable("cache", "k", jnp.zeros,
                               (b, self.max_len, hkv, dh), self.dtype)
            cv = self.variable("cache", "v", jnp.zeros,
                               (b, self.max_len, hkv, dh), self.dtype)
            idx = self.variable("cache", "idx",
                                lambda: jnp.zeros((), jnp.int32))
            # capacity comes from the SUPPLIED cache, not max_len: the
            # serving layer passes smaller ring-buffered pages
            # (serving/kv_cache.py) and writes wrap at `cap`
            cap = ck.value.shape[1]
            pos = idx.value
            # scalar cursor: generate()'s one-stream-per-row contract.
            # vector cursor [b]: serving slots — every row advances its
            # own position independently (continuous batching)
            per_slot = jnp.ndim(pos) == 1
            rows = (pos[:, None] if per_slot else pos) + jnp.arange(l)
            if self.pos_emb == "rope":
                q = apply_rope(q, rows, self.rope_theta)
                k = apply_rope(k, rows, self.rope_theta)
            start = pos % cap
            if self.chunked_prefill:
                # per-position scatter, not dynamic_update_slice: a chunk
                # whose window overhangs the page end would be CLAMPED to
                # cap - l and land at the wrong offset. Overhanging rows
                # (final-chunk padding — no wrap during prefill) drop.
                wrows = rows if per_slot else rows[None]
                safe = jnp.where(wrows < cap, wrows, cap)
                bidx = jnp.arange(b)[:, None]
                ck.value = ck.value.at[bidx, safe].set(
                    k.astype(self.dtype), mode="drop")
                cv.value = cv.value.at[bidx, safe].set(
                    v.astype(self.dtype), mode="drop")
            elif per_slot:
                # l == 1 is every serving engine's decode step: one row
                # per slot at its own cursor, one in-place kernel for K
                # and V where ops/page_write.py can serve; else, and for
                # an l > 1 slab, vmap(dynamic_update_slice). Same bytes.
                with jax.named_scope("cache_write"):
                    ck.value, cv.value = write_rows(
                        ck.value, cv.value, k.astype(self.dtype),
                        v.astype(self.dtype), start)
            else:
                ck.value = jax.lax.dynamic_update_slice(
                    ck.value, k.astype(self.dtype), (0, start, 0, 0))
                cv.value = jax.lax.dynamic_update_slice(
                    cv.value, v.astype(self.dtype), (0, start, 0, 0))
            idx.value = pos + l
            if l > 1:
                # PREFILL slab. Default contract: nothing precedes it
                # (the cache starts empty), so attention is causal
                # self-attention over the slab itself. Flash path: no
                # dense [l, max_len] scores and no full-cache read — a
                # 32k-token prompt prefills at the training path's
                # memory cost. Reference models keep the reference
                # kernel so prefill logits are THE SAME PROGRAM as the
                # full forward (bitwise — the serving parity tests
                # depend on it).
                if self.chunked_prefill:
                    # CHUNKED prefill: the slab (already written above at
                    # its absolute positions) attends over the FULL cache
                    # — prefix + itself — under an absolute-position
                    # causal mask. Same einsum forms, scale, and f32
                    # casts as local_attention_reference: the only delta
                    # vs the monolithic slab is extra key lanes that are
                    # masked to exactly-zero softmax weight, which the
                    # zero-lane-absorption property (test_decode_bitwise)
                    # makes bitwise-invisible — chunked == monolithic,
                    # token for token AND cache byte for cache byte.
                    with jax.named_scope("attend_cache"):
                        kc = ck.value.astype(jnp.float32)
                        vc = cv.value.astype(jnp.float32)
                        if hkv != self.n_heads:
                            kc = jnp.repeat(kc, self.n_heads // hkv, axis=2)
                            vc = jnp.repeat(vc, self.n_heads // hkv, axis=2)
                        s = jnp.einsum("bqhd,bkhd->bhqk",
                                       q.astype(jnp.float32), kc) * dh ** -0.5
                        keys = jnp.arange(cap)
                        # no-wrap contract: cache slot j holds absolute
                        # position j, so causality is just keys <= row; rows
                        # beyond each slot's fill hold garbage but only
                        # padding queries (ignored downstream) can see them
                        visible = keys <= rows[..., None]
                        if self.attention_window is not None:
                            visible &= keys > (rows[..., None]
                                               - self.attention_window)
                        vis = (visible[:, None] if per_slot
                               else visible[None, None])
                        s = jnp.where(vis, s, -jnp.inf)
                        att = jnp.einsum("bhqk,bkhd->bqhd",
                                         jax.nn.softmax(s, -1),
                                         vc).astype(q.dtype)
                elif self.attention == "reference":
                    kr, vr = k, v
                    if hkv != self.n_heads:
                        kr = jnp.repeat(kr, self.n_heads // hkv, axis=2)
                        vr = jnp.repeat(vr, self.n_heads // hkv, axis=2)
                    att = local_attention_reference(q, kr, vr, causal=True)
                else:
                    bq, bk = self.attention_blocks or DEFAULT_BLOCKS
                    att = flash_attention(q, k, v, causal=True, block_q=bq,
                                          block_k=bk,
                                          window=self.attention_window)
            elif self.attention != "reference":
                # every model that did not ask for the oracle: the page is
                # read at its stored dtype, grouped, on the MXU — the
                # filled blocks by one kernel where the call's shapes let
                # it, else all of it (cache_decode_attention picks) —
                # logits within tolerance of the float32 reference, NOT
                # bitwise a row of the full forward (docs/serving.md
                # §numerics; the branch below keeps that contract and
                # shares no arithmetic with this one)
                with jax.named_scope("attend_cache"):
                    att = cache_decode_attention(
                        q[:, 0], ck.value, cv.value, rows[..., -1],
                        self.attention_window)[:, None]
            else:
                with jax.named_scope("attend_cache"):
                    kc = ck.value.astype(jnp.float32)
                    vc = cv.value.astype(jnp.float32)
                    if hkv != self.n_heads:
                        kc = jnp.repeat(kc, self.n_heads // hkv, axis=2)
                        vc = jnp.repeat(vc, self.n_heads // hkv, axis=2)
                    # squeezed-q contractions: on XLA these are bitwise-equal
                    # to the corresponding row of the full-forward [L, L]
                    # attention; the q=1 "bqhd,bkhd->bhqk"/"bhqk,bkhd->bqhd"
                    # pair is NOT (different reduction order). The serving
                    # bitwise-parity guarantee lives or dies here —
                    # docs/serving.md §numerics.
                    s = jnp.einsum("bhd,bkhd->bhk",
                                   q[:, 0].astype(jnp.float32),
                                   kc) * dh ** -0.5
                    row = rows[..., -1]              # [b] per-slot, else ()
                    keys = jnp.arange(cap)
                    # ring inversion: slot j holds token position
                    # row - ((row - j) mod cap) — the newest position ≡ j
                    # (mod cap) not exceeding row. Unwritten slots land
                    # negative; wrapped-over history is unreachable by
                    # construction. With cap == max_len and no wrap this
                    # reduces exactly to the old `keys <= row` mask.
                    kpos = row[..., None] - (row[..., None] - keys) % cap
                    visible = kpos >= 0
                    if self.attention_window is not None:
                        visible &= kpos > (row[..., None]
                                           - self.attention_window)
                    vis = visible[:, None] if per_slot else visible[None, None]
                    s = jnp.where(vis, s, -jnp.inf)
                    att = jnp.einsum("bhk,bkhd->bhd",
                                     jax.nn.softmax(s, -1), vc)[:, None]
            # falls through to the SHARED projection/FFN tail below — the
            # decode path must never duplicate training-path math
        elif self.pos_emb == "rope":
            po = jnp.asarray(pos_offset)
            # scalar offset (sequence parallelism) or per-row [b] offset
            # (serving full-forward audit) — both yield global positions
            pos = (po[:, None] if po.ndim else po) + jnp.arange(l)
            q = apply_rope(q, pos, self.rope_theta)
            k = apply_rope(k, pos, self.rope_theta)
        if self.decode:
            pass  # att computed above from the KV cache
        elif (self.attention_window is not None
              and self.attention != "flash"):
            raise ValueError(
                "attention_window is supported on the 'flash' path")
        elif self.attention in ("ring", "ring_flash", "ulysses"):
            if self.seq_axis is None:
                raise ValueError(
                    f"attention={self.attention!r} requires seq_axis")
            seq_fn = {"ring": ring_attention,
                      "ring_flash": ring_flash_attention,
                      "ulysses": ulysses_attention}[self.attention]
            att = seq_fn(q, k, v, axis_name=self.seq_axis, causal=True)
        elif self.attention == "flash":
            bq, bk = self.attention_blocks or DEFAULT_BLOCKS
            att = flash_attention(q, k, v, causal=True, block_q=bq,
                                  block_k=bk, window=self.attention_window)
        else:
            if hkv != self.n_heads:
                k = jnp.repeat(k, self.n_heads // hkv, axis=2)
                v = jnp.repeat(v, self.n_heads // hkv, axis=2)
            att = local_attention_reference(q, k, v, causal=True)
        att = att.reshape(b, l, -1).astype(self.dtype)  # local heads if TP
        if self.tp_axis is not None:
            x = x + RowParallelDense(self.d_model, self.tp_axis,
                                     use_bias=False, dtype=self.dtype,
                                     name="attn_out")(att)
        else:
            x = x + nn.Dense(self.d_model, use_bias=False, dtype=self.dtype,
                             name="attn_out")(att)
        return self._ffn(x, b, l, d)

    def _bhld_attention(self, x, h, b, l, d, dh, hkv, pos_offset):
        """Head-major attention: projections emit [B, H, L, Dh] directly
        (XLA folds the permutation into the matmul — measured free,
        2026-07-31), the flash kernel consumes/produces that layout with
        zero-cost reshapes, and the output projection contracts (h, e)
        straight back to [B, L, D]. No transpose copy exists anywhere on
        the attention path, forward or backward."""
        if (self.decode or self.tp_axis is not None
                or self.attention != "flash"):
            raise ValueError(
                "qkv_layout='bhld' supports the plain flash attention "
                "path (no decode, no tp_axis); use the default 'blhd' "
                "layout elsewhere")
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=0)
        hdt = h.astype(self.dtype)
        if hkv == self.n_heads:
            w = self.param("qkv_bhld", init,
                           (d, 3, self.n_heads, dh), jnp.float32)
            y = jnp.einsum("bld,dthe->tbhle", hdt, w.astype(self.dtype))
            q, k, v = y[0], y[1], y[2]
        else:
            wq = self.param("q_bhld", init,
                            (d, self.n_heads, dh), jnp.float32)
            wkv = self.param("kv_bhld", init,
                             (d, 2, hkv, dh), jnp.float32)
            q = jnp.einsum("bld,dhe->bhle", hdt, wq.astype(self.dtype))
            ykv = jnp.einsum("bld,dthe->tbhle", hdt,
                             wkv.astype(self.dtype))
            k, v = ykv[0], ykv[1]
        if self.pos_emb == "rope":
            po = jnp.asarray(pos_offset)
            pos = (po[:, None] if po.ndim else po) + jnp.arange(l)
            q = apply_rope_bhld(q, pos, self.rope_theta)
            k = apply_rope_bhld(k, pos, self.rope_theta)
        bq, bk = self.attention_blocks or DEFAULT_BLOCKS
        att = flash_attention(q, k, v, causal=True, block_q=bq,
                              block_k=bk, window=self.attention_window,
                              layout="bhld")
        wo = self.param("attn_out_bhld", nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=(0, 1)),
            (self.n_heads, dh, d), jnp.float32)
        return x + jnp.einsum("bhle,hed->bld", att.astype(self.dtype),
                              wo.astype(self.dtype))

    def _ffn(self, x, b, l, d):
        h = nn.LayerNorm(dtype=self.dtype)(x)
        if self.tp_axis is not None:
            x = x + TensorParallelMLP(self.d_ff, self.d_model, self.tp_axis,
                                      dtype=self.dtype, name="tp_ffn")(h)
        elif self.moe_experts_per_device > 0:
            y, aux = ExpertParallelMLP(
                hidden=self.d_ff,
                experts_per_device=self.moe_experts_per_device,
                axis_name=self.expert_axis,
                capacity_factor=self.capacity_factor,
                top_k=self.moe_top_k,
                dtype=self.dtype, name="moe",
            )(h.reshape(b * l, d))
            # surfaced through the 'losses' collection; see lm_loss_with_aux
            self.sow("losses", "moe_aux", aux,
                     reduce_fn=lambda a, b_: a + b_, init_fn=lambda: 0.0)
            x = x + y.reshape(b, l, d)
        else:
            y = nn.Dense(self.d_ff, dtype=self.dtype, name="ffn_in")(h)
            y = nn.gelu(y)
            x = x + nn.Dense(self.d_model, dtype=self.dtype,
                             name="ffn_out")(y)
        return x


class TransformerLM(nn.Module):
    """Causal LM: tokens [B, L] → logits [B, L, vocab] (fp32).

    ``pos_offset`` supports sequence parallelism: with tokens sharded on a
    mesh axis, each shard passes its global position offset
    (``axis_index * L_local``) so positional embeddings stay global.
    """

    vocab: int
    d_model: int = 256
    n_heads: int = 8
    n_kv_heads: Optional[int] = None   # < n_heads → GQA/MQA
    n_layers: int = 4
    d_ff: int = 1024
    max_len: int = 2048
    pos_emb: str = "learned"           # 'learned' | 'rope'
    rope_theta: float = 10000.0
    attention_window: Optional[int] = None
    attention_blocks: Optional[tuple] = None
    dtype: Any = jnp.float32
    attention: str = "flash"
    seq_axis: Optional[str] = None
    tp_axis: Optional[str] = None      # Megatron intra-op TP (see block)
    lm_head_tp: bool = False           # column-parallel head: returns
    #                                    VOCAB-SHARDED logits; consume with
    #                                    vocab_parallel_cross_entropy (the
    #                                    full [B, L, V] never materializes)
    moe_experts_per_device: int = 0
    expert_axis: str = "expert"
    capacity_factor: float = 1.25
    moe_top_k: int = 1                 # 1 = Switch, 2 = GShard top-2
    decode: bool = False               # single-token KV-cache decoding
    chunked_prefill: bool = False      # serving chunk path (see block)
    qkv_layout: str = "blhd"           # 'bhld': pivot-free head-major
    #                                    attention (see TransformerBlock)
    remat: bool = False                # rematerialize each block's
    #                                    activations in backward (trade
    #                                    FLOPs for HBM at long L)
    return_hidden: bool = False        # skip the head: return the final
    #                                    post-LN hidden states (the fused
    #                                    head+CE loss applies lm_head
    #                                    itself — ops/fused_ce.py)

    def block_config(self) -> dict:
        """The per-layer TransformerBlock constructor kwargs — ONE source
        of truth shared by ``__call__`` and
        :func:`make_lm_fsdp_scan_loss` (a field added here reaches both;
        hand-copied kwargs in two sites silently diverged otherwise)."""
        return dict(
            d_model=self.d_model, n_heads=self.n_heads, d_ff=self.d_ff,
            n_kv_heads=self.n_kv_heads, dtype=self.dtype,
            attention=self.attention,
            attention_window=self.attention_window,
            attention_blocks=self.attention_blocks,
            pos_emb=self.pos_emb, rope_theta=self.rope_theta,
            seq_axis=self.seq_axis, tp_axis=self.tp_axis,
            moe_experts_per_device=self.moe_experts_per_device,
            expert_axis=self.expert_axis,
            capacity_factor=self.capacity_factor,
            moe_top_k=self.moe_top_k, decode=self.decode,
            chunked_prefill=self.chunked_prefill,
            max_len=self.max_len, qkv_layout=self.qkv_layout)

    @nn.compact
    def __call__(self, tokens, pos_offset=0):
        b, l = tokens.shape
        emb = nn.Embed(self.vocab, self.d_model,
                       dtype=self.dtype, name="tok_emb")(tokens)
        if self.pos_emb == "learned":
            pos = self.param(
                "pos_emb", nn.initializers.normal(0.02),
                (self.max_len, self.d_model))
            po = jnp.asarray(pos_offset)
            # scalar offset → one shared position row (broadcast over b);
            # vector [b] offset → per-row positions (serving slots sit at
            # independent depths). take() clips out-of-range indices,
            # which only retired/idle slots ever produce.
            idx = (po[:, None] if po.ndim else po) + jnp.arange(l)
            pe = jnp.take(pos, idx, axis=0).astype(self.dtype)
            x = emb + (pe if po.ndim else pe[None])
        else:  # 'rope': positions enter inside each block's attention
            x = emb
        block_cls = (nn.remat(TransformerBlock)
                     if self.remat and not self.decode else TransformerBlock)
        for i in range(self.n_layers):
            x = block_cls(**self.block_config(),
                          name=f"block_{i}")(x, pos_offset=pos_offset)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        if self.return_hidden:
            return x
        if self.lm_head_tp:
            if self.tp_axis is None:
                raise ValueError("lm_head_tp requires tp_axis")
            logits = ColumnParallelDense(
                self.vocab, self.tp_axis, use_bias=False,
                dtype=jnp.float32, name="lm_head")(x)
        else:
            logits = nn.Dense(self.vocab, use_bias=False, dtype=jnp.float32,
                              name="lm_head")(x)
        return logits.astype(jnp.float32)


def stack_lm_blocks(params):
    """TransformerLM params → the scanned-stack layout: the homogeneous
    ``block_i`` subtrees stacked leaf-wise on a leading layer dim under
    ``"blocks"``, everything else passed through. This is the parameter
    layout :func:`make_lm_fsdp_scan_loss` consumes (and
    ``optimizers.fsdp_scan_apply`` scans over); invert with
    :func:`unstack_lm_blocks` for checkpoints, ``generate``, or any
    per-layer tooling."""
    names = sorted((k for k in params if k.startswith("block_")),
                   key=lambda k: int(k.split("_")[1]))
    if not names:
        raise ValueError("no block_i subtrees found — not TransformerLM "
                         "params?")
    rest = {k: v for k, v in params.items() if not k.startswith("block_")}
    stacked = jax.tree_util.tree_map(
        lambda *ls: jnp.stack(ls), *[params[k] for k in names])
    return {"blocks": stacked, **rest}


def unstack_lm_blocks(packed):
    """Inverse of :func:`stack_lm_blocks`: ``{"blocks": [L, ...], ...}``
    → the original ``block_i`` per-layer tree."""
    blocks = packed["blocks"]
    n = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    out = {k: v for k, v in packed.items() if k != "blocks"}
    for i in range(n):
        out[f"block_{i}"] = jax.tree_util.tree_map(
            lambda l, i=i: l[i], blocks)
    return out


def make_lm_fsdp_scan_loss(model):
    """A step-factory ``loss_fn`` running TransformerLM's layer stack
    through ``optimizers.fsdp_scan_apply`` — the COMPILER-FORCED FSDP
    memory bound (peak gathered params ≈ one layer, re-gathered in
    backward) on the flagship model, with the fused head+CE loss
    (ops/fused_ce.py — the full logits never materialize).

    The forward is rebuilt from the model's OWN flax submodules applied
    piecewise (``nn.Embed``/``TransformerBlock``/``nn.LayerNorm`` with
    the extracted param subtrees) — embed/blocks/LN numerics are those
    of ``model.apply`` exactly, and the head follows ``fused_lm_loss``'s
    convention (the dot takes ``h.dtype`` inputs with f32 accumulation;
    for bf16 models that differs from the unfused head's f32-input
    Dense, exactly as the fused path always has). Asserted against the
    replicated step by the oracle test
    (tests/optimizers_tests/test_zero.py). Use with the stacked layout
    and a mixed sharding tree::

        packed = stack_lm_blocks(params)
        shardings = dict(fsdp_shardings(packed, comm),
                         blocks=fsdp_stack_shardings(packed, comm)["blocks"])
        step, state = make_fsdp_train_step(
            None, optimizer, comm, packed,
            loss_fn=make_lm_fsdp_scan_loss(model),
            param_shardings=shardings)

    Supported envelope: plain data-axis FSDP under jit — no
    ``tp_axis``/``seq_axis`` (those need shard_map axis context), no
    MoE (the load-balancing 'losses' collection cannot thread through
    the scan), no decode. The scan body is always rematerialized (the
    FSDP memory floor), independent of ``model.remat``.
    """
    if getattr(model, "moe_experts_per_device", 0):
        raise ValueError("MoE models: the load-balancing aux cannot "
                         "thread through the scan; use the per-layer "
                         "model with lm_loss_with_aux")
    if model.tp_axis is not None or model.seq_axis is not None:
        raise ValueError("tp_axis/seq_axis need shard_map axis context; "
                         "the FSDP scan step runs under plain jit")
    if model.decode or model.lm_head_tp:
        raise ValueError("decode/lm_head_tp unsupported in the FSDP "
                         "scan loss")
    from chainermn_tpu.ops.fused_ce import fused_ce_head

    block = TransformerBlock(**model.block_config())
    embed = nn.Embed(model.vocab, model.d_model, dtype=model.dtype)
    ln_f = nn.LayerNorm(dtype=model.dtype)

    def loss_fn(_model, p, x, y, train=True, **kw):
        from chainermn_tpu.optimizers import fsdp_scan_apply

        h = embed.apply({"params": p["tok_emb"]}, x)
        if model.pos_emb == "learned":
            idx = jnp.arange(x.shape[1])
            h = h + jnp.take(p["pos_emb"], idx, axis=0).astype(
                model.dtype)[None]
        h = fsdp_scan_apply(
            lambda pi, h: block.apply({"params": pi}, h), p["blocks"], h)
        h = ln_f.apply({"params": p["LayerNorm_0"]}, h)
        b, l, d = h.shape
        w = p["lm_head"]["kernel"].astype(h.dtype)
        # vocab tile: the largest kernel-legal tile dividing the vocab
        # (the kernel requires vocab % block_v == 0, and its dW pass
        # needs a dividing sub-tile — a 128-multiple keeps Mosaic's
        # lane tiling happy)
        bv = next((t for t in (2048, 1024, 512, 256, 128)
                   if model.vocab % t == 0), None)
        if bv is None:
            raise ValueError(
                f"vocab {model.vocab} has no 128-multiple tile divisor "
                "<= 2048; pad the vocabulary to a multiple of 128 for "
                "the fused-CE head")
        loss, acc = fused_ce_head(
            h.reshape(b * l, d), w, jnp.asarray(y, jnp.int32).reshape(-1),
            block_v=bv)
        return loss, (acc, {})

    return loss_fn


def bhld_to_blhd_params(model, params):
    """Convert a bhld-trained parameter tree to the blhd layout.

    The head-major einsum kernels are reshapes/concats of the Dense
    kernels the blhd path declares (same math, different factorization):
    ``qkv_bhld [d,3,h,e]`` → ``qkv/kernel [d,3·d_model]`` (q/k/v blocks
    concatenated the way ``jnp.split`` undoes), ``q_bhld``/``kv_bhld``
    likewise for GQA, ``attn_out_bhld [h,e,d]`` → ``attn_out/kernel
    [h·e,d]``. Everything else passes through. Use before
    :func:`generate` (the KV-cache decode path is blhd-only) or to hand
    a bhld-trained model to blhd-layout tooling.
    """
    d = model.d_model
    h = model.n_heads
    hkv = model.n_kv_heads or h
    e = d // h

    def convert_block(bp):
        out = {k: v for k, v in bp.items() if not k.endswith("_bhld")}
        if "qkv_bhld" in bp:
            w = jnp.asarray(bp["qkv_bhld"])          # [d, 3, h, e]
            out["qkv"] = {"kernel": jnp.concatenate(
                [w[:, t].reshape(d, h * e) for t in range(3)], axis=1)}
        if "q_bhld" in bp:
            out["q_proj"] = {"kernel":
                             jnp.asarray(bp["q_bhld"]).reshape(d, h * e)}
        if "kv_bhld" in bp:
            w = jnp.asarray(bp["kv_bhld"])           # [d, 2, hkv, e]
            out["kv_proj"] = {"kernel": jnp.concatenate(
                [w[:, t].reshape(d, hkv * e) for t in range(2)], axis=1)}
        if "attn_out_bhld" in bp:
            out["attn_out"] = {"kernel":
                               jnp.asarray(bp["attn_out_bhld"])
                               .reshape(h * e, d)}
        return out

    return {k: (convert_block(v) if k.startswith("block_") else v)
            for k, v in params.items()}


def generate(model, params, prompt, max_new_tokens: int,
             rng=None, temperature: float = 1.0, top_k: Optional[int] = None,
             eos_id: Optional[int] = None, pad_id: int = 0,
             use_cache: bool = True):
    """Autoregressive sampling over the serving KV cache.

    The prompt prefills ONCE (the only legal l > 1 apply — see
    :class:`TransformerBlock`'s decode precondition) into a
    ``serving/kv_cache.py`` page sized exactly to the stream, then
    decoding proceeds one token at a time against the cache — O(1)
    compiled programs regardless of length.

    model: the TRAINING TransformerLM (decode twin derived internally);
    prompt: int32 [B, Lp]; returns int32 [B, Lp + max_new_tokens].
    ``rng=None`` → greedy argmax; else categorical at ``temperature``
    (optionally truncated to the ``top_k`` highest logits). ``eos_id``
    enables per-sequence early stop: once a sequence samples it, every
    later position emits ``pad_id`` (shapes stay static — finished
    sequences idle through the remaining scan steps, the SPMD-friendly
    form of early exit).

    ``use_cache=False`` is the FULL-RECOMPUTE reference path: every step
    re-runs the complete forward over the growing prefix (one XLA
    program per prefix length — the cost the cache exists to delete).
    Both paths thread the SAME rng-split sequence, so at fixed rng the
    sampled tokens pin identical between them (tested); keep the slow
    path for auditing cache numerics, never for throughput.
    """
    if model.moe_experts_per_device > 0:
        raise ValueError("generate() does not support MoE models: the "
                         "decode path has no expert dispatch")
    if model.tp_axis is not None or model.lm_head_tp:
        raise ValueError("generate() runs the single-device decode path; "
                         "tp_axis/lm_head_tp models decode without TP "
                         "(clone with tp_axis=None, lm_head_tp=False and "
                         "gather the sharded weights)")
    if model.qkv_layout == "bhld":
        # the KV-cache decode path is blhd-only; fold the head-major
        # kernels back into Dense form (exact, see bhld_to_blhd_params)
        params = bhld_to_blhd_params(model, params)
        model = model.clone(qkv_layout="blhd")
    b, lp = prompt.shape
    total = lp + max_new_tokens
    if total > model.max_len:
        raise ValueError(
            f"prompt + max_new_tokens ({total}) exceeds max_len "
            f"({model.max_len})")
    prompt = jnp.asarray(prompt, jnp.int32)
    greedy = rng is None
    rng = jax.random.PRNGKey(0) if greedy else rng

    def sample(logits, rng):
        if greedy:
            return jnp.argmax(logits, -1).astype(jnp.int32)
        scaled = logits / jnp.maximum(temperature, 1e-6)
        if top_k is not None:
            kth = jax.lax.top_k(scaled, top_k)[0][:, -1:]
            scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)
        return jax.random.categorical(rng, scaled).astype(jnp.int32)

    def mask_eos(nxt, done):
        if eos_id is None:
            return nxt, done
        nxt = jnp.where(done, jnp.int32(pad_id), nxt)
        return nxt, done | (nxt == eos_id)

    if max_new_tokens == 0:
        return prompt

    if not use_cache:
        # reference path: recompute the whole prefix each step (identical
        # rng threading to the cached path below — token-pinning contract)
        toks = prompt
        logits = model.apply({"params": params}, toks)[:, -1]
        rng, sub = jax.random.split(rng)
        tok = sample(logits, sub)
        done = (jnp.zeros((b,), bool) if eos_id is None
                else tok == eos_id)
        toks = jnp.concatenate([toks, tok[:, None]], axis=1)
        for _ in range(max_new_tokens - 1):
            logits = model.apply({"params": params}, toks)[:, -1]
            rng, sub = jax.random.split(rng)
            nxt, done = mask_eos(sample(logits, sub), done)
            toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
            # 1-CORE SYNC: eager dispatch queues ahead; bound it per step
            nxt.block_until_ready()
        return toks

    from chainermn_tpu.serving.kv_cache import (decode_apply, init_cache,
                                                prefill_apply)

    dm = model.clone(decode=True)
    # page sized exactly to the stream: no ring wrap, and (with reference
    # attention) bitwise full-forward parity — tests/serving_tests
    cache0 = init_cache(model, b, total)

    # prefill: ONE forward over the whole prompt fills every layer's page
    # (lp sequential steps collapse into one compute-bound pass); the last
    # prompt position's logits seed the first sampled token
    logits_p, cache = prefill_apply(
        dm, params, cache0, prompt, jnp.full((b,), lp, jnp.int32),
        jnp.arange(b, dtype=jnp.int32))
    rng, sub = jax.random.split(rng)
    tok0 = sample(logits_p, sub)
    done0 = (jnp.zeros((b,), bool) if eos_id is None
             else tok0 == eos_id)

    def step(carry, _):
        cache, tok, rng, done = carry
        logits, cache = decode_apply(dm, params, cache, tok)
        rng, sub = jax.random.split(rng)
        nxt, done = mask_eos(sample(logits, sub), done)
        return (cache, nxt, rng, done), nxt

    # an empty scan (max_new_tokens == 1) returns the carry and 0 tokens
    (_, _, _, _), toks = jax.lax.scan(
        step, (cache, tok0, rng, done0), None, length=max_new_tokens - 1)
    return jnp.concatenate([prompt, tok0[:, None], toks.T], axis=1)


def tp_lm_loss(model, params, x, y, train=True, mutable=None,
               extra_vars=None, rngs=None):
    """Loss for ``lm_head_tp`` models: vocab-parallel cross-entropy over the
    sharded logits (communication O(B·L), the full vocab never gathers).
    Step-factory signature; accuracy is the global argmax assembled with
    pmax (the shard holding the global max logit contributes its index)."""
    from jax import lax

    if not getattr(model, "lm_head_tp", False):
        raise ValueError(
            "tp_lm_loss expects an lm_head_tp model (sharded logits); a "
            "replicated head would inflate the psum'd normalizer by the "
            "axis size and desynchronize gradients")
    variables = {"params": params, **(extra_vars or {})}
    logits = model.apply(variables, x, rngs=rngs)
    ax = model.tp_axis
    loss = vocab_parallel_cross_entropy(logits, y, ax).mean()
    # accuracy: global argmax = the shard holding the global max logit.
    # pmax has no differentiation rule; the metric needs no gradient, so
    # route it through the zero-cotangent custom_vjp
    vl = logits.shape[-1]
    lo = lax.axis_index(ax) * vl
    local_max = jnp.max(logits, -1)
    local_arg = (lo + jnp.argmax(logits, -1)).astype(jnp.float32)
    global_max = pmax_stop_gradient(local_max, ax)
    # the owning shard contributes its argmax (ties: highest shard wins)
    mine = local_max == global_max
    pred = pmax_stop_gradient(jnp.where(mine, local_arg, -1.0), ax)
    acc = jnp.mean((pred == y.astype(jnp.float32)).astype(jnp.float32))
    return loss, (acc, {})


def lm_loss_with_aux(model, params, x, y, train=True, mutable=None,
                     extra_vars=None, rngs=None, aux_weight: float = 0.01):
    """Next-token CE + MoE load-balancing aux, in the step-factory loss
    signature (training/step.py). ``x`` = input tokens, ``y`` = targets."""
    import optax

    variables = {"params": params, **(extra_vars or {})}
    logits, state = model.apply(variables, x, mutable=["losses"], rngs=rngs)
    loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
    aux_tree = state.get("losses", {})
    aux = sum(jax.tree_util.tree_leaves(aux_tree)) if aux_tree else 0.0
    acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
    return loss + aux_weight * aux, (acc, {})
