"""Speculative decoding: a draft model proposes, the target verifies —
tokens-per-dispatch without giving up bitwise streams.

``decode_k`` already amortizes dispatch overhead by committing ``k``
tokens per host round trip, but every token still costs one full
TARGET-model forward. Speculative decoding (ISSUE 20) splits the work:
a small draft ``TransformerLM`` — its own paged KV slots, always f32 —
proposes ``spec_k`` tokens per round, and the target model verifies all
of them in ONE batched chunk forward. Each round is exactly two
dispatches for the whole slot grid:

1. **Propose** (:func:`propose_apply`, one draft program): a width-2
   catch-up chunk writes the tokens the draft cache is missing (the
   current token; plus the previous round's bonus token after a full
   accept), its last-position logits sample the first draft ``d_1``,
   and a ``lax.scan`` of ``spec_k - 1`` draft decode steps samples
   ``d_2 .. d_spec_k``. Sampling uses a SHADOW copy of the target's
   per-slot PRNG rows (:func:`~.sampling.draft_shadow_keys`): the same
   key values, at the same stream positions, the target will use —
   that alignment is what makes sampled-mode acceptance nonzero. The
   shadow is discarded; the drafts never leave the device.

2. **Verify** (:func:`verify_apply`, one target program): a chunked
   forward of ``[cur, d_1 .. d_spec_k]`` at each slot's fill level
   returns per-position logits ``L_0 .. L_spec_k``, where ``L_j`` is
   BITWISE the logits non-speculative decode would compute at that
   stream position (chunked == monolithic == squeezed-q decode — the
   pinned parity chain in models/transformer.py, ``attention=
   'reference'``, no ring wrap). The on-device acceptance scan
   (:func:`~.sampling.acceptance_scan`, shared with the self-drafting
   round of ``state_cache.py``, where a model's own multi-token-prediction
   module is the draft and propose, verify and accept are ONE program) then
   samples ``s_j`` from ``L_j`` with the REAL key rows — advancing a
   row's key only when it actually emits, the one-split-per-sampled-
   token contract — and emits the longest accepted prefix
   (``s_j == d_{j+1}``) plus one more target-sampled token: the
   CORRECTION on the first mismatch, or the BONUS ``s_spec_k`` after a
   full accept. EOS/budget stop masks mirror ``decode_k_apply``
   exactly. The host pulls ONE ``[n_slots, spec_k + 1]`` int32 array.

Because every emitted token is sampled by the TARGET from bitwise-
oracle logits with the oracle's own key stream, accepted streams are
bitwise-identical to non-speculative decode — greedy and sampled, at
every scheduler shape (tests/serving_tests/test_speculative.py). The
draft only decides how far a round advances (1 to ``spec_k + 1``
tokens), never what gets emitted.

Garbage discipline: rejected-draft K/V beyond the accepted prefix stays
in the target pages, but the next round's verify window starts at the
new fill and rewrites every such column before any mask can read it
(the chunk writes all of its columns before attending, and both
attention masks stop at the query row). Ride-along rows (mid-prefill,
held) ride with their cursors parked at their real fill, exactly like
``decode_k`` — their garbage lands at-or-beyond fill and is clipped at
the page end (the chunk branch drops, never wraps).

No-wrap contract: ``submit`` enforces ``prompt + max_new + spec_k <=
capacity`` — the verify chunk's absolute-position mask (and the parity
chain above) has no ring semantics, and the margin keeps the draft's
own pages from wrapping too.

Host-transfer honesty: a round moves ``4 · (spec_k + 1)`` bytes per
slot for 1..``spec_k + 1`` emitted tokens, so the ≤ 8 bytes/token
bound of the plain decode path (DL110) holds only at healthy acceptance
rates;
``ServingReport.acceptance_rate`` / ``tokens_per_dispatch`` are the
observability for exactly that (reports.py).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chainermn_tpu import tracing
from chainermn_tpu.models.transformer import bhld_to_blhd_params
from chainermn_tpu.serving.engine import Engine, EngineConfig
from chainermn_tpu.serving.kv_cache import (
    _check_servable,
    decode_apply,
    init_cache,
    prefill_apply,
    prefill_chunk_apply,
    repack_cache,
    unpack_cache,
)
from chainermn_tpu.serving.sampling import (acceptance_scan,
                                            draft_shadow_keys, sample_tokens)
from chainermn_tpu.serving.state_cache import refuse_recurrent

__all__ = ["DraftStep", "SpeculativeEngine", "propose_apply",
           "verify_apply"]


def propose_apply(dm, dm_chunk, params, cache, prev, cur, valid, starts,
                  keys, temps, top_ks, live, park, spec_k: int):
    """PURE draft proposal for the whole grid: one catch-up chunk + a
    ``spec_k - 1``-step decode scan, fused into one program.

    prev/cur ``[n]`` int32 — the previous round's bonus token (used only
    where ``valid == 2``) and each slot's current token; valid ``[n]``
    (1 normally, 2 after a full accept — the bonus token was proposed
    but never written to the draft pages); starts ``[n]`` = fill -
    (valid - 1); keys ``[n, 2]`` — the TARGET's key rows, shadow-copied
    here and discarded; live/park as in ``decode_k_apply``.

    Returns ``(drafts [n, spec_k] int32 — ON DEVICE, new draft cache)``.
    The draft cache invariant this maintains: between rounds a live
    slot's pages hold exactly the stream positions ``[0, fill)`` — the
    same invariant the target pages keep — so the catch-up never needs
    more than width 2.
    """
    n = cur.shape[0]
    cur = jnp.asarray(cur, jnp.int32)
    prev = jnp.asarray(prev, jnp.int32)
    valid = jnp.asarray(valid, jnp.int32)
    starts = jnp.asarray(starts, jnp.int32)
    live = jnp.asarray(live, bool)
    park = jnp.asarray(park, jnp.int32)
    two = valid == 2
    chunk = jnp.stack([jnp.where(two, prev, cur),
                       jnp.where(two, cur, 0)], axis=1)
    last, cache = prefill_chunk_apply(
        dm_chunk, params, cache, chunk, starts, valid,
        jnp.arange(n, dtype=jnp.int32))
    shadow = draft_shadow_keys(keys)
    d1, shadow = sample_tokens(last, shadow, temps, top_ks)

    def body(carry, _):
        cache, tok, shadow = carry
        logits, cache = decode_apply(dm, params, cache, tok)
        nxt, shadow = sample_tokens(logits, shadow, temps, top_ks)
        return (cache, nxt, shadow), nxt

    (cache, _, _), rest = jax.lax.scan(
        body, (cache, d1, shadow), None, length=spec_k - 1)
    drafts = jnp.concatenate([d1[:, None], rest.T], axis=1)
    # ride-along rows: cursors back to their real fill, like decode_k
    cache = {name: {**page, "idx": jnp.where(live, page["idx"], park)}
             for name, page in cache.items()}
    return drafts, cache


def verify_apply(dm_chunk, params, cache, cur, drafts, keys, temps,
                 top_ks, eos_ids, remaining, live, park, spec_k: int):
    """PURE target verification + acceptance for the whole grid.

    One chunked forward of ``[cur, d_1 .. d_spec_k]`` (width ``spec_k +
    1``) at ``starts = fill`` yields per-position logits; the shared
    acceptance scan (``sampling.acceptance_scan``, which the
    self-drafting round of ``state_cache.py`` runs too) samples ``s_j``
    from position ``j`` with the real key rows and emits while ``s_j ==
    d_{j+1}``, then one correction or bonus token. Key rows advance ONLY on emission — one split per
    sampled token, the same contract as ``decode_k_apply`` — and the
    EOS/budget masks mirror its stop logic token for token.

    Returns ``(emitted [n, spec_k+1] int32 — -1 past each row's stop,
    new keys, new cache)``. Live cursors land at ``fill + emitted``;
    ride-along rows stay parked. ``cache`` must be the f32 view
    (callers unpack/repack int8 pages around this).
    """
    cur = jnp.asarray(cur, jnp.int32)
    drafts = jnp.asarray(drafts, jnp.int32)
    live = jnp.asarray(live, bool)
    park = jnp.asarray(park, jnp.int32)
    eos_ids = jnp.asarray(eos_ids, jnp.int32)
    remaining = jnp.asarray(remaining, jnp.int32)
    temps = jnp.asarray(temps, jnp.float32)
    top_ks = jnp.asarray(top_ks, jnp.int32)
    start = jnp.where(live, cache["block_0"]["idx"], park)
    chunk = jnp.concatenate([cur[:, None], drafts], axis=1)
    # the pages ARE the batch (every slot rides along), so unlike
    # prefill_chunk_apply no gather/scatter detour is needed: the chunk
    # branch writes each column at its absolute position (clip-drop at
    # the page end) before attention reads it
    sub = {name: {"k": page["k"], "v": page["v"], "idx": start}
           for name, page in cache.items()}
    logits, upd = dm_chunk.apply(
        {"params": params, "cache": sub}, chunk, pos_offset=start,
        mutable=["cache"])
    new_cache = upd["cache"]

    emitted, keys, _, _, m = acceptance_scan(
        jnp.moveaxis(logits, 1, 0), drafts.T, keys, temps, top_ks, eos_ids,
        remaining, live)
    idx = jnp.where(live, start + m, park)
    new_cache = {name: {**page, "idx": idx}
                 for name, page in new_cache.items()}
    return emitted, keys, new_cache


class DraftStep:
    """The draft model's compiled programs + paged cache (always f32 —
    the draft's logits only pick how far a round advances, so its pages
    never justify quantization complexity). Mirrors the target's
    admission writes (:meth:`mirror_prefill` / :meth:`mirror_chunk`,
    logits discarded) and runs the fused proposal (:meth:`propose`).
    One compiled program per shape, counted — the DL108 discipline."""

    def __init__(self, model, params, n_slots: int, capacity: int, *,
                 donate: bool = True):
        _check_servable(model)
        self.src_model = model
        if model.qkv_layout == "bhld":
            params = bhld_to_blhd_params(model, params)
            model = model.clone(qkv_layout="blhd")
        self.model = model
        self.dm = model.clone(decode=True)
        self.dm_chunk = self.dm.clone(chunked_prefill=True)
        self.params = params
        self.n_slots = int(n_slots)
        self.capacity = int(capacity)
        self.cache = init_cache(model, n_slots, capacity)
        self.propose_traces = 0
        self.mirror_traces: Dict[tuple, int] = {}
        self._mirror_jits: Dict[tuple, Any] = {}
        self._propose_jits: Dict[int, Any] = {}
        self._donate = (1,) if donate else ()

    def mirror_prefill(self, tokens, lengths, slot_ids) -> None:
        """Write a monolithic prefill cohort's prompts into the draft
        pages (same slab/scatter as the target's prefill; the draft's
        first-token logits are discarded — the target samples)."""
        tokens = jnp.asarray(tokens, jnp.int32)
        key = ("prefill",) + tokens.shape
        if key not in self._mirror_jits:
            def _mp(params, cache, tokens, lengths, slot_ids, _key=key):
                self.mirror_traces[_key] = (
                    self.mirror_traces.get(_key, 0) + 1)
                _, cache = prefill_apply(self.dm, params, cache, tokens,
                                         lengths, slot_ids)
                return cache

            self._mirror_jits[key] = jax.jit(
                _mp, donate_argnums=self._donate)
        self.cache = self._mirror_jits[key](
            self.params, self.cache, tokens,
            jnp.asarray(lengths, jnp.int32),
            jnp.asarray(slot_ids, jnp.int32))

    def mirror_chunk(self, tokens, starts, valid, slot_ids) -> None:
        """Chunked twin of :meth:`mirror_prefill`."""
        tokens = jnp.asarray(tokens, jnp.int32)
        key = ("chunk",) + tokens.shape
        if key not in self._mirror_jits:
            def _mc(params, cache, tokens, starts, valid, slot_ids,
                    _key=key):
                self.mirror_traces[_key] = (
                    self.mirror_traces.get(_key, 0) + 1)
                _, cache = prefill_chunk_apply(
                    self.dm_chunk, params, cache, tokens, starts, valid,
                    slot_ids)
                return cache

            self._mirror_jits[key] = jax.jit(
                _mc, donate_argnums=self._donate)
        self.cache = self._mirror_jits[key](
            self.params, self.cache, tokens,
            jnp.asarray(starts, jnp.int32),
            jnp.asarray(valid, jnp.int32),
            jnp.asarray(slot_ids, jnp.int32))

    def propose(self, prev, cur, valid, starts, keys, temps, top_ks,
                live, park, spec_k: int):
        """One fused proposal dispatch (see :func:`propose_apply`);
        compiled once per ``spec_k``, counted in ``propose_traces``.
        Returns drafts ``[n, spec_k]`` ON DEVICE."""
        kk = int(spec_k)
        if kk not in self._propose_jits:
            def _pp(params, cache, prev, cur, valid, starts, keys,
                    temps, top_ks, live, park, _k=kk):
                self.propose_traces += 1    # trace-time only
                return propose_apply(self.dm, self.dm_chunk, params,
                                     cache, prev, cur, valid, starts,
                                     keys, temps, top_ks, live, park,
                                     _k)

            self._propose_jits[kk] = jax.jit(
                _pp, donate_argnums=self._donate)
        drafts, self.cache = self._propose_jits[kk](
            self.params, self.cache, jnp.asarray(prev, jnp.int32),
            jnp.asarray(cur, jnp.int32), jnp.asarray(valid, jnp.int32),
            jnp.asarray(starts, jnp.int32), keys,
            jnp.asarray(temps, jnp.float32),
            jnp.asarray(top_ks, jnp.int32),
            jnp.asarray(live, bool), jnp.asarray(park, jnp.int32))
        return drafts

    def load_params(self, params) -> None:
        """Swap draft weights (rolling update of the draft/target pair;
        same conversion contract as ``ServingStep.load_params``)."""
        if self.src_model.qkv_layout == "bhld":
            params = bhld_to_blhd_params(self.src_model, params)
        self.params = params

    def reset(self) -> None:
        self.cache = init_cache(self.model, self.n_slots, self.capacity)


class SpeculativeEngine(Engine):
    """The continuous-batching engine with speculative rounds replacing
    ``decode_k`` dispatches. Scheduling, admission, chunked prefill,
    token budgets, holds, and exports are all inherited — a round
    reserves ``spec_k + 1`` cache columns per slot
    (:meth:`_max_decode_advance`), and the admission hooks mirror every
    prompt write into the draft pages so the draft is always exactly
    one token behind the target.

    ``cfg.decode_k`` is ignored: the verify width is ``spec_k + 1``.
    Works with f32 or int8-block target pages (``cfg.kv_dtype``); the
    draft pages are always f32. Exports (handoff/session) read target
    state only, so a speculative replica hands off to any engine;
    imports mirror the adopted prefix into the draft pages in fixed-
    width chunks before the next round."""

    #: draft-prefix mirror chunk width for imports when the engine
    #: isn't running chunked prefill (one compiled mirror shape)
    _IMPORT_MIRROR_CHUNK = 32

    def __init__(self, model, params, draft_model, draft_params,
                 config: EngineConfig = EngineConfig(), *,
                 spec_k: int = 4, report=None, time_fn=None,
                 weights_version: Optional[str] = None):
        if spec_k < 1:
            raise ValueError("spec_k must be >= 1")
        for m in (model, draft_model):
            refuse_recurrent(
                m, "speculative decoding (rewind on reject)",
                instead="propose and verify here are written against K/V "
                "pages; a model of declared positional pages drafts from "
                "its own multi-token-prediction module, in the decode "
                "program (n_mtp 1, EngineConfig.self_draft)")
        super().__init__(model, params, config, report=report,
                         time_fn=time_fn, weights_version=weights_version)
        if draft_model.vocab != model.vocab:
            raise ValueError(
                f"draft vocab {draft_model.vocab} != target vocab "
                f"{model.vocab} — proposals would not be sampleable "
                "by the target")
        self.spec_k = int(spec_k)
        self.draft = DraftStep(draft_model, draft_params,
                               config.n_slots, config.capacity)
        n = config.n_slots
        # full-accept bookkeeping: after a round that emitted
        # spec_k + 1 tokens, the bonus token was never written to the
        # draft pages — the next catch-up chunk is width 2
        self._spec_full = np.zeros(n, bool)
        self._spec_prev = np.zeros(n, np.int32)
        self.verify_traces = 0
        self._verify_jit = None

    # -- scheduling integration ---------------------------------------

    def _max_decode_advance(self) -> int:
        return self.spec_k + 1

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               **kwargs):
        prompt_arr = np.asarray(prompt, np.int32).reshape(-1)
        budget = (max_new_tokens if max_new_tokens is not None
                  else self.config.max_new_tokens)
        if prompt_arr.size + budget + self.spec_k > self.config.capacity:
            raise ValueError(
                "speculative decode forbids ring wrap: prompt "
                f"({prompt_arr.size}) + max_new_tokens ({budget}) + "
                f"spec_k ({self.spec_k}) exceeds the page capacity "
                f"({self.config.capacity}) — the verify chunk and the "
                "draft pages both need the absolute-position no-wrap "
                "margin")
        return super().submit(prompt, max_new_tokens, **kwargs)

    def _install(self, req, slot: int) -> None:
        super()._install(req, slot)
        self._spec_full[slot] = False
        self._spec_prev[slot] = 0

    def _fork_slot_rows(self) -> None:
        super()._fork_slot_rows()
        self._spec_prev = self._spec_prev.copy()    # given to ``propose``

    def _on_prefill(self, tokens, lengths, slot_ids) -> None:
        self.draft.mirror_prefill(tokens, lengths, slot_ids)

    def _on_prefill_chunk(self, tokens, starts, valid, slot_ids,
                          final) -> None:
        self.draft.mirror_chunk(tokens, starts, valid, slot_ids)

    def import_handoff(self, handoff: dict, prompt,
                       max_new_tokens: Optional[int] = None):
        req = super().import_handoff(handoff, prompt,
                                     max_new_tokens=max_new_tokens)
        if req.slot is not None:    # terminal handoffs retired already
            if (req.prompt.size + req.max_new_tokens + self.spec_k
                    > self.config.capacity):
                self._retire(req, aborted=True)
                raise ValueError(
                    "adopted session does not fit the speculative "
                    f"no-wrap margin (prompt {req.prompt.size} + budget "
                    f"{req.max_new_tokens} + spec_k {self.spec_k} > "
                    f"capacity {self.config.capacity})")
            # the draft pages must hold the adopted stream's positions
            # [0, fill) before the next round's catch-up
            prefix = np.concatenate(
                [req.prompt, np.asarray(req.tokens[:-1], np.int32)])
            self._mirror_prefix(req.slot, prefix)
            self._spec_full[req.slot] = False
            self._spec_prev[req.slot] = 0
        return req

    def _mirror_prefix(self, slot: int, prefix: np.ndarray) -> None:
        c = self.config.prefill_chunk or min(self._IMPORT_MIRROR_CHUNK,
                                             self.config.capacity)
        pos = 0
        while pos < prefix.size:
            v = int(min(c, prefix.size - pos))
            tokens = np.zeros((1, c), np.int32)
            tokens[0, :v] = prefix[pos:pos + v]
            self.draft.mirror_chunk(
                tokens, np.array([pos], np.int32),
                np.array([v], np.int32), np.array([slot], np.int32))
            pos += v

    # -- the speculative round ----------------------------------------

    def _verify(self, cur, drafts, remaining, live, park):
        if self._verify_jit is None:
            steps = self.steps
            w = self.spec_k + 1

            def _vf(params, cache, cur, drafts, keys, temps, top_ks,
                    eos, rem, live, park):
                self.verify_traces += 1     # trace-time only
                f32c = unpack_cache(cache)
                start = jnp.where(jnp.asarray(live, bool),
                                  f32c["block_0"]["idx"],
                                  jnp.asarray(park, jnp.int32))
                emitted, keys, f32c = verify_apply(
                    steps.dm_chunk, params, f32c, cur, drafts, keys,
                    temps, top_ks, eos, rem, live, park, self.spec_k)
                # the chunk branch clip-DROPS columns past the page end
                # (never wraps), so the int8 commit window clips too
                count = jnp.clip(steps.capacity - start, 0, w)
                return emitted, keys, repack_cache(cache, f32c, start,
                                                   count)

            self._verify_jit = jax.jit(_vf, donate_argnums=(1,))
        emitted, keys, self.steps.cache = self._verify_jit(
            self.steps.params, self.steps.cache,
            jnp.asarray(cur, jnp.int32), drafts, self._keys,
            jnp.asarray(self._temps, jnp.float32),
            jnp.asarray(self._topks, jnp.int32),
            jnp.asarray(self._eos, jnp.int32),
            jnp.asarray(remaining, jnp.int32),
            jnp.asarray(live, bool), jnp.asarray(park, jnp.int32))
        return emitted, keys

    def _decode(self) -> int:
        """One speculative ROUND for the whole grid (propose + verify,
        two dispatches) in place of the base engine's one ``decode_k``
        dispatch; the host pulls a single ``[n_slots, spec_k + 1]``
        int32 array and replays the device's emissions."""
        cfg = self.config
        n = cfg.n_slots
        w = self.spec_k + 1
        with tracing.span("engine.decode.enqueue", live=len(self.active)):
            live = np.zeros(n, bool)
            remaining = np.ones(n, np.int32)
            fills = np.zeros(n, np.int32)
            for slot, req in self.active.items():
                live[slot] = True
                remaining[slot] = req.max_new_tokens - len(req.tokens)
                fills[slot] = req.prompt.size + len(req.tokens) - 1
            park = np.zeros(n, np.int32)
            for slot, req in self.prefilling.items():
                park[slot] = req.prefill_pos
            for slot, req in self.held.items():
                park[slot] = req.prompt.size + len(req.tokens) - 1
            valid = np.where(live & self._spec_full, 2, 1).astype(np.int32)
            starts = np.where(live, fills - (valid - 1), park)
            drafts = self.draft.propose(
                self._spec_prev, self.cur_tokens, valid, starts, self._keys,
                self._temps, self._topks, live, park, self.spec_k)
            emitted_dev, self._keys = self._verify(
                self.cur_tokens, drafts, remaining, live, park)
        self._admit_ahead()
        with tracing.span("engine.decode.wait"):
            toks = np.asarray(emitted_dev)  # [n, spec_k+1] int32 — the
            #                                 round's ONLY host pull
        self.report.record_host_bytes(toks.nbytes)
        with tracing.span("engine.emit") as sp:
            emitted = retired = 0
            for slot, req in list(self.active.items()):
                m = self._replay(req, toks[slot])
                emitted += m
                retired += req.finished
                # the round's last token is always target-sampled
                # (correction, bonus, or terminal) → accepted = m - 1
                self.report.record_spec_round(self.spec_k, max(m - 1, 0), m)
                self._spec_full[slot] = m == w
                if m == w:
                    self._spec_prev[slot] = int(toks[slot, w - 2])
            if sp:
                sp.set(tokens=emitted, retired=retired)
        return emitted
