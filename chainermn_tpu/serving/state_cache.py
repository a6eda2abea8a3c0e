"""Pages taken from what the model declares: the serving programs for models
whose per-sequence state is not K/V rows.

``kv_cache.py`` sizes its pages from ``cache_spec`` — ``n_kv_heads · d_head``
keys and values per token and layer. A model that sets ``declares_cache``
(``models/hybrid.py``) holds other kinds of state side by side: a fixed-size
recurrent state and a convolution tail per linear-attention layer, a latent
page per latent-attention layer, no K/V at all. For such a model the page
tree IS the flax ``cache`` collection ``model.clone(decode=True,
max_len=capacity)`` declares at batch ``n_slots``: every leaf is slot-major
(``[n_slots, ...]``), the root leaf ``idx`` is the per-slot cursor, and
install, evict, reset, export and import work leaf by leaf on axis 0 with no
knowledge of what a leaf means.

What the cursor can and cannot do here goes by the KIND of each declared
leaf, which the model names (``positional_leaves``, ``window_leaves``;
:func:`leaf_kinds`):

* a POSITIONAL leaf (a latent page, a K/V page: axis 1 is the position,
  ``capacity`` long) is addressed by the cursor like K/V rows. A prompt may
  arrive in chunks (:func:`state_prefill_chunk_apply`,
  ``EngineConfig.prefill_chunk``): the model runs ``[S, C]`` at the rows'
  cursors against the cohort's rows of the pages, writes ``[start, start +
  valid)`` and attends what is filled. It does not wrap: ``prompt +
  max_new_tokens`` is held to the capacity;
* a WINDOW leaf (a K/V ring: axis 1 is ``window`` columns, the model's own
  length whatever the capacity) is addressed by the cursor ``mod window``.
  It wraps by nature — column ``j`` holds the newest position ``≡ j`` — and
  a chunk may write it (the chunk's last ``min(valid, window)`` rows). Its
  slot row and the cursor are the whole of its state, so export and import
  move it like any leaf; a self-drafted round is refused by its name (a
  rejected draft's row would have OVERWRITTEN the position a window back,
  which no later write restores);
* a RECURRENT leaf (every other one but ``idx``) is the sum of its history
  and cannot be rewound, re-windowed or wrapped by moving a cursor. A model
  with one is refused chunked prefill with a ``ValueError`` that names the
  leaf and says "recurrent state".

For every model here: a row that is not live must not be stepped at all — the
model takes the ``live`` mask and leaves such a row's state exactly as it was
(``β = 0``, ``α = 1``), where the K/V path lets it write garbage past its
cursor; a right-padded prefill row must stop at its true length — the model
takes ``lengths`` and installs the state after the row's last real token.
``int8-block`` pages (per-column requantisation) are refused for all of
them, and running a cursor past the capacity for every model with a
positional or a recurrent leaf: a recurrent leaf forbids both by nature
("recurrent state"), and for positional leaves these programs are not
written (the message says so). A model of window leaves alone has nothing
the capacity bounds.

Speculation is SELF-DRAFTING here (``StateServingStep(self_draft=True)``,
``EngineConfig.self_draft``): a model that carries a multi-token-prediction
module (``n_mtp`` 1) drafts its own next token, and propose, verify and
accept are one body of the ``decode_k`` scan
(:func:`state_self_draft_k_apply`): the main layers over TWO positions a
slot, the acceptance scan ``speculative.py`` uses too, the module over the
positions just accepted. A rejected draft leaves one garbage row at the
slot's fill in every page, which the next round's write covers before any
mask reads it: positional leaves need no snapshot and no rewind. A
recurrent leaf still refuses, by its name.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chainermn_tpu.serving.kv_cache import ServingStep
from chainermn_tpu.serving.sampling import (acceptance_scan,
                                            draft_shadow_keys, sample_tokens)

__all__ = ["StateServingStep", "serving_step", "declares_cache",
           "init_state_cache", "state_decode_apply", "state_prefill_apply",
           "state_decode_k_apply", "state_prefill_chunk_apply",
           "state_self_draft_k_apply", "state_prefill_draft_apply",
           "leaf_kinds", "recurrent_leaves", "refuse_recurrent"]

#: the per-slot scalars of a declared cache: the cursor, and the draft a
#: self-drafting slot holds between rounds. Neither is a page nor a state
BOOKKEEPING_LEAVES = ("idx", "draft")


def declares_cache(model) -> bool:
    return bool(getattr(model, "declares_cache", False))


def leaf_kinds(model):
    """``{path: kind}`` of the declared leaves (``block_0/kda/state`` ->
    ``"recurrent"``), the per-slot scalars (``BOOKKEEPING_LEAVES``) left
    out: ``"positional"`` and ``"window"`` for the ones the model names in
    ``positional_leaves`` and ``window_leaves``, ``"recurrent"`` for every
    other."""
    shapes = jax.eval_shape(lambda: init_state_cache(model, 1, 8))
    named = {n: kind for kind in ("positional", "window")
             for n in getattr(model, kind + "_leaves", ())}
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    paths = ["/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in flat]
    return {p: named.get(p.rsplit("/", 1)[-1], "recurrent") for p in paths
            if p not in BOOKKEEPING_LEAVES}


def recurrent_leaves(model):
    """Paths of the declared leaves that are a recurrence."""
    return [p for p, kind in leaf_kinds(model).items()
            if kind == "recurrent"]


def refuse_recurrent(model, what: str, *, positional_too: bool = True,
                     instead: str = "") -> None:
    """Raise for a feature that moves K/V rows by cursor. A model with a
    recurrent leaf is always refused, by that leaf's name; one whose
    declared leaves are all positional or window leaves only where the
    feature has no program for declared pages (``positional_too``), with
    ``instead`` — what serves such a model in the feature's place — in the
    message's stead where there is one."""
    if not declares_cache(model):
        return
    name = type(model).__name__
    kinds = leaf_kinds(model)
    leaves = [p for p, kind in kinds.items() if kind == "recurrent"]
    if leaves:
        more = f" (and {len(leaves) - 1} more)" if len(leaves) > 1 else ""
        raise ValueError(
            f"{what} is not available for {name}: its leaf "
            f"{leaves[0]!r}{more} is a recurrent state, which is the sum "
            "of its history and cannot be rewound, requantised by column "
            "or wrapped by moving a cursor")
    if positional_too:
        raise ValueError(
            f"{what} is not available for {name}: " + (instead or (
                "its declared pages are positional"
                + (" or window rings" if "window" in kinds.values() else "")
                + " and could take it, but serving/state_cache.py has no "
                "such program for declared pages yet")))


def init_state_cache(model, n_slots: int, capacity: int):
    """Zeroed pages: the declared ``cache`` collection at batch ``n_slots``
    and page length ``capacity``, each leaf in the dtype the model gives it
    (recurrent state f32, pages in the compute dtype)."""
    dm = model.clone(decode=True, max_len=capacity)
    shapes = jax.eval_shape(
        lambda: dm.init(jax.random.PRNGKey(0),
                        jnp.zeros((n_slots, 1), jnp.int32))["cache"])
    return jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), shapes)


def _apply(dm, params, cache, tokens, lengths, live, **at):
    """One call of the model on its declared cache: (logits, cache, what the
    model counted — the ``stats`` collection's leaves, {} where it counts
    nothing)."""
    logits, upd = dm.apply(
        {"params": params, "cache": cache}, tokens, lengths=lengths,
        live=live, mutable=["cache", "stats"], **at)
    return logits, upd["cache"], dict(upd.get("stats", {}))


def _last(lengths):
    return jnp.maximum(lengths - 1, 0)


def _fresh_rows(cache, s):
    """An ``s``-row zeroed copy of every declared leaf: a cohort's slab."""
    return jax.tree_util.tree_map(
        lambda page: jnp.zeros((s,) + page.shape[1:], page.dtype), cache)


def _install_rows(cache, slab, sid):
    """Every leaf's slab rows at ``sid`` on axis 0 (sentinel rows drop)."""
    return jax.tree_util.tree_map(
        lambda page, rows: page.at[sid].set(rows, mode="drop"), cache, slab)


def state_decode_apply(dm, params, cache, tokens, live=None):
    """PURE one-token step: tokens ``[n]`` → (logits ``[n, vocab]``, cache,
    the model's counts). Rows that are not ``live`` keep their state and
    cursor."""
    n = tokens.shape[0]
    live = jnp.ones((n,), bool) if live is None else live
    logits, cache, stats = _apply(
        dm, params, cache, tokens[:, None], jnp.ones((n,), jnp.int32), live)
    return logits[:, 0], cache, stats


def state_prefill_apply(dm, params, cache, tokens, lengths, slot_ids):
    """PURE cohort prefill: the model runs the right-padded ``[S, L]``
    cohort on a fresh ``S``-row copy of the declared state and stops each
    row at its true length; every leaf's rows are then installed at
    ``slot_ids`` on axis 0 (sentinel ``n_slots`` rows drop), cursor
    included. Returns (last-real-position logits ``[S, vocab]``, cache)."""
    s, l = tokens.shape
    n_slots = cache["idx"].shape[0]
    sid = jnp.asarray(slot_ids, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    slab = _fresh_rows(cache, s)
    logits, slab, _ = _apply(dm, params, slab, tokens, lengths,
                             sid < n_slots)
    last = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
    return last, _install_rows(cache, slab, sid)


def state_prefill_chunk_apply(dm, params, cache, tokens, starts, valid,
                              slot_ids):
    """``kv_cache.prefill_chunk_apply`` for declared POSITIONAL leaves: the
    model runs the ``[S, C]`` cohort at ``starts`` against the whole grid's
    pages where they lie, row ``s`` of the cohort in row ``slot_ids[s]`` of
    every page (no copy of a page in or out: at 12 x 33,024 columns a row of
    the 7 pages is 0.3 GB), and stops each row at ``valid`` — it writes the
    row's latents at ``[start, start + valid)`` and attends the filled page
    plus the chunk; a sentinel ``n_slots`` row writes nothing. Cursors go to
    ``start + valid``. Returns (last-real-position logits ``[S, vocab]``,
    cache)."""
    n_slots = cache["idx"].shape[0]
    sid = jnp.asarray(slot_ids, jnp.int32)
    valid = jnp.asarray(valid, jnp.int32)
    logits, cache, _ = _apply(
        dm, params, cache, tokens, valid, sid < n_slots,
        pos_offset=jnp.asarray(starts, jnp.int32), slots=sid)
    last = jnp.take_along_axis(
        logits, jnp.clip(valid - 1, 0, tokens.shape[1] - 1)[:, None, None],
        axis=1)[:, 0]
    return last, cache


def state_decode_k_apply(dm, params, cache, tokens, keys, temps, top_ks,
                         eos_ids, remaining, live, park, k):
    """``kv_cache.decode_k_apply`` for declared state: ``k`` steps under one
    scan with on-device sampling and stop masks. A row is stepped only
    while it is alive, so a slot that is held, mid-prefill, free or
    finished inside the dispatch keeps the state of its last real token.
    Also returns the model's counts summed over the steps."""
    tokens = jnp.asarray(tokens, jnp.int32)
    live = jnp.asarray(live, bool)
    remaining = jnp.asarray(remaining, jnp.int32)
    eos_ids = jnp.asarray(eos_ids, jnp.int32)
    temps = jnp.asarray(temps, jnp.float32)
    top_ks = jnp.asarray(top_ks, jnp.int32)
    cache = {**cache, "idx": jnp.where(live, cache["idx"],
                                       jnp.asarray(park, jnp.int32))}
    zeros = jnp.zeros((tokens.shape[0], dm.vocab), jnp.float32)

    def body(carry, _):
        cache, tok, keys, rem, alive, _last = carry
        logits, cache, stats = state_decode_apply(dm, params, cache, tok,
                                                  alive)
        nxt, keys2 = sample_tokens(logits, keys, temps, top_ks)
        keys = jnp.where(alive[:, None], keys2, keys)
        valid = alive
        rem = rem - valid.astype(jnp.int32)
        hit_eos = (nxt == eos_ids) & (eos_ids >= 0)
        alive = alive & ~hit_eos & (rem > 0)
        tok = jnp.where(valid, nxt, tok)
        out = jnp.where(valid, nxt, jnp.int32(-1))
        return (cache, tok, keys, rem, alive, logits), (out, stats)

    (cache, _, keys, _, _, last), (toks, stats) = jax.lax.scan(
        body, (cache, tokens, keys, remaining, live, zeros), None, length=k)
    stats = jax.tree_util.tree_map(lambda a: a.sum(0), stats)
    return toks.T, last, keys, cache, stats


def state_prefill_draft_apply(dm, params, cache, tokens, lengths, slot_ids,
                              keys, temps, top_ks):
    """:func:`state_prefill_apply` for a self-drafting model, the first
    token's sampling included (the module needs it): the main layers run
    the cohort, the first token ``t_L`` is sampled from the last real
    position with the slot's key, and the MTP module runs over the whole
    prompt — inputs ``h_i`` and ``t_{i+1}``, the sampled token at ``L - 1``
    — so that its page holds rows ``0 .. L-1`` before the first round, and
    leaves the slot's first draft (of ``t_{L+1}``, sampled with the shadow
    of the key the target will use there). Returns (first tokens ``[S]``,
    keys, cache)."""
    s, l = tokens.shape
    n_slots = cache["idx"].shape[0]
    sid = jnp.asarray(slot_ids, jnp.int32)
    gid = jnp.clip(sid, 0, n_slots - 1)
    lengths = jnp.asarray(lengths, jnp.int32)
    real = sid < n_slots
    slab = _fresh_rows(cache, s)
    (last, hidden), slab, _ = _apply(dm, params, slab, tokens, lengths, real,
                                     return_hidden=True, at=_last(lengths))
    tok, newk = sample_tokens(last, keys[gid], temps[gid], top_ks[gid])
    # sentinel rows (sid == n_slots) drop out of the key scatter
    keys = keys.at[sid].set(newk, mode="drop")
    nxt = jnp.concatenate([tokens[:, 1:], jnp.zeros((s, 1), jnp.int32)], 1)
    nxt = jnp.where(jnp.arange(l)[None] == _last(lengths)[:, None],
                    tok[:, None], nxt)
    dlast, slab, _ = _apply(dm, params, slab, nxt, lengths, real,
                            hidden=hidden, pos_offset=jnp.zeros((s,),
                                                                jnp.int32),
                            at=_last(lengths))
    draft, _ = sample_tokens(dlast, draft_shadow_keys(newk), temps[gid],
                             top_ks[gid])
    return tok, keys, _install_rows(cache, {**slab, "draft": draft}, sid)


def state_self_draft_k_apply(dm, params, cache, tokens, keys, temps, top_ks,
                             eos_ids, remaining, live, park, k):
    """``k`` self-drafted ROUNDS under one scan, no host pull between them.
    A live slot holds ``cur`` (emitted, not yet in a page; ``tokens``) and
    ``draft`` (the module's guess of the token after it; the cache's
    ``draft`` leaf). One round:

    1. the main layers over ``[cur, draft]`` at the slot's cursor ``c`` and
       ``c + 1`` (two absorbed queries against one read of each page) —
       logits ``L_0, L_1`` and hidden states ``h_c, h_{c+1}``;
    2. the acceptance scan (``sampling.acceptance_scan``): ``s_0`` from
       ``L_0`` with the row's REAL key; ``s_0 == draft`` emits ``draft`` and
       the bonus ``s_1``, cursor ``+ 2``; else ``s_0`` alone, cursor ``+ 1``
       — row ``c + 1`` of every main page then holds a rejected draft's
       latent AT the new fill, which the next round's write covers before
       any mask reads it. EOS and the budget stop a row mid-round as in
       ``decode_k``;
    3. the MTP module over the ``m`` positions just accepted (``h_c`` with
       ``t_{c+1}``, then ``h_{c+1}`` with ``t_{c+2}``), so that its page
       holds every accepted position, and the next ``draft`` sampled from
       its last logits with the SHADOW of the row's key.

    What is emitted is sampled by the target from the logits plain decode
    would compute, with plain decode's keys: streams are bitwise those of
    ``state_decode_k_apply``. Returns (tokens ``[n, 2k]``, round-major, -1
    where a round's place stayed empty; the main logits of each row's last
    emitted token; keys; cache; the model's counts and the rounds' —
    ``drafts_verified``, ``drafts_accepted``, ``tokens_emitted``,
    ``rounds`` — summed over the rounds; the module's logits of each row's
    last draft)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    live = jnp.asarray(live, bool)
    remaining = jnp.asarray(remaining, jnp.int32)
    eos_ids = jnp.asarray(eos_ids, jnp.int32)
    temps = jnp.asarray(temps, jnp.float32)
    top_ks = jnp.asarray(top_ks, jnp.int32)
    n = tokens.shape[0]
    cache = {**cache, "idx": jnp.where(live, cache["idx"],
                                       jnp.asarray(park, jnp.int32))}
    zeros = jnp.zeros((n, dm.vocab), jnp.float32)
    two = jnp.full((n,), 2, jnp.int32)
    rows = jnp.arange(n)

    def body(carry, _):
        cache, tok, keys, rem, alive, last, dlast = carry
        start, draft = cache["idx"], cache["draft"]
        (logits, hidden), cache, stats = _apply(
            dm, params, cache, jnp.stack([tok, draft], 1), two, alive,
            absorbed=True, return_hidden=True)
        with jax.named_scope("mtp_accept"):
            out, keys, rem2, alive2, m = acceptance_scan(
                jnp.moveaxis(logits, 1, 0), draft[None], keys, temps, top_ks,
                eos_ids, rem, alive)
            # a draft is verified where the first token left the row alive
            verified = alive & ~(((out[:, 0] == eos_ids) & (eos_ids >= 0))
                                 | (rem <= 1))
            at = _last(m)
            tok = jnp.where(alive, out[rows, at], tok)
            last = jnp.where(alive[:, None], logits[rows, at], last)
        cache = {**cache, "idx": start + m}
        dlogits, cache, mstats = _apply(
            dm, params, cache, jnp.maximum(out, 0), m, alive, hidden=hidden,
            pos_offset=start, absorbed=True, at=at)
        with jax.named_scope("mtp_draft"):
            nd, _ = sample_tokens(dlogits, draft_shadow_keys(keys), temps,
                                  top_ks)
        cache = {**cache, "draft": jnp.where(alive, nd, draft)}
        dlast = jnp.where(alive[:, None], dlogits, dlast)
        count = lambda a: a.sum(dtype=jnp.int32)
        stats = {**stats, **mstats, "drafts_verified": count(verified),
                 "drafts_accepted": count(verified & (m == 2)),
                 "tokens_emitted": count(m), "rounds": jnp.int32(1)}
        return (cache, tok, keys, rem2, alive2, last, dlast), (out, stats)

    (cache, _, keys, _, _, last, dlast), (toks, stats) = jax.lax.scan(
        body, (cache, tokens, keys, remaining, live, zeros, zeros), None,
        length=k)
    stats = jax.tree_util.tree_map(lambda a: a.sum(0), stats)
    # [k, n, 2] -> [n, 2k], round-major
    return (jnp.moveaxis(toks, 0, 1).reshape(n, 2 * k), last, keys, cache,
            stats, dlast)


class StateServingStep(ServingStep):
    """:class:`ServingStep` for a model that declares its cache: the same
    entry points, jit caches, trace counters, donation and placement; the
    pages, the three programs, the shardings and the slot export are the
    ones above, leaf-wise on axis 0."""

    _decode_k_extra = 1     # the model's counts, summed over the steps

    def __init__(self, model, params, n_slots, capacity, *, kv_dtype=None,
                 self_draft=False, **kw):
        if kv_dtype not in (None, "f32"):
            refuse_recurrent(model, f"kv_dtype={kv_dtype!r}")
        #: ONE decode program, chosen here: ``decode_k`` runs self-drafted
        #: rounds (two positions a slot) instead of one-token steps
        self.self_draft = bool(self_draft)
        if self.self_draft:
            refuse_recurrent(model, "self-drafting (rewind on reject)",
                             positional_too=False)
            rings = [p for p, kind in leaf_kinds(model).items()
                     if kind == "window"]
            if rings:
                raise ValueError(
                    f"self-drafting is not available for "
                    f"{type(model).__name__}: its leaf {rings[0]!r} is a "
                    "window ring, in which a rejected draft's row has "
                    "overwritten the position a window back")
            # the model says whether it can, and why not: the step knows
            # nothing of modules or of how a page is read
            refusal = getattr(model, "self_draft_refusal",
                              lambda: "it declares no such rounds")()
            if refusal:
                raise ValueError(
                    f"{type(model).__name__} cannot self-draft: {refusal}")

            self._decode_k_extra = 2    # and the last draft's logits
        #: the module's logits of each slot's last draft (device, like
        #: ``last_decode_logits``); None unless self-drafting
        self.last_draft_logits = None
        super().__init__(model, params, n_slots, capacity, **kw)

    @property
    def no_wrap(self):
        kinds = set(self._kinds.values())
        if "recurrent" in kinds:
            return "a recurrent state forbids ring wrap"
        if "positional" in kinds:
            return ("a declared page has no ring wrap (the model's decode "
                    "write drops past the capacity)")
        return None         # window rings alone: they wrap by nature

    def _init_pages(self, model, params, cache_dtype):
        # each leaf has the dtype the model declares: no ``cache_dtype``
        self.model = model
        self.dm = model.clone(decode=True, max_len=self.capacity)
        self.dm_chunk = None
        self.cache = init_state_cache(model, self.n_slots, self.capacity)
        # read off the declared tree once: a trace of the model's init
        self._kinds = leaf_kinds(model)
        self._recurrent = [p for p, kind in self._kinds.items()
                           if kind == "recurrent"]
        return params

    def _decode_program(self, params, cache, tokens):
        return state_decode_apply(self.dm, params, cache, tokens)[:2]

    def _prefill_program(self, params, cache, tokens, lengths, slot_ids):
        return state_prefill_apply(self.dm, params, cache, tokens, lengths,
                                   slot_ids)

    def _prefill_sampled_program(self, params, cache, tokens, lengths,
                                 slot_ids, keys, temps, top_ks):
        if not self.self_draft:
            return super()._prefill_sampled_program(
                params, cache, tokens, lengths, slot_ids, keys, temps, top_ks)
        return state_prefill_draft_apply(self.dm, params, cache, tokens,
                                         lengths, slot_ids, keys, temps,
                                         top_ks)

    def _decode_k_program(self, params, cache, *args):
        if self.self_draft:
            return state_self_draft_k_apply(self.dm, params, cache, *args)
        return state_decode_k_apply(self.dm, params, cache, *args)

    def _keep_decode_extra(self, stats, draft_logits=None):
        self.last_decode_stats = stats
        self.last_draft_logits = draft_logits

    def _shardings(self, mesh, axis):
        from jax.sharding import NamedSharding, PartitionSpec as P

        repl = NamedSharding(mesh, P())     # every leaf whole on every device
        return repl, jax.tree_util.tree_map(lambda _: repl, self.cache)

    def cache_bytes(self) -> int:
        return self.n_slots * self.slot_bytes

    def cursors(self):
        return jax.device_get(self.cache["idx"])

    def _prefill_chunk_program(self, params, cache, tokens, starts, valid,
                               slot_ids):
        return state_prefill_chunk_apply(self.dm, params, cache, tokens,
                                         starts, valid, slot_ids)

    def prefill_chunk(self, *args, **kw):
        if self._recurrent:
            refuse_recurrent(self.model, "chunked prefill")
        if self.self_draft:
            raise ValueError(
                "chunked prefill is not written for a self-drafting step: "
                "the module runs over the prompt in the bucketed prefill")
        return super().prefill_chunk(*args, **kw)

    def _export_rows(self, slot, fill):
        # whatever a leaf means: a recurrent state has no rows to cut at
        # ``fill``, a ring's columns are addressed by the cursor that
        # travels with them; a page is taken whole
        return jax.tree_util.tree_map(
            lambda page: np.asarray(  # dlint: disable=DL121 — sanctioned migration pull
                page[slot]), self.cache)

    def _import_rows(self, slot, pages, cursor):
        def put(page, row):
            row = jnp.asarray(row, page.dtype)
            if row.shape != page.shape[1:]:
                raise ValueError(f"handoff leaf has shape {row.shape}, "
                                 f"want {page.shape[1:]}")
            return page.at[slot].set(row)

        cache = jax.tree_util.tree_map(put, self.cache, pages)
        cache["idx"] = cache["idx"].at[slot].set(jnp.int32(cursor))
        self.cache = cache

    def load_params(self, params):
        self.params = self.place(params)

    def reset(self):
        self.cache = self.place(init_state_cache(
            self.model, self.n_slots, self.capacity), pages=True)


def serving_step(model, *args, **kw) -> ServingStep:
    """The step for ``model``, picked once: :class:`StateServingStep` where
    the model declares its cache, :class:`ServingStep` (K/V pages) else."""
    cls = StateServingStep if declares_cache(model) else ServingStep
    return cls(model, *args, **kw)
