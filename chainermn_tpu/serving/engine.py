"""Continuous-batching engine: iteration-level scheduling over the
paged KV cache.

The engine owns a fixed grid of ``n_slots`` decode slots. Every
``step()`` is one scheduler iteration under a shared per-iteration
TOKEN BUDGET (``EngineConfig.token_budget``; ``None`` → unbounded):

1. **Budget** — the iteration reserves ``len(active) × decode_k``
   tokens for decode first; prefill spends what is left.
2. **Prefill** — either one monolithic same-bucket cohort (the classic
   path: up to ``prefill_cohort`` prompts right-padded to the bucket
   length, sentinel rows filling the fixed shape), or — with
   ``prefill_chunk`` set — fixed-size ``[S, C]`` prompt CHUNKS written
   incrementally at each slot's cursor, so a long prompt streams in
   across iterations instead of head-of-line-blocking every active
   decode slot. A deferral cap (``max_prefill_defer``) guarantees
   prefill still happens under sustained decode pressure, and a wrap
   guard force-finishes any prefill within ``decode_k`` tokens of the
   page end before decode may run again.
3. **Decode** — ONE ``decode_k`` dispatch advances every live slot up
   to ``k`` tokens: sampling runs ON DEVICE (serving/sampling.py, keyed
   by per-slot PRNG state the engine threads), EOS/budget stop masks
   are evaluated in the compiled scan, and the host pulls a single
   ``[n_slots, k]`` int32 array — 4 bytes/token instead of
   ``vocab × 4`` (dlint DL110 polices the old full-logits pull). With
   ``EngineConfig.self_draft`` (a model that carries a multi-token-
   prediction module, ``state_cache.py``) the dispatch is ``k``
   self-drafted ROUNDS of one or two tokens a slot, the pull ``[n_slots,
   2k]``, and every reservation below counts ``2k``.
4. **Retirement** — slots whose request emitted ``eos_id`` or reached
   its token budget are freed for the next admission.

The monolithic admission of the NEXT iteration runs one decode ahead
whenever its decision is already fixed (:meth:`Engine._admission_is_fixed`):
the cohort is composed and its prefill dispatched right after ``decode_k``
has been enqueued, while the device decodes, and the next ``step()``
SETTLES it (waits for the first tokens, emits them) in place of admitting.
The schedule is the late one carried out earlier: the same requests in the
same slots in the same iteration, the same programs in the same device
order.

Prefill and decode co-exist without recompilation — the DL108
invariant: after warmup, serving any traffic mix executes exactly one
compiled ``decode_k`` program plus one prefill program per bucket (or
ONE chunk program total in chunked mode). ``resilience/chaos.py::
on_step`` fires at the top of every iteration, so
``$CHAINERMN_TPU_CHAOS='kill@step=N'`` kills a replica mid-decode — the
supervisor drill in tests/serving_tests.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from chainermn_tpu import tracing
from chainermn_tpu.resilience import chaos
from chainermn_tpu.serving.reports import ServingReport
from chainermn_tpu.serving.sampling import init_keys, request_key
from chainermn_tpu.serving.state_cache import refuse_recurrent, serving_step

__all__ = ["Engine", "EngineConfig", "Request", "WeightsVersionSkew",
           "default_buckets"]


class WeightsVersionSkew(ValueError):
    """A handoff/session was minted under a different weights version
    than this engine serves. Adoption is REFUSED — continuing a
    prefill-v2 stream on a decode-v1 replica would silently mix model
    versions inside one output. Callers route the refusal through the
    existing fallbacks: the decode pool re-prefills the stream cleanly
    (fleet/pools.py), the router replays it from seed on a survivor
    (fleet/router.py) — either way the stream is entirely ONE version,
    bitwise against that version's oracle."""


def default_buckets(capacity: int, lo: int = 8) -> Tuple[int, ...]:
    """Power-of-two bucket table up to the page capacity: every prompt
    compiles against one of O(log capacity) prefill shapes."""
    out = []
    b = lo
    while b < capacity:
        out.append(b)
        b *= 2
    out.append(capacity)
    return tuple(out)


@dataclasses.dataclass
class EngineConfig:
    n_slots: int = 4
    capacity: int = 256
    max_new_tokens: int = 64          # default per-request budget
    prefill_cohort: int = 2           # S — cohort width (fixed shape)
    buckets: Optional[Sequence[int]] = None  # None → default_buckets()
    cache_dtype: object = None
    decode_k: int = 4                 # tokens per decode dispatch (the
    #                                   on-device scan length; 1 = the
    #                                   classic one-token step)
    prefill_chunk: Optional[int] = None  # chunk width C; None → the
    #                                      monolithic per-bucket path
    token_budget: Optional[int] = None   # per-iteration token budget
    #                                      shared by decode + prefill;
    #                                      None → unbounded
    max_prefill_defer: int = 4        # iterations prefill may yield to
    #                                   decode before it runs anyway
    kv_dtype: Optional[str] = None    # page storage mode: None/'f32' or
    #                                   'int8-block' (kv_cache.py — int8
    #                                   pages forbid ring wrap, so submit
    #                                   enforces prompt + max_new ≤
    #                                   capacity)
    self_draft: bool = False          # decode_k runs self-drafted rounds
    #                                   (the model's own MTP module drafts,
    #                                   state_cache.py): 1 or 2 tokens a
    #                                   slot a round; submit enforces
    #                                   prompt + max_new + 1 ≤ capacity

    def bucket_table(self) -> Tuple[int, ...]:
        return (tuple(sorted(self.buckets)) if self.buckets
                else default_buckets(self.capacity))


@dataclasses.dataclass(eq=False)   # identity semantics (prompt is an array)
class Request:
    """One generation stream. ``tokens`` grows as the engine emits;
    terminal states are 'done' (eos or budget) and 'aborted'.

    Sampling happens ON DEVICE (serving/sampling.py): ``temperature``
    ``None``/``0`` → greedy argmax (bit-identical to the old host
    ``np.argmax`` path), ``top_k`` ``None``/``0`` → full vocabulary,
    ``seed`` keys the per-slot PRNG stream — one split per sampled
    token, so a fixed seed replays the same stream under any scheduler
    interleaving.
    """
    request_id: int
    prompt: np.ndarray                # int32 [L]
    max_new_tokens: int
    eos_id: Optional[int] = None
    temperature: Optional[float] = None   # None → greedy argmax
    top_k: Optional[int] = None           # None → full vocab
    seed: int = 0
    tokens: List[int] = dataclasses.field(default_factory=list)
    state: str = "queued"             # queued|running|held|done|aborted
    slot: Optional[int] = None
    prefill_pos: int = 0              # chunked prefill: tokens written
    hold: bool = False                # retire → 'held' (slot kept bound
    #                                   for export_handoff; fleet pools)
    t_submit: float = dataclasses.field(   # time.perf_counter at creation:
        default_factory=time.perf_counter)  # the queue's age in engine.step
    drafts_proposed: int = 0          # rounds whose first token left this
    drafts_accepted: int = 0          # stream alive; those that gave two

    @property
    def finished(self) -> bool:
        return self.state in ("done", "aborted")


class Engine:
    """Single-threaded scheduler core (the thread-safe face is
    ``frontend.Frontend``). ``submit()`` queues, ``step()`` advances one
    iteration, ``run_until_drained()`` loops until idle."""

    def __init__(self, model, params, config: EngineConfig = EngineConfig(),
                 *, mesh=None, axis=None, report: Optional[ServingReport] = None,
                 time_fn=None, weights_version: Optional[str] = None):
        self.config = config
        #: which published weights this engine serves (None = unversioned
        #: — every skew check passes, so pre-rollout fleets are unchanged)
        self.weights_version = weights_version
        if config.decode_k < 1:
            raise ValueError("decode_k must be >= 1")
        if config.prefill_chunk is not None and config.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if config.prefill_chunk is not None:
            refuse_recurrent(model, "chunked prefill", positional_too=False)
        if config.self_draft and config.prefill_chunk is not None:
            raise ValueError(
                "self_draft serves bucketed prefill only: the module runs "
                "over the prompt there (prefill_chunk must be None)")
        with tracing.lifecycle_span("engine.build", n_slots=config.n_slots,
                                    capacity=config.capacity) as sp:
            self.steps = serving_step(
                model, params, config.n_slots, config.capacity,
                cache_dtype=config.cache_dtype, mesh=mesh, axis=axis,
                kv_dtype=config.kv_dtype,
                **({"self_draft": True} if config.self_draft else {}))
            # per-slot sampling state, threaded through the compiled
            # programs (sampling.py encoding: temp<=0 greedy, top_k<=0 full)
            self._keys = self.steps.place(init_keys(config.n_slots))
            sp.set(page_bytes=self.steps.cache_bytes())
        self.report = report or (ServingReport(time_fn) if time_fn
                                 else ServingReport())
        self.queue: deque[Request] = deque()
        self.active: Dict[int, Request] = {}          # slot → decoding
        self.prefilling: Dict[int, Request] = {}      # slot → mid-chunk
        self.held: Dict[int, Request] = {}            # slot → awaiting
        #                                               export (handoff)
        self.free_slots: List[int] = list(range(config.n_slots))
        self.cur_tokens = np.zeros(config.n_slots, np.int32)
        self._temps = np.zeros(config.n_slots, np.float32)
        self._topks = np.zeros(config.n_slots, np.int32)
        self._eos = np.full(config.n_slots, -1, np.int32)
        self._prefill_defer = 0
        #: (cohort, first-token ids on the device) of the admission that was
        #: dispatched one decode ahead and is not settled yet: its requests
        #: hold their slots but join ``active`` only with their first token
        self._ahead: Optional[Tuple[List[Request], object]] = None
        self.iteration = 0
        self._ids = itertools.count()
        self._buckets = config.bucket_table()
        if self._buckets[-1] < config.capacity:
            raise ValueError("largest bucket must reach capacity")

    @property
    def last_logits(self) -> Optional[np.ndarray]:
        """Final decode-step logits ``[n_slots, vocab]`` — materialized
        from device ONLY when read (debug/parity hook; the serving hot
        loop itself never pulls them — that's the point of DL110)."""
        dev = self.steps.last_decode_logits
        return None if dev is None else np.asarray(dev)

    # ----------------------------------------------------------------
    # request lifecycle
    # ----------------------------------------------------------------

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               eos_id: Optional[int] = None,
               temperature: Optional[float] = None,
               top_k: Optional[int] = None,
               seed: int = 0, hold: bool = False) -> Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if self.config.prefill_chunk is not None:
            # chunked prefill is bucket-free; the page (and the no-wrap
            # chunk contract) is the only length limit
            if prompt.size > self.config.capacity:
                raise ValueError(
                    f"prompt length {prompt.size} exceeds the page "
                    f"capacity ({self.config.capacity})")
        elif prompt.size > self._buckets[-1]:
            raise ValueError(
                f"prompt length {prompt.size} exceeds the largest prefill "
                f"bucket ({self._buckets[-1]})")
        budget = (max_new_tokens if max_new_tokens is not None
                  else self.config.max_new_tokens)
        if (self.steps.no_wrap
                and prompt.size + budget > self.config.capacity):
            raise ValueError(
                f"{self.steps.no_wrap}: prompt ({prompt.size})"
                f" + max_new_tokens ({budget}) exceeds the page capacity "
                f"({self.config.capacity})")
        if (self.config.self_draft
                and prompt.size + budget + 1 > self.config.capacity):
            raise ValueError(
                "a self-drafted round writes the draft's row beside the "
                f"current token's: prompt ({prompt.size}) + max_new_tokens "
                f"({budget}) + 1 exceeds the page capacity "
                f"({self.config.capacity})")
        req = Request(request_id=next(self._ids), prompt=prompt,
                      max_new_tokens=budget,
                      eos_id=eos_id, temperature=temperature,
                      top_k=top_k, seed=seed, hold=hold)
        self.queue.append(req)
        self.report.record_submit(req.request_id)
        return req

    def _bucket_for(self, length: int) -> int:
        for b in self._buckets:
            if b >= length:
                return b
        raise ValueError(f"no bucket covers prompt length {length}")

    def _install(self, req: Request, slot: int) -> None:
        """Bind a request to a slot: sampling state rows + PRNG key."""
        req.slot = slot
        req.state = "running"
        self._temps[slot] = (req.temperature
                             if req.temperature is not None else 0.0)
        self._topks[slot] = req.top_k if req.top_k is not None else 0
        self._eos[slot] = req.eos_id if req.eos_id is not None else -1
        self._keys = self._keys.at[slot].set(request_key(req.seed))
        self.report.record_admit(req.request_id)

    def _emit(self, req: Request, token: int) -> None:
        req.tokens.append(int(token))
        hit_eos = req.eos_id is not None and token == req.eos_id
        if hit_eos or len(req.tokens) >= req.max_new_tokens:
            if req.hold:
                self._hold(req)
            else:
                self._retire(req)
        elif req.slot is not None:
            self.cur_tokens[req.slot] = token

    def _replay(self, req: Request, row) -> int:
        """Emit one request's share of a dispatch's pull: the leading
        valid tokens of ``row`` (-1 ends them: the device has already
        applied EOS and budget). One report call for the dispatch, made
        BEFORE the emits because the last of them may retire the request.
        Returns the number of tokens emitted."""
        n = 0
        while n < len(row) and row[n] >= 0:
            n += 1
        if n:
            self.report.record_tokens(req.request_id, n)
        for m in range(n):
            self._emit(req, int(row[m]))
            if req.finished:
                return m + 1
        return n

    def _retire(self, req: Request, aborted: bool = False) -> None:
        req.state = "aborted" if aborted else "done"
        if req.slot is not None:
            self.free_slots.append(req.slot)
            self.active.pop(req.slot, None)
            self.prefilling.pop(req.slot, None)
            self.held.pop(req.slot, None)
            req.slot = None
        self.report.record_retire(req.request_id, aborted=aborted)

    def _hold(self, req: Request) -> None:
        """Terminal-by-budget request parks in 'held' instead of
        retiring: the slot stays bound (its KV rows, cursor, and PRNG
        key intact) until ``export_handoff`` + ``release_held`` — the
        prefill side of the disaggregated fleet (fleet/pools.py). The
        conveyor defers the release until the handoff TRANSPORT reports
        a terminal status, so a slot may stay held across many engine
        steps while its bytes are in flight — ``export_handoff`` is a
        pure read precisely so that window is harmless."""
        req.state = "held"
        self.active.pop(req.slot, None)
        self.prefilling.pop(req.slot, None)
        self.held[req.slot] = req

    def release_held(self, req: Request, aborted: bool = False) -> None:
        """Free a held request's slot (after ``export_handoff`` reached
        a terminal outcome — adopted by a peer, or abandoned)."""
        self._settle()
        if req.state != "held" or self.held.get(req.slot) is not req:
            raise ValueError(
                f"request {req.request_id} is not held by this engine")
        self._retire(req, aborted=aborted)

    def abort_held(self, req: Request) -> None:
        """Release a held slot whose handoff could NOT be delivered
        (transport attempt budget exhausted): the slot frees cleanly,
        the retire is counted as an abort, and the receiver's clean
        re-prefill owns the stream from here — this engine must not
        keep decoding it."""
        self.release_held(req, aborted=True)

    def export_handoff(self, req: Request) -> dict:
        """Package a HELD request's device state for a decode replica:
        per-block KV rows up to the real fill level, the cursor, the
        post-sampling PRNG key row, the emitted tokens, and the sampling
        knobs. ``fleet/handoff.py`` serializes this dict to a
        manifest-versioned wire blob; raw-format round-trips are
        bitwise, so the importing engine continues the exact stream."""
        self._settle()
        if req.state != "held" or self.held.get(req.slot) is not req:
            raise ValueError(
                f"request {req.request_id} is not held by this engine")
        slot = req.slot
        # every emitted token except the newest has been written into
        # the cache (the newest is the decode input still in flight)
        fill = int(req.prompt.size + len(req.tokens) - 1)
        return {
            "pages": self.steps.export_slot(slot, fill),
            "cursor": fill,
            "tokens": list(req.tokens),
            "key": np.asarray(self._keys[slot]),
            "prompt_len": int(req.prompt.size),
            "eos_id": req.eos_id,
            "temperature": req.temperature,
            "top_k": req.top_k,
            "seed": req.seed,
            "weights_version": self.weights_version,
        }

    def export_session(self, req: Request) -> dict:
        """Freeze an ACTIVELY DECODING request at its current token
        boundary and package it for another engine — the decode→decode
        generalization of ``export_handoff``. The slot moves to 'held'
        (decode stops advancing it; ``_decode``'s park pins its cursor),
        so the exported KV rows, PRNG key row, and token history are a
        consistent snapshot no matter how many steps run while the
        bytes are in flight. The dict is ``export_handoff``'s plus the
        remaining-budget field ``max_new_tokens``; ``import_session``
        on the adopting engine continues the stream BITWISE (raw wire),
        because the per-slot key already consumed exactly one split per
        sampled token. Terminal outcomes mirror the prefill conveyor:
        ``release_held`` after the peer adopts, ``abort_held`` if the
        transport gives up (the stream then replays from seed), or
        ``resume_session`` to keep decoding here."""
        self._settle()
        if req.state == "held" and self.held.get(req.slot) is req:
            raise ValueError(
                f"request {req.request_id} is a held prefill-handoff "
                "slot — migrate it with export_handoff (the "
                "prefill→decode conveyor); export_session moves "
                "actively DECODING slots")
        if req.slot is not None and self.prefilling.get(req.slot) is req:
            raise ValueError(
                f"request {req.request_id} is mid-prefill — a "
                "partially written slot cannot migrate; let prefill "
                "finish (first token sampled) or re-queue the request "
                "on the destination")
        if req.slot is None or self.active.get(req.slot) is not req:
            raise ValueError(
                f"request {req.request_id} is not actively decoding on "
                f"this engine (state={req.state!r})")
        self.active.pop(req.slot)
        req.state = "held"
        self.held[req.slot] = req
        out = self.export_handoff(req)
        out["max_new_tokens"] = int(req.max_new_tokens)
        return out

    def resume_session(self, req: Request) -> None:
        """Un-freeze a session ``export_session`` held: the slot's KV
        rows, cursor, key, and sampling rows never moved, so decoding
        continues here exactly where it stopped (the migration was
        abandoned before the destination adopted)."""
        self._settle()
        if req.state != "held" or self.held.get(req.slot) is not req:
            raise ValueError(
                f"request {req.request_id} is not held by this engine")
        hit_eos = (req.eos_id is not None and req.tokens
                   and req.tokens[-1] == req.eos_id)
        if hit_eos or len(req.tokens) >= req.max_new_tokens:
            raise ValueError(
                f"request {req.request_id} is terminal (a prefill-hold "
                "park, not a frozen session) — release_held it")
        del self.held[req.slot]
        req.state = "running"
        self.active[req.slot] = req
        self.cur_tokens[req.slot] = req.tokens[-1]

    def import_session(self, session: dict, prompt) -> Request:
        """Adopt a migrated decode session (``export_session``'s dict,
        wire-decoded by ``fleet/handoff.py``). The per-request budget
        travels IN the session — the continued stream stops exactly
        where the unmigrated one would have."""
        if "max_new_tokens" not in session:
            raise ValueError(
                "not a decode-session export (no max_new_tokens) — "
                "prefill handoffs are adopted with import_handoff")
        return self.import_handoff(
            session, prompt,
            max_new_tokens=int(session["max_new_tokens"]))

    def import_handoff(self, handoff: dict, prompt,
                       max_new_tokens: Optional[int] = None) -> Request:
        """Adopt an exported slot: bind a free slot, write the KV rows
        and cursor, restore the PRNG key and sampling rows, and resume
        decoding from the handed-off last token. The resumed stream is
        bitwise-identical to the exporting engine continuing (raw wire
        format) — the disaggregation contract
        (``tests/fleet_tests/test_handoff.py``)."""
        self._settle()
        if not self.free_slots:
            raise RuntimeError("no free slot to import a handoff into")
        hv = handoff.get("weights_version")
        if (hv is not None and self.weights_version is not None
                and hv != self.weights_version):
            raise WeightsVersionSkew(
                f"handoff was minted under weights {hv!r} but this "
                f"engine serves {self.weights_version!r} — refusing "
                "the adoption (fall back to a clean re-prefill / "
                "replay-from-seed so the stream stays one version)")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size != int(handoff["prompt_len"]):
            raise ValueError(
                f"handoff prompt_len {handoff['prompt_len']} does not "
                f"match the supplied prompt ({prompt.size})")
        if not handoff["tokens"]:
            raise ValueError("handoff carries no sampled token")
        req = Request(
            request_id=next(self._ids), prompt=prompt,
            max_new_tokens=(max_new_tokens if max_new_tokens is not None
                            else self.config.max_new_tokens),
            eos_id=handoff["eos_id"], temperature=handoff["temperature"],
            top_k=handoff["top_k"], seed=handoff["seed"],
            tokens=list(handoff["tokens"]), state="running")
        self.report.record_submit(req.request_id)
        slot = self.free_slots.pop(0)
        req.slot = slot
        self._temps[slot] = (req.temperature
                             if req.temperature is not None else 0.0)
        self._topks[slot] = req.top_k if req.top_k is not None else 0
        self._eos[slot] = req.eos_id if req.eos_id is not None else -1
        # the handed-off key CONTINUES the stream (one split consumed
        # per sampled token so far) — never re-derive from the seed
        self._keys = self._keys.at[slot].set(
            jnp.asarray(handoff["key"], jnp.uint32))
        # a wire-decoded handoff from an int8-resident source carries
        # the verbatim codes next to the dequantized pages — an int8
        # destination adopts those bytes directly (zero extra
        # quantization error, fleet/handoff.py)
        pages = handoff["pages"]
        if (self.steps.kv_dtype == "int8-block"
                and handoff.get("pages_q8")):
            pages = handoff["pages_q8"]
        self.steps.import_slot(slot, pages, int(handoff["cursor"]))
        last = req.tokens[-1]
        hit_eos = req.eos_id is not None and last == req.eos_id
        if hit_eos or len(req.tokens) >= req.max_new_tokens:
            self._retire(req)              # already terminal at handoff
        else:
            self.cur_tokens[slot] = last
            self.active[slot] = req
        return req

    def abort_all(self, requeue: bool = False) -> List[Request]:
        """Watchdog-bounded teardown: every in-flight request aborts (or
        requeues for a warm restart) and every queued request drains back
        to the caller. Returns the affected requests."""
        self._settle()
        hit = []
        inflight = (list(self.active.values())
                    + list(self.prefilling.values())
                    + list(self.held.values()))
        for req in inflight:
            if requeue:
                req.state = "queued"
                req.tokens = []
                req.prefill_pos = 0
                if req.slot is not None:
                    self.free_slots.append(req.slot)
                    self.active.pop(req.slot, None)
                    self.prefilling.pop(req.slot, None)
                    self.held.pop(req.slot, None)
                    req.slot = None
                self.queue.appendleft(req)
            else:
                self._retire(req, aborted=True)
            hit.append(req)
        if not requeue:
            while self.queue:
                req = self.queue.popleft()
                req.state = "aborted"
                self.report.record_retire(req.request_id, aborted=True)
                hit.append(req)
        return hit

    def swap_weights(self, params, weights_version: Optional[str] = None,
                     *, converted: bool = False):
        """Install new weights on a QUIESCENT engine (the SWAP leg of a
        rolling update — fleet/rollout.py). Refused while any request
        is queued, decoding, prefilling, or held: a mid-stream weight
        change would mix model versions inside one output. Drain first
        (``Router.drain`` migrates live sessions to survivors), swap,
        then readmit. No recompile happens — params are per-call
        arguments to every jitted program (``ServingStep.load_params``).

        Returns ``(old_params, old_version)`` — the previous weights in
        the engine's INTERNAL (already layout-converted) form, so a
        failed rollout can walk this replica back with
        ``swap_weights(old_params, old_version, converted=True)``.
        ``converted=True`` skips the caller-layout conversion for
        exactly that round-trip."""
        self._settle()
        if self.queue or self.active or self.prefilling or self.held:
            raise RuntimeError(
                "swap_weights requires a drained engine — "
                f"{len(self.queue)} queued, {len(self.active)} active, "
                f"{len(self.prefilling)} prefilling, "
                f"{len(self.held)} held")
        old_params = self.steps.params
        old_version = self.weights_version
        if converted:
            self.steps.params = params
        else:
            self.steps.load_params(params)
        self.weights_version = weights_version
        return old_params, old_version

    # ----------------------------------------------------------------
    # scheduler iterations
    # ----------------------------------------------------------------

    def _max_decode_advance(self) -> int:
        """Cache columns one decode iteration may write per slot — the
        wrap guard's and token budget's reservation unit. The base
        engine advances ``decode_k`` (``2 · decode_k`` when its rounds are
        self-drafted); ``speculative.SpeculativeEngine`` overrides this
        with its verify width (``spec_k + 1``)."""
        return self.config.decode_k * (2 if self.config.self_draft else 1)

    def _on_prefill(self, tokens, lengths, slot_ids) -> None:
        """Subclass hook, fired after every monolithic prefill dispatch
        with the cohort's host-side arrays (sentinel rows included).
        ``SpeculativeEngine`` mirrors the prompts into the draft model's
        pages here; the base engine does nothing."""

    def _on_prefill_chunk(self, tokens, starts, valid, slot_ids,
                          final) -> None:
        """Chunked twin of :meth:`_on_prefill` — fired after every
        chunk dispatch with that dispatch's host-side arrays."""

    def _head_cohort(self) -> Tuple[int, int, bool]:
        """``(bucket, size, closed)`` of the queue's head cohort: the leading
        same-bucket run, at most ``prefill_cohort`` long. It is CLOSED when
        the run is full or a request of another bucket stands behind it:
        arrivals only append, so nothing can change a closed head before it
        is admitted."""
        s = self.config.prefill_cohort
        bucket = self._bucket_for(self.queue[0].prompt.size)
        size = 0
        for req in self.queue:
            if size == s or self._bucket_for(req.prompt.size) != bucket:
                return bucket, size, True
            size += 1
        return bucket, size, size == s

    def _admission_is_fixed(self) -> bool:
        """Whether what :meth:`_admit` would decide at the top of the NEXT
        iteration is fixed now, with this iteration's ``decode_k`` enqueued
        and its tokens not yet seen: no cohort in flight already, monolithic
        mode, no token budget (its ``avail`` depends on the retirements to
        come), a closed head cohort, and a free slot for each of its
        requests. ``free_slots`` is FIFO, so the slots this decode's
        retirements free come behind the ones taken: the same requests in
        the same slots as the late path would choose."""
        cfg = self.config
        if (self._ahead is not None or cfg.prefill_chunk is not None
                or cfg.token_budget is not None or not self.queue):
            return False
        _, size, closed = self._head_cohort()
        return closed and len(self.free_slots) >= size

    def _in_flight(self) -> List[Request]:
        """The requests of the cohort admitted ahead and not settled yet."""
        return self._ahead[0] if self._ahead is not None else []

    def _admit_ahead(self) -> None:
        """Between ``decode_k``'s enqueue and the wait for its tokens: the
        next iteration's admission, if it is fixed already. The device runs
        the prefill right behind the decode, under the host's emit and the
        caller's bookkeeping, instead of waiting for the host to compose it."""
        if self._admission_is_fixed():
            self._admit(float("inf"), ahead=True)

    def _fork_slot_rows(self) -> None:
        """Fresh copies of the per-slot host arrays the compiled programs
        are given. An admission ahead writes rows of them while the decode
        dispatch that was handed the same arrays may not have read them yet
        (a backend may alias or read a NumPy argument after the call
        returns): the new rows go into the copies."""
        self._temps = self._temps.copy()
        self._topks = self._topks.copy()
        self._eos = self._eos.copy()

    def _admit(self, avail: float, ahead: bool = False) -> int:
        """One monolithic prefill cohort: same-bucket FIFO prompts into
        free slots, first token sampled on device. ``ahead`` leaves the
        cohort in flight for the next iteration to settle."""
        if not self.queue or not self.free_slots:
            return 0
        s = self.config.prefill_cohort
        bucket, size, _ = self._head_cohort()
        if (bucket > avail and self.active
                and self._prefill_defer < self.config.max_prefill_defer):
            # over budget: let decode keep the iteration, try again next
            # time (the defer cap bounds prefill starvation)
            self._prefill_defer += 1
            return 0
        self._prefill_defer = 0
        cohort: List[Request] = []
        with tracing.span("engine.admit", bucket=bucket,
                          ahead=int(ahead)) as sp:
            if ahead:
                self._fork_slot_rows()
            for _ in range(min(size, len(self.free_slots))):
                req = self.queue.popleft()
                self._install(req, self.free_slots.pop(0))
                cohort.append(req)
            tokens = np.zeros((s, bucket), np.int32)
            lengths = np.ones(s, np.int32)          # sentinel rows: length 1
            slot_ids = np.full(s, self.steps.n_slots, np.int32)  # sentinel
            for i, req in enumerate(cohort):
                tokens[i, :req.prompt.size] = req.prompt
                lengths[i] = req.prompt.size
                slot_ids[i] = req.slot
            tok, self._keys = self.steps.prefill_sampled(
                tokens, lengths, slot_ids, self._keys, self._temps,
                self._topks)
            self._on_prefill(tokens, lengths, slot_ids)
            if sp:
                filled = sum(r.prompt.size for r in cohort)
                sp.set(admitted=len(cohort), rows=s, prompt_tokens=filled,
                       padded_tokens=s * bucket - filled,
                       state_bytes=len(cohort) * self.steps.slot_bytes)
        self._ahead = (cohort, tok)
        return len(cohort) if ahead else self._settle()

    def _settle(self) -> int:
        """Wait for the first tokens of the cohort in flight, if any, and
        emit them: its requests join ``active`` here. ``step()`` settles in
        place of admitting; everything that changes the engine between two
        steps, or reads a request's pages, settles first. Returns the
        number of requests settled."""
        if self._ahead is None:
            return 0
        cohort, tok = self._ahead
        self._ahead = None
        with tracing.span("engine.prefill.wait"):
            first = np.asarray(tok)         # [S] int32 — ids, never logits
        self.report.record_host_bytes(first.nbytes)
        with tracing.span("engine.emit") as sp:
            for i, req in enumerate(cohort):
                self.active[req.slot] = req
                self._replay(req, first[i:i + 1])
            if sp:
                sp.set(tokens=len(cohort),
                       retired=sum(r.finished for r in cohort))
        return len(cohort)

    def _advance_prefill_chunks(self, avail: float) -> int:
        """Chunked prefill scheduling: spend the iteration's leftover
        token budget on fixed-size chunk cohorts — in-flight prefills
        first (oldest request first), fresh admissions filling the rest
        of each cohort. Two overrides beat the budget: the WRAP GUARD
        (a prefilling slot within ``decode_k`` of the page end must
        finish before decode's garbage rows can wrap its cursor over
        real prefix tokens) and the livelock guard (if nothing else can
        make progress this iteration, one cohort runs regardless)."""
        cfg = self.config
        c = cfg.prefill_chunk
        s = cfg.prefill_cohort
        admitted = 0
        spent = 0
        dispatched = False
        while True:
            forced = sorted(
                slot for slot, r in self.prefilling.items()
                if (r.prefill_pos + self._max_decode_advance()
                    > self.steps.capacity))
            if not forced:
                if not (self.prefilling
                        or (self.queue and self.free_slots)):
                    break
                if dispatched and cfg.token_budget is None:
                    break       # unbudgeted: one cohort per iteration
                over = spent + c > avail
                starved = self._prefill_defer >= cfg.max_prefill_defer
                if over and not starved and (self.active or dispatched):
                    self._prefill_defer += 1
                    break
            with tracing.span("engine.admit", chunk=c) as sp:
                cohort = [(slot, self.prefilling[slot])
                          for slot in forced[:s]]
                for slot, req in sorted(self.prefilling.items(),
                                        key=lambda kv: kv[1].request_id):
                    if len(cohort) >= s:
                        break
                    if all(slot != s0 for s0, _ in cohort):
                        cohort.append((slot, req))
                fresh = 0
                while len(cohort) < s and self.queue and self.free_slots:
                    req = self.queue.popleft()
                    slot = self.free_slots.pop(0)
                    self._install(req, slot)
                    self.prefilling[slot] = req
                    fresh += 1
                    cohort.append((slot, req))
                admitted += fresh
                if cohort:
                    self._prefill_defer = 0
                    spent += len(cohort) * c
                    tok, valid, final = self._dispatch_chunk(cohort)
                    if sp:
                        v = valid[:len(cohort)].astype(np.int64)
                        at = np.array([r.prefill_pos for _, r in cohort],
                                      np.int64)
                        filled = int(v.sum())
                        # start_tokens: columns already in the cohort's
                        # pages; attended_pairs: the query-column pairs a
                        # causal attention over page + chunk computes
                        sp.set(admitted=fresh, rows=s, prompt_tokens=filled,
                               padded_tokens=s * c - filled,
                               start_tokens=int(at.sum()),
                               attended_pairs=int(
                                   (v * at + v * (v + 1) // 2).sum()))
                        if self.steps.chunk_attention:
                            sp.set(chunk_attention=self.steps.chunk_attention)
            if not cohort:
                break
            self._finish_chunk(cohort, tok, valid, final)
            dispatched = True
        return admitted

    def _dispatch_chunk(self, cohort):
        """Enqueue one fixed-shape ``[S, C]`` chunk dispatch; returns the
        first-token ids ON DEVICE with the cohort's ``valid`` and ``final``
        rows for :meth:`_finish_chunk`."""
        cfg = self.config
        c = cfg.prefill_chunk
        s = cfg.prefill_cohort
        tokens = np.zeros((s, c), np.int32)
        starts = np.zeros(s, np.int32)
        valid = np.ones(s, np.int32)            # sentinel rows: 1 token
        sids = np.full(s, self.steps.n_slots, np.int32)
        final = np.zeros(s, bool)
        for i, (slot, req) in enumerate(cohort):
            pos = req.prefill_pos
            v = min(c, req.prompt.size - pos)
            tokens[i, :v] = req.prompt[pos:pos + v]
            starts[i] = pos
            valid[i] = v
            sids[i] = slot
            final[i] = pos + v == req.prompt.size
        tok, self._keys = self.steps.prefill_chunk(
            tokens, starts, valid, sids, final, self._keys, self._temps,
            self._topks)
        self._on_prefill_chunk(tokens, starts, valid, sids, final)
        return tok, valid, final

    def _finish_chunk(self, cohort, tok, valid, final) -> None:
        """Pull a chunk dispatch's ids; completing rows emit their first
        token and move to decode."""
        with tracing.span("engine.prefill.wait"):
            first = np.asarray(tok)         # [S] int32 ids (-1 = not final)
        self.report.record_host_bytes(first.nbytes)
        with tracing.span("engine.emit") as sp:
            done = []
            for i, (slot, req) in enumerate(cohort):
                req.prefill_pos += int(valid[i])
                if final[i]:
                    del self.prefilling[slot]
                    self.active[slot] = req
                    self._replay(req, first[i:i + 1])
                    done.append(req)
            if sp:
                sp.set(tokens=len(done),
                       retired=sum(r.finished for r in done))

    def _decode(self) -> int:
        """One ``decode_k`` dispatch for the whole grid; the host pulls
        a single ``[n_slots, k]`` int32 array (validity in-band as -1)
        and replays the device's EOS/budget retirement decisions. A
        self-drafted dispatch pulls ``[n_slots, 2k]``, round-major: a
        round's second place is -1 where the draft was rejected, both
        past the row's stop."""
        cfg = self.config
        n = cfg.n_slots
        with tracing.span("engine.decode.enqueue",
                          live=len(self.active)) as enq:
            if enq:     # page columns the live slots hold at this dispatch
                enq.set(filled_columns=sum(
                    r.prompt.size + len(r.tokens) - 1
                    for r in self.active.values()))
            live = np.zeros(n, bool)
            remaining = np.ones(n, np.int32)
            for slot, req in self.active.items():
                live[slot] = True
                remaining[slot] = req.max_new_tokens - len(req.tokens)
            park = np.zeros(n, np.int32)
            for slot, req in self.prefilling.items():
                park[slot] = req.prefill_pos
            for slot, req in self.held.items():
                # a held slot's rows await export: pin its cursor to the
                # real fill so the ride-along garbage steps can't wrap it
                park[slot] = req.prompt.size + len(req.tokens) - 1
            toks_dev, self._keys = self.steps.decode_k(
                self.cur_tokens, self._keys, self._temps, self._topks,
                self._eos, remaining, live, park, cfg.decode_k)
            if enq and self.steps.decode_attention:
                enq.set(decode_attention=self.steps.decode_attention)
            if enq and self.steps.state_step:
                enq.set(state_step=self.steps.state_step)
        self._admit_ahead()
        with tracing.span("engine.decode.wait"):
            toks = np.asarray(toks_dev)         # [n, k] int32 — the ONLY
            #                                     per-token host transfer
            if enq and self.steps.last_decode_stats:
                # what the model counted on the device during the dispatch
                # (expert routing): it came back with the tokens, so
                # reading it waits for nothing
                enq.set(**{name: np.asarray(v).item() for name, v in
                           self.steps.last_decode_stats.items()})
        self.report.record_host_bytes(toks.nbytes)
        with tracing.span("engine.emit") as sp:
            emitted = retired = 0
            if cfg.self_draft:
                # [slot, round, place]: a round ran where its first place
                # holds a token, and its draft was accepted where the second
                # does
                got = toks.reshape(cfg.n_slots, cfg.decode_k, 2) >= 0
                ran = got[..., 0].sum(1)
                took = got[..., 1].sum(1).tolist()
                # whether the last round a row ran gave its second token
                whole = got[np.arange(cfg.n_slots), np.maximum(ran - 1, 0),
                            1].tolist()
                ran = ran.tolist()
                rounds = proposed = accepted = 0
            for slot, req in list(self.active.items()):
                row = toks[slot]
                if cfg.self_draft:
                    row = row[row >= 0]
                emitted += self._replay(req, row)
                retired += req.finished
                if cfg.self_draft:
                    # a draft was verified where the round's first token
                    # left the row alive (the device's ``drafts_verified``):
                    # a stream that ended on a first place did not verify
                    # that round's
                    verified = ran[slot] - bool(
                        req.finished and ran[slot] and not whole[slot])
                    req.drafts_proposed += verified
                    req.drafts_accepted += took[slot]
                    rounds += ran[slot]
                    proposed += verified
                    accepted += took[slot]
            if cfg.self_draft:
                self.report.record_spec_round(proposed, accepted, emitted,
                                              rounds=rounds)
            if sp:
                sp.set(tokens=emitted, retired=retired)
        return emitted

    def step(self) -> dict:
        """One scheduler iteration: chaos hook → token budget → prefill
        (chunked or monolithic; or the settling of the cohort the last
        iteration admitted ahead) → decode_k, with the next iteration's
        admission dispatched behind it when that is fixed → retirement.
        Returns counters for the caller's loop policy. A cohort in flight
        counts as queued until it is settled: it has no token yet."""
        chaos.on_step(self.iteration)
        self.iteration += 1
        with tracing.span("engine.step", iteration=self.iteration) as sp:
            if sp:
                # the queue as it stood before an admission ahead took its
                # head, so that the sample reads as the late path's does
                ahead = self._in_flight()
                head = ahead or self.queue
                sp.set(queued=len(self.queue) + len(ahead),
                       active=len(self.active),
                       oldest_wait_s=(
                           time.perf_counter() - head[0].t_submit
                           if head else 0.0))
            budget = self.config.token_budget
            avail = (float("inf") if budget is None else
                     budget - len(self.active) * self._max_decode_advance())
            if self._ahead is not None:
                admitted = self._settle()
            elif self.config.prefill_chunk is not None:
                admitted = self._advance_prefill_chunks(avail)
            else:
                admitted = self._admit(avail)
            emitted = self._decode() if self.active else 0
            queued = len(self.queue) + len(self._in_flight())
            self.report.record_step(
                queued,
                (len(self.active) + len(self.prefilling))
                / self.config.n_slots)
        return {"admitted": admitted, "emitted": emitted,
                "active": len(self.active), "queued": queued}

    def idle(self) -> bool:
        return (not self.queue and not self.active and not self.prefilling
                and self._ahead is None)

    def run_until_drained(self, max_steps: int = 100_000) -> int:
        """Step until no queued or active work remains; returns the
        number of iterations taken."""
        n = 0
        while not self.idle():
            if n >= max_steps:
                raise RuntimeError(
                    f"engine failed to drain within {max_steps} steps")
            # step() syncs internally: one [n_slots, k] int32 pull
            self.step()  # dlint: disable=DL104
            n += 1
        return n
