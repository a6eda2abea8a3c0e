"""KVHandoff codec: raw round-trips are bitwise, int8-block error is
bounded by the per-block quantization step (the PR-8 codec contract
applied to KV pages), wire bytes are exact, and every defect —
truncation, corruption, unknown format, broken manifest — is REFUSED
with HandoffError instead of poisoning a decode slot."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.collectives.quantized import (QUANT_BLOCK,
                                                 block_quantize)
from chainermn_tpu.fleet import DisaggregatedFleet
from chainermn_tpu.fleet.handoff import (HandoffError, decode_handoff,
                                         encode_handoff,
                                         handoff_payload_bytes)
from chainermn_tpu.models.transformer import TransformerLM
from chainermn_tpu.serving.engine import Engine, EngineConfig

VOCAB = 43
PROMPT_LEN = 8


def _model(**kw):
    # d_head = 8, n_kv = 4: a full-prompt KV leaf is 8×4×8 = 256 f32 —
    # exactly one quant block, so wire accounting is easy to eyeball
    base = dict(vocab=VOCAB, d_model=32, n_heads=4, n_layers=1, d_ff=48,
                max_len=64, attention="reference", pos_emb="rope")
    base.update(kw)
    return TransformerLM(**base)


@functools.lru_cache(maxsize=None)
def _setup(seed=0):
    model = _model()
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def _cfg(**kw):
    base = dict(n_slots=2, capacity=16, max_new_tokens=6,
                prefill_cohort=1, buckets=[PROMPT_LEN, 16])
    base.update(kw)
    return EngineConfig(**base)


@functools.lru_cache(maxsize=None)
def _handoff(seed=0, temperature=None, top_k=None):
    """Prefill one prompt to its first token and export the held slot."""
    model, params = _setup()
    eng = Engine(model, params, _cfg())
    rng = np.random.RandomState(seed)
    prompt = rng.randint(0, VOCAB, (PROMPT_LEN,)).astype(np.int32)
    req = eng.submit(prompt, max_new_tokens=1, hold=True,
                     temperature=temperature, top_k=top_k, seed=seed)
    while not eng.held:
        eng.step()  # dlint: disable=DL104
    handoff = eng.export_handoff(req)
    eng.release_held(req)
    assert sorted(eng.free_slots) == [0, 1], "release must free the slot"
    return handoff, prompt


def test_raw_roundtrip_is_bitwise():
    handoff, _prompt = _handoff()
    manifest, blob = encode_handoff(handoff, "f32")
    assert manifest["format"] == 1
    assert handoff_payload_bytes(manifest) == len(blob)
    out = decode_handoff(manifest, blob)
    for blk, page in handoff["pages"].items():
        for leaf in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(page[leaf]),
                                          out["pages"][blk][leaf])
    np.testing.assert_array_equal(np.asarray(handoff["key"]), out["key"])
    for key in ("cursor", "tokens", "prompt_len", "eos_id",
                "temperature", "top_k", "seed"):
        assert out[key] == handoff[key]


def test_int8_block_error_bounded_by_quant_step():
    """Per element: |kv - deq(q(kv))| <= scale/2 with the PER-BLOCK
    scale — the exact bound tests/collectives_tests pins for the wire
    codec, holding through the handoff container."""
    handoff, _prompt = _handoff()
    manifest, blob = encode_handoff(handoff, "int8-block")
    assert manifest["format"] == 2
    assert manifest["codec"]["wire_format"] == "int8-block"
    out = decode_handoff(manifest, blob)
    for blk, page in handoff["pages"].items():
        for leaf in ("k", "v"):
            v = np.asarray(page[leaf], np.float32).reshape(-1)
            _q, s = block_quantize(jnp.asarray(v), "int8-block")
            step = np.repeat(np.asarray(s), QUANT_BLOCK)[:v.size]
            deq = np.asarray(out["pages"][blk][leaf],
                             np.float32).reshape(-1)
            assert (np.abs(deq - v) <= step / 2 + 1e-7).all()


def test_int8_block_logit_error_calibrated():
    """Decoding from an int8 handoff perturbs the next-step logits by
    no more than a small multiple of the KV quantization step (the
    handoff-level observable the wire-level bound buys)."""
    model, params = _setup()
    handoff, prompt = _handoff()
    max_step = 0.0
    for page in handoff["pages"].values():
        for leaf in ("k", "v"):
            v = np.asarray(page[leaf], np.float32).reshape(-1)
            _q, s = block_quantize(jnp.asarray(v), "int8-block")
            max_step = max(max_step, float(np.asarray(s).max()) / 2)
    logits = {}
    for wf in ("f32", "int8-block"):
        manifest, blob = encode_handoff(handoff, wf)
        eng = Engine(model, params, _cfg())
        req = eng.import_handoff(decode_handoff(manifest, blob), prompt)
        eng.step()  # dlint: disable=DL104
        logits[wf] = eng.last_logits[req.slot].copy()
    dlogit = np.abs(logits["int8-block"] - logits["f32"]).max()
    assert 0 < dlogit <= 10 * max_step, (dlogit, max_step)


def test_wire_bytes_exact_and_quantized_ratio():
    """manifest["bytes"] is the exact blob length; with one-block
    leaves the int8-block wire is (256 + 4)/1024 of raw + the shared
    key tail — comfortably under 0.27 of raw."""
    handoff, _prompt = _handoff()
    m_raw, b_raw = encode_handoff(handoff, "f32")
    m_q, b_q = encode_handoff(handoff, "int8-block")
    key_bytes = np.asarray(handoff["key"]).nbytes
    page_bytes = sum(np.asarray(p[leaf]).nbytes
                     for p in handoff["pages"].values()
                     for leaf in ("k", "v"))
    assert handoff_payload_bytes(m_raw) == len(b_raw)
    assert len(b_raw) == page_bytes + key_bytes
    assert handoff_payload_bytes(m_q) == len(b_q)
    assert len(b_q) - key_bytes <= 0.27 * page_bytes


def test_unknown_wire_format_rejected_at_encode():
    handoff, _prompt = _handoff()
    with pytest.raises(ValueError, match="wire_format"):
        encode_handoff(handoff, "fp8-exotic")


def test_truncated_blob_refused():
    handoff, _prompt = _handoff()
    manifest, blob = encode_handoff(handoff, "f32")
    with pytest.raises(HandoffError, match="truncated"):
        decode_handoff(manifest, blob[:len(blob) - 16])


def test_corrupted_blob_refused():
    handoff, _prompt = _handoff()
    manifest, blob = encode_handoff(handoff, "f32")
    torn = bytearray(blob)
    torn[100] ^= 0x40
    with pytest.raises(HandoffError, match="sha256"):
        decode_handoff(manifest, bytes(torn))


def test_unknown_manifest_format_refused():
    handoff, _prompt = _handoff()
    manifest, blob = encode_handoff(handoff, "f32")
    manifest = dict(manifest, format=99)
    with pytest.raises(HandoffError, match="format"):
        decode_handoff(manifest, blob)


def test_structurally_broken_manifest_refused():
    """A manifest missing its arrays table (or any required key) is a
    HandoffError too — the caller's fallback contract covers EVERY
    defect, not just checksum failures."""
    handoff, _prompt = _handoff()
    manifest, blob = encode_handoff(handoff, "f32")
    for broken in (
            {k: v for k, v in manifest.items() if k != "arrays"},
            {k: v for k, v in manifest.items() if k != "meta"},
            {k: v for k, v in manifest.items() if k != "sha256"},
    ):
        with pytest.raises(HandoffError):
            decode_handoff(broken, blob)


def test_sampled_handoff_preserves_key_and_knobs():
    """A temperature/top_k handoff carries the CONTINUED PRNG key (one
    split already consumed for the prefill token) and the sampling
    knobs verbatim — the decode side must resume the stream, not
    restart it."""
    from chainermn_tpu.serving.sampling import request_key

    handoff, _prompt = _handoff(seed=3, temperature=0.8, top_k=5)
    manifest, blob = encode_handoff(handoff, "f32")
    out = decode_handoff(manifest, blob)
    assert out["temperature"] == 0.8 and out["top_k"] == 5
    assert out["seed"] == 3
    # the key must NOT be the fresh request key — a split was consumed
    fresh = np.asarray(request_key(3))
    assert not np.array_equal(out["key"], fresh)


# ---------------------------------------------------------------------------
# Streamed (format-5) handoffs: per-layer chunk frames + closing manifest
# ---------------------------------------------------------------------------

from chainermn_tpu.fleet.handoff import (CHUNKS_PER_STREAM,
                                         decode_handoff_streamed,
                                         encode_handoff_streamed,
                                         streamed_chunk_sid,
                                         streamed_parent_sid,
                                         streamed_wire_bytes)


def _multi_handoff(n_blocks=3, seed=7):
    """A handcrafted multi-block handoff: the streamed codec is pure
    bytes-in/bytes-out, so it needs page arrays, not a live engine."""
    rng = np.random.RandomState(seed)
    pages = {f"block{i}": {
        "k": rng.rand(8, 2, 4).astype(np.float32),
        "v": rng.rand(8, 2, 4).astype(np.float32)} for i in range(n_blocks)}
    return {"pages": pages, "cursor": 8, "tokens": [1, 2],
            "key": np.asarray([3, 4], np.uint32), "prompt_len": 8,
            "eos_id": None, "temperature": None, "top_k": None, "seed": 0}


def test_streamed_chunk_sid_roundtrips_and_bounds():
    assert streamed_parent_sid(streamed_chunk_sid(17, 3)) == (17, 3)
    assert streamed_chunk_sid(0, 0) == -1          # negative: no client sid
    with pytest.raises(ValueError):
        streamed_chunk_sid(1, CHUNKS_PER_STREAM)
    with pytest.raises(ValueError):
        streamed_parent_sid(5)


def test_streamed_roundtrip_is_bitwise_one_chunk_per_block():
    handoff = _multi_handoff()
    chunks, closing, closing_blob = encode_handoff_streamed(handoff, "f32")
    assert len(chunks) == 3 and closing["kind"] == "closing"
    out = decode_handoff_streamed(closing, closing_blob, chunks)
    for block in handoff["pages"]:
        for leaf in ("k", "v"):
            np.testing.assert_array_equal(out["pages"][block][leaf],
                                          handoff["pages"][block][leaf])
    assert out["tokens"] == handoff["tokens"]
    np.testing.assert_array_equal(out["key"], handoff["key"])


def test_streamed_wire_bytes_equal_monolithic_blob():
    """Chunking must not inflate the priced payload: the sum of chunk
    bytes plus the closing blob equals the monolithic format-1 blob."""
    handoff = _multi_handoff()
    _manifest, blob = encode_handoff(handoff, "f32")
    _chunks, closing, _cb = encode_handoff_streamed(handoff, "f32")
    assert streamed_wire_bytes(closing) == len(blob)


def test_streamed_int8_roundtrip_error_bounded():
    handoff = _multi_handoff()
    chunks, closing, closing_blob = encode_handoff_streamed(
        handoff, "int8-block")
    out = decode_handoff_streamed(closing, closing_blob, chunks)
    for block in handoff["pages"]:
        for leaf in ("k", "v"):
            ref = handoff["pages"][block][leaf]
            step = np.abs(ref).max() / 127.0
            assert np.abs(out["pages"][block][leaf] - ref).max() \
                <= step + 1e-7


def test_streamed_corrupt_chunk_refused_naming_the_chunk():
    handoff = _multi_handoff()
    chunks, closing, closing_blob = encode_handoff_streamed(handoff, "f32")
    man, blob = chunks[1]
    chunks[1] = (man, blob[:10] + bytes([blob[10] ^ 0xFF]) + blob[11:])
    with pytest.raises(HandoffError, match="chunk 1"):
        decode_handoff_streamed(closing, closing_blob, chunks)


def test_streamed_missing_chunk_refused():
    handoff = _multi_handoff()
    chunks, closing, closing_blob = encode_handoff_streamed(handoff, "f32")
    with pytest.raises(HandoffError, match="incomplete stream"):
        decode_handoff_streamed(closing, closing_blob, chunks[:-1])


def test_streamed_chunk_swapped_from_another_stream_refused():
    """A chunk with a VALID self-manifest lifted from a different
    handoff still fails the closing table's commitment — completeness
    is proven against the table, not per-frame checks."""
    chunks, closing, closing_blob = encode_handoff_streamed(
        _multi_handoff(seed=7), "f32")
    other, _c2, _b2 = encode_handoff_streamed(_multi_handoff(seed=8), "f32")
    chunks[0] = other[0]
    with pytest.raises(HandoffError, match="chunk 0"):
        decode_handoff_streamed(closing, closing_blob, chunks)


def test_streamed_refuses_session_exports():
    handoff = _multi_handoff()
    handoff["max_new_tokens"] = 5      # session migration: whole or not at all
    with pytest.raises(ValueError, match="migrate whole"):
        encode_handoff_streamed(handoff, "f32")


# ---------------------------------------------------------------------------
# int8-resident sources: pages already quantized on the exporting engine
# ship their codes and scales VERBATIM — re-quantizing would stack a
# second rounding error on top of the one the slot already paid
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _resident_handoff(seed=0):
    model, params = _setup()
    cfg = EngineConfig(n_slots=1, capacity=32, max_new_tokens=10,
                       prefill_cohort=1, buckets=[8, 32],
                       kv_dtype="int8-block")
    eng = Engine(model, params, cfg)
    rng = np.random.RandomState(seed)
    prompt = rng.randint(0, VOCAB, (5,)).astype(np.int32)
    req = eng.submit(prompt, max_new_tokens=4, temperature=0.8, top_k=6,
                     seed=3, hold=True)
    eng.run_until_drained()
    handoff = eng.export_handoff(req)
    eng.release_held(req)
    return handoff, prompt


_RESIDENT_LEAVES = ("k_q", "k_s", "v_q", "v_s")


def test_resident_wire_bytes_are_verbatim():
    """The quantized wire IS the resident pages: blob == the source's
    code/scale bytes (packer order per block: k codes, k scales,
    v codes, v scales) + the PRNG key tail. No transform, no extra
    quantization error — bitwise by construction."""
    handoff, _prompt = _resident_handoff()
    manifest, blob = encode_handoff(handoff, "int8-block")
    resident = b"".join(
        np.ascontiguousarray(np.asarray(handoff["pages"][b][leaf])).tobytes()
        for b in sorted(handoff["pages"]) for leaf in _RESIDENT_LEAVES)
    key_tail = np.ascontiguousarray(
        np.asarray(handoff["key"], np.uint32)).tobytes()
    assert blob == resident + key_tail
    # the manifest advertises the PAGE block, not the wire default
    some_page = next(iter(handoff["pages"].values()))
    page_block = (np.asarray(some_page["k_q"]).size
                  // np.asarray(some_page["k_s"]).size)
    assert manifest["codec"]["block"] == page_block


def test_resident_pages_q8_roundtrip_bitwise():
    handoff, _prompt = _resident_handoff()
    manifest, blob = encode_handoff(handoff, "int8-block")
    out = decode_handoff(manifest, blob)
    assert "pages_q8" in out
    for blk in out["pages_q8"]:
        for leaf in _RESIDENT_LEAVES:
            np.testing.assert_array_equal(
                out["pages_q8"][blk][leaf],
                np.asarray(handoff["pages"][blk][leaf]))


def test_resident_adoption_continues_bitwise():
    """int8 source → wire → int8 destination adopts the codes verbatim,
    so the continued stream equals a fresh int8 engine's stream exactly
    (the zero-extra-error observable)."""
    model, params = _setup()
    handoff, prompt = _resident_handoff()
    manifest, blob = encode_handoff(handoff, "int8-block")
    cfg = EngineConfig(n_slots=1, capacity=32, max_new_tokens=10,
                       prefill_cohort=1, buckets=[8, 32],
                       kv_dtype="int8-block")
    dst = Engine(model, params, cfg)
    adopted = dst.import_handoff(decode_handoff(manifest, blob), prompt,
                                 max_new_tokens=8)
    dst.run_until_drained()
    ref_eng = Engine(model, params, cfg)
    ref = ref_eng.submit(prompt, max_new_tokens=8, temperature=0.8,
                         top_k=6, seed=3)
    ref_eng.run_until_drained()
    assert adopted.tokens == ref.tokens


def test_raw_format_from_resident_source_dequantizes_once():
    """An f32 wire from an int8 source carries ONE dequantization — the
    same values an int8 wire's decoder reconstructs."""
    handoff, _prompt = _resident_handoff()
    m_raw, b_raw = encode_handoff(handoff, "f32")
    raw = decode_handoff(m_raw, b_raw)
    assert "pages_q8" not in raw
    m_q, b_q = encode_handoff(handoff, "int8-block")
    quant = decode_handoff(m_q, b_q)
    for blk in handoff["pages"]:
        for leaf in ("k", "v"):
            np.testing.assert_array_equal(raw["pages"][blk][leaf],
                                          quant["pages"][blk][leaf])


def test_streamed_resident_roundtrip_bitwise():
    handoff, _prompt = _resident_handoff()
    chunks, closing, closing_blob = encode_handoff_streamed(
        handoff, "int8-block")
    out = decode_handoff_streamed(closing, closing_blob, chunks)
    for blk in out["pages_q8"]:
        for leaf in _RESIDENT_LEAVES:
            np.testing.assert_array_equal(
                out["pages_q8"][blk][leaf],
                np.asarray(handoff["pages"][blk][leaf]))


def test_f32_source_wire_is_unchanged_by_resident_support():
    """Regression: an f32 source still quantizes at the wire with the
    stock codec block and never grows a pages_q8 face."""
    handoff, _prompt = _handoff()
    manifest, blob = encode_handoff(handoff, "int8-block")
    assert manifest["codec"]["block"] == QUANT_BLOCK
    out = decode_handoff(manifest, blob)
    assert "pages_q8" not in out


# ---------------------------------------------------------------------------
# the codec under the fleet: prefill on one engine, decode on another
# ---------------------------------------------------------------------------

def test_raw_disagg_streams_bitwise_vs_single_engine():
    """The disaggregation contract on real engines: prefill on engine A,
    f32 handoff, decode on engine B — every stream is the single
    engine's, token for token, with no fallback. (Chunked prefill and
    sampled streams: the slow ``test_pools.py``.)"""
    model, params = _setup()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, VOCAB, (PROMPT_LEN,)).astype(np.int32)
               for _ in range(4)]
    fleet = DisaggregatedFleet(Engine(model, params, _cfg()),
                               Engine(model, params, _cfg()))
    streams = [fleet.submit(p, max_new_tokens=6) for p in prompts]
    fleet.run_until_drained()

    single = Engine(model, params, _cfg())
    reqs = [single.submit(p, max_new_tokens=6) for p in prompts]
    single.run_until_drained()

    assert [list(s.tokens) for s in streams] == [list(r.tokens)
                                                 for r in reqs]
    assert all(s.finished and not s.fell_back for s in streams)
    assert fleet.report.handoffs == len(prompts)
    assert fleet.report.handoff_fallbacks == 0
