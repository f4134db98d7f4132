"""Quantized paged KV (ISSUE 19): the int8/int4 block codec, the
quantized serving programs, the ``/v1/score`` quality oracle, and the
v2 migration wire.

Acceptance contracts pinned here:

- **codec exactness properties** — int4 pack/unpack is a bitwise
  roundtrip (odd head_dim included), all-zero rows quantize to scale 0
  and dequantize to EXACT zeros (the sentinel-row invariant the paged
  gather math relies on), per-element reconstruction error is bounded
  by half a quantization step, and the jnp/numpy twins make
  bit-identical decisions (device writes and host prefill landings
  must agree);
- **within-dtype bit-exactness** — a quantized request preempts,
  offloads, migrates over the v2 wire, and resumes emitting the
  IDENTICAL token stream as an unmigrated quantized run (the contract
  temp-0 exactness became under quantization: exact WITHIN a dtype,
  token-agreement-gated ACROSS dtypes);
- **refusal matrix** — torn/truncated/trailing frames, unknown
  versions, kv_dtype mismatches, and wrong per-layer arity are all
  refused loudly; legacy v1 fp records still import;
- **score() is verify-without-accept** — one forward, no serving
  state perturbed, greedy self-agreement exactly 1.0 on the engine's
  own temperature-0 output.
"""

import json
import struct
import urllib.error
import urllib.request

import numpy as np
import pytest

from elephas_tpu.fleet import decode_record, encode_record, migration
from elephas_tpu.serving.kv_quant import (
    KV_DTYPES,
    dequantize_rows,
    dequantize_rows_np,
    pack_int4,
    packed_head_dim,
    pool_bytes_per_pos,
    quantize_rows,
    quantize_rows_np,
    unpack_int4,
)

VOCAB, MAXLEN = 16, 32


@pytest.fixture(scope="module")
def lm():
    """Tiny UNtrained LM — the within-dtype contracts are about
    determinism (a fixed init's argmax is all the parity asserts
    need); cross-dtype quality runs on the trained stand-in in the
    slow test at the bottom."""
    from elephas_tpu.models import transformer_lm

    return transformer_lm(
        vocab_size=VOCAB, maxlen=MAXLEN, d_model=32, num_heads=2,
        num_layers=2, dropout=0.0, seed=0,
    )


def make_engine(lm, **overrides):
    from elephas_tpu.serving import InferenceEngine

    kw = dict(
        num_slots=2, paged=True, block_size=4, num_blocks=16,
        preemption=True,
    )
    kw.update(overrides)
    return InferenceEngine(lm, **kw)


def greedy_tokens(eng, prompt, max_new):
    out = list(eng.run([(list(prompt), max_new)]).values())[0].tolist()
    return out[len(prompt):]


# -- block codec ------------------------------------------------------


class TestCodec:
    def test_int4_pack_unpack_roundtrip(self):
        rng = np.random.default_rng(0)
        for dh in (1, 2, 7, 8, 16):  # odd widths zero-pad the tail
            q = rng.integers(-7, 8, size=(3, 5, 2, dh)).astype(np.int8)
            p = np.asarray(pack_int4(q))
            assert p.shape == (3, 5, 2, packed_head_dim(dh, "int4"))
            assert p.dtype == np.int8
            back = np.asarray(unpack_int4(p, dh))
            np.testing.assert_array_equal(back, q)

    def test_all_zero_rows_roundtrip_to_exact_zeros(self):
        """The sentinel-row invariant: pool rows nothing ever wrote
        are zeros, quantize to scale 0, and MUST dequantize to exact
        zeros — the paged gather feeds them to masked lanes assuming
        they contribute exactly nothing."""
        x = np.zeros((4, 2, 8), np.float32)
        for dt in ("int8", "int4"):
            q, s = quantize_rows_np(x, dt)
            assert not s.any()
            back = dequantize_rows_np(q, s, dt, 8)
            assert back.dtype == np.float32
            assert not back.any()
            qj, sj = quantize_rows(x, dt)
            backj = np.asarray(dequantize_rows(qj, sj, dt, 8))
            assert not backj.any()

    def test_reconstruction_error_bounded(self):
        """|x - dequant(quant(x))| <= scale/2 per element (symmetric
        round-to-nearest), which is what makes the agreement gates
        meaningful rather than luck."""
        rng = np.random.default_rng(1)
        x = rng.normal(size=(16, 4, 32)).astype(np.float32)
        for dt in ("int8", "int4"):
            q, s = quantize_rows_np(x, dt)
            back = dequantize_rows_np(q, s, dt, 32)
            bound = s[..., None] * 0.5 + 1e-7
            assert (np.abs(x - back) <= bound).all(), dt

    def test_bf16_inputs(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(2)
        x = rng.normal(size=(3, 2, 16)).astype(np.float32)
        xb = jnp.asarray(x, dtype=jnp.bfloat16)
        q, s = quantize_rows(xb, "int8")
        assert np.asarray(q).dtype == np.int8
        assert np.asarray(s).dtype == np.float32
        back = np.asarray(dequantize_rows(q, s, "int8", 16))
        # bf16 keeps ~3 significant digits; the roundtrip must land
        # within the bf16 input's own resolution plus a quant step
        assert np.abs(back - np.asarray(xb, np.float32)).max() < 0.05

    def test_jnp_np_twins_bit_identical(self):
        """Device writes (jnp) and host prefill landings (numpy) must
        make the SAME quantization decisions — otherwise an SP-prefill
        handoff would not be bit-exact against a device-prefilled
        block."""
        rng = np.random.default_rng(3)
        # include exact ties (x.5 cases) via a coarse grid, where
        # round-half-to-even either agrees in both or the twin lies
        x = np.concatenate([
            rng.normal(size=(8, 2, 7)).astype(np.float32),
            (rng.integers(-10, 11, size=(8, 2, 7)) / 2.0).astype(
                np.float32
            ),
        ])
        for dt in ("int8", "int4"):
            qj, sj = quantize_rows(x, dt)
            qn, sn = quantize_rows_np(x, dt)
            np.testing.assert_array_equal(np.asarray(qj), qn)
            np.testing.assert_array_equal(np.asarray(sj), sn)
            dj = np.asarray(dequantize_rows(qj, sj, dt, 7))
            dn = dequantize_rows_np(qn, sn, dt, 7)
            np.testing.assert_array_equal(dj, dn)

    def test_byte_math(self):
        specs = [("a", 4, 32), ("b", 4, 7)]
        assert packed_head_dim(7, "int4") == 4
        assert packed_head_dim(7, "int8") == 7
        assert pool_bytes_per_pos(specs, "fp") == (
            (4 * 32 + 4 * 7) * 2 * 4
        )
        assert pool_bytes_per_pos(specs, "int8") == (
            (4 * 32 + 4 * 4) + (4 * 7 + 4 * 4)
        ) * 2
        assert pool_bytes_per_pos(specs, "int4") == (
            (4 * 16 + 4 * 4) + (4 * 4 + 4 * 4)
        ) * 2

    def test_kv_dtype_validation(self):
        from elephas_tpu.serving.kv_quant import check_kv_dtype

        for dt in KV_DTYPES:
            assert check_kv_dtype(dt) == dt
        with pytest.raises(ValueError, match="kv_dtype"):
            check_kv_dtype("int2")


# -- quantized engine -------------------------------------------------


class TestQuantizedEngine:
    def test_flash_naive_parity_within_dtype(self, lm):
        """attention="naive" stays the parity oracle INSIDE a
        kv_dtype: both kernels read the same quantized blocks, so
        temp-0 tokens must match exactly. (Doubles as the basic
        generate-per-dtype smoke — same engines, same streams.)"""
        prompt = [2, 3, 4, 5, 2, 3]
        for dt in ("int8", "int4"):
            f = make_engine(lm, kv_dtype=dt)
            n = make_engine(lm, kv_dtype=dt, attention="naive")
            toks = greedy_tokens(f, prompt, 8)
            assert len(toks) == 8
            assert all(0 <= t < VOCAB for t in toks)
            assert f.debug_snapshot()["kv_dtype"] == dt
            assert toks == greedy_tokens(n, prompt, 8)
            f.release_telemetry()
            n.release_telemetry()

    def test_knob_refusals(self, lm):
        from elephas_tpu.serving import InferenceEngine

        with pytest.raises(ValueError, match="kv_dtype"):
            make_engine(lm, kv_dtype="fp8")
        with pytest.raises(ValueError, match="paged"):
            InferenceEngine(lm, num_slots=2, kv_dtype="int8")

    def test_pool_arity_and_bytes(self, lm):
        fp = make_engine(lm)
        q8 = make_engine(lm, kv_dtype="int8")
        q4 = make_engine(lm, kv_dtype="int4")
        for leaves in fp._caches.values():
            assert len(leaves) == 2
        for eng in (q8, q4):
            for kq, vq, ks, vs in eng._caches.values():
                assert np.asarray(kq).dtype == np.int8
                assert np.asarray(vs).dtype == np.float32
        # same block count, ~3.5x / ~6x fewer arena bytes
        nb_fp = fp.arena.nbytes()
        assert nb_fp / q8.arena.nbytes() > 3.0
        assert nb_fp / q4.arena.nbytes() > 5.0
        for eng in (fp, q8, q4):
            eng.release_telemetry()

    @pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
    def test_second_identical_drive_compiles_nothing(self, lm, kv_dtype):
        """The quantized programs keep the compiled-shape set closed:
        a mixed-length drive compiles what it touches, the same drive
        again compiles nothing."""
        eng = make_engine(lm, kv_dtype=kv_dtype, num_slots=4)
        workload = [
            ([2, 3], 4), ([4, 5, 2, 3, 4], 6),
            ([3, 4, 5, 2, 3, 4, 5, 2, 3], 5),
        ]
        eng.run(workload)
        first = eng.compile_stats()
        assert first["decode_compiles"] >= 1
        eng.run(workload)
        assert eng.compile_stats() == first
        eng.release_telemetry()

    def test_equal_bytes_pool_admits_more(self, lm):
        """What the bytes buy, counted: pools sized to the float
        pool's byte budget through ``pool_bytes_per_pos`` (blocks
        rounded down) store no more bytes than it, array for array,
        and on one over-subscribed request set the schedulers run at
        least twice the float pool's requests at once at int8, and at
        int4 at least int8's."""
        from tests.conftest import peak_admitted

        lanes, block_size, blocks_fp = 32, 4, 16
        probe = make_engine(lm, num_slots=lanes, num_blocks=blocks_fp)
        budget = probe.arena.nbytes()
        engines = {"fp": probe}
        for dt in ("int8", "int4"):
            per_block = block_size * pool_bytes_per_pos(
                probe.arena.specs, dt
            )
            engines[dt] = make_engine(
                lm, num_slots=lanes, num_blocks=budget // per_block,
                kv_dtype=dt,
            )
        stored = {
            dt: sum(
                np.asarray(leaf).nbytes
                for leaves in eng._caches.values() for leaf in leaves
            )
            for dt, eng in engines.items()
        }
        for dt, eng in engines.items():
            assert stored[dt] == eng.arena.nbytes()  # the price is real
            assert stored[dt] <= stored["fp"]
        workload = [([2, 3, 4, 5], 4)] * lanes  # two blocks a request
        peak = {
            dt: peak_admitted(eng.scheduler, workload)
            for dt, eng in engines.items()
        }
        assert peak["fp"] == blocks_fp // 2
        assert peak["int4"] >= peak["int8"] >= 2 * peak["fp"], peak
        for eng in engines.values():
            eng.release_telemetry()

    def test_quant_telemetry_exists_in_every_mode(self, lm):
        """Counter families exist from construction in EVERY mode
        (the stats()/scrape contract), and the info gauge names the
        stored dtype in its label."""
        for dt in ("fp", "int8"):
            eng = make_engine(lm, kv_dtype=dt)
            text = eng.scrape()
            for fam in (
                "elephas_serving_kv_quant_offload_bytes_total",
                "elephas_serving_kv_quant_export_bytes_total",
                "elephas_serving_score_requests_total",
            ):
                assert fam in text, (dt, fam)
            assert "elephas_serving_kv_quant_mode" in text
            assert f'kv_dtype="{dt}"' in text
            eng.release_telemetry()

    def test_preempt_offload_resume_bit_exact_int8(self, lm):
        """Pool pressure preempts a quantized request to host and
        resumes it; the stream must be IDENTICAL to an un-preempted
        int8 run — blocks offload and scatter back at their stored
        bytes, so the roundtrip is bitwise."""
        prompt = [2, 3, 4, 5, 2, 3]
        ref = make_engine(lm, kv_dtype="int8", num_blocks=64)
        want = greedy_tokens(ref, prompt, 16)
        eng = make_engine(lm, kv_dtype="int8", num_blocks=10)
        low = eng.submit(prompt, 16, priority=0)
        eng.step()
        eng.submit([3, 4, 5, 2], 16, priority=5)
        while eng.scheduler.has_work:
            eng.step()
        assert eng.stats()["preemptions"] >= 1
        assert low.done and list(low.tokens) == want
        assert eng.stats()["kv_quant_offload_bytes"] > 0
        ref.release_telemetry()
        eng.release_telemetry()


# -- /v1/score (verify-without-accept) --------------------------------


class TestScore:
    def test_greedy_self_agreement_is_exact(self, lm):
        prompt = [2, 3, 4, 5, 2, 3]
        eng = make_engine(lm)
        toks = greedy_tokens(eng, prompt, 8)
        out = eng.score(prompt, toks)
        assert out["agreement"] == 1.0
        assert out["greedy_tokens"] == toks
        assert len(out["logprobs"]) == len(toks)
        assert all(x <= 0.0 for x in out["logprobs"])
        assert out["total_logprob"] == pytest.approx(
            sum(out["logprobs"])
        )
        eng.release_telemetry()

    def test_score_on_fixed_arena(self, lm):
        from elephas_tpu.serving import InferenceEngine

        for attn in ("flash", "naive"):
            eng = InferenceEngine(lm, num_slots=2, attention=attn)
            toks = greedy_tokens(eng, [2, 3, 4, 5], 6)
            assert eng.score([2, 3, 4, 5], toks)["agreement"] == 1.0
            eng.release_telemetry()

    def test_score_validation(self, lm):
        eng = make_engine(lm)
        with pytest.raises(ValueError, match="non-empty prompt"):
            eng.score([], [1])
        with pytest.raises(ValueError, match="non-empty completion"):
            eng.score([1], [])
        with pytest.raises(ValueError, match="maxlen"):
            eng.score([1] * MAXLEN, [1])
        eng.release_telemetry()

    def test_score_does_not_perturb_serving(self, lm):
        """Scoring mid-flight must not move cursors, allocate blocks,
        or consume PRNG state: a request decoded across interleaved
        score() calls emits the same tokens as an undisturbed one."""
        prompt = [2, 3, 4, 5, 2, 3]
        ref = make_engine(lm)
        want = greedy_tokens(ref, prompt, 8)
        eng = make_engine(lm)
        req = eng.submit(prompt, 8)
        while eng.scheduler.has_work:
            eng.step()
            eng.score([5, 4, 3], [2, 2])
        assert list(req.tokens) == want
        assert eng.stats()["score_requests"] >= 5
        ref.release_telemetry()
        eng.release_telemetry()

    def test_gateway_score_route(self, lm):
        from elephas_tpu.serving import Gateway

        eng = make_engine(lm, kv_dtype="int8")
        gw = Gateway(eng, port=0).start()
        base = f"http://127.0.0.1:{gw.port}"
        try:
            body = json.dumps({
                "prompt": [2, 3, 4, 5], "completion": [3, 3, 3],
            }).encode()
            r = urllib.request.urlopen(urllib.request.Request(
                base + "/v1/score", data=body,
                headers={"Content-Type": "application/json"},
            ))
            out = json.loads(r.read())
            assert set(out) == {
                "logprobs", "total_logprob", "greedy_tokens",
                "agreement",
            }
            assert len(out["logprobs"]) == 3
            # malformed bodies: unknown field, wrong type, empty
            for bad in (
                {"prompt": [1], "completion": [2], "stream": True},
                {"prompt": "abc", "completion": [2]},
                {"prompt": [1], "completion": []},
            ):
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(urllib.request.Request(
                        base + "/v1/score",
                        data=json.dumps(bad).encode(),
                        headers={"Content-Type": "application/json"},
                    ))
                assert ei.value.code == 400, bad
            # the health surface: exactly these fields (the engine
            # runs where JAX put it; there is no fallback to report)
            h = json.loads(
                urllib.request.urlopen(base + "/healthz").read()
            )
            assert set(h) == {
                "status", "steps", "queue_has_work", "driver_alive",
                "weight_version", "anomalies",
            }
            d = json.loads(
                urllib.request.urlopen(base + "/debug/engine").read()
            )
            assert d["kv_dtype"] == "int8"
            assert not any("fallback" in key for key in d)
        finally:
            gw.stop()
            eng.release_telemetry()


# -- migration wire v2 ------------------------------------------------


def warm_export(eng, prompt=(2, 3, 4, 5, 2, 3), steps=3):
    req = eng.submit(list(prompt), 12)
    for _ in range(steps):
        eng.step()
    assert req.tokens
    return req, eng.export_request(req.rid)


def encode_v1(record):
    """Hand-rolled legacy v1 frame (fixed fp k/v pair per layer) —
    what a pre-quantization replica put on the wire."""
    rows = record.get("rows") or {}
    layers, blobs = [], []
    for name in sorted(rows):
        k, v = (np.ascontiguousarray(a) for a in rows[name])
        layers.append({
            "name": str(name),
            "k_shape": list(k.shape), "k_dtype": k.dtype.name,
            "v_shape": list(v.shape), "v_dtype": v.dtype.name,
        })
        blobs += [k.tobytes(), v.tobytes()]
    header = {k2: v2 for k2, v2 in record.items()
              if k2 not in ("rows", "kv_dtype")}
    header["version"] = 1
    header["layers"] = layers
    hb = json.dumps(header).encode("utf-8")
    out = bytearray(b"EMIG") + struct.pack("<HI", 1, len(hb)) + hb
    for blob in blobs:
        out += blob
    return bytes(out)


class TestMigrationWireV2:
    def test_quantized_roundtrip_bit_exact(self, lm):
        a = make_engine(lm, kv_dtype="int8")
        _, rec = warm_export(a)
        assert rec["version"] == migration.VERSION
        assert rec["kv_dtype"] == "int8"
        back = decode_record(encode_record(rec))
        assert back["kv_dtype"] == "int8"
        for name, leaves in rec["rows"].items():
            assert len(leaves) == 4
            for x, y in zip(leaves, back["rows"][name]):
                assert x.dtype == y.dtype
                np.testing.assert_array_equal(np.asarray(x), y)
        a.release_telemetry()

    def test_migrated_stream_matches_unmigrated(self, lm):
        prompt = [2, 3, 4, 5, 2, 3]
        ref = make_engine(lm, kv_dtype="int8")
        want = greedy_tokens(ref, prompt, 12)
        a = make_engine(lm, kv_dtype="int8")
        b = make_engine(lm, kv_dtype="int8")
        req, rec = warm_export(a, prompt)
        pre = list(req.tokens)
        adopted = b.import_request(decode_record(encode_record(rec)))
        while b.scheduler.has_work:
            b.step()
        toks = list(adopted.tokens)  # carries the pre-migration prefix
        assert toks[: len(pre)] == pre
        assert toks == want
        assert a.stats()["kv_quant_export_bytes"] > 0
        for eng in (ref, a, b):
            eng.release_telemetry()

    def test_wire_bytes_shrink(self, lm):
        """The compressed-state-movement claim, counted: the same
        warm request's record is >2.5x smaller at int8 on this tiny
        stand-in (H=2 Dh=16 rows shrink 3.2x; the JSON header is a
        larger fraction here than at a served model's head geometry)."""
        fp = make_engine(lm)
        q8 = make_engine(lm, kv_dtype="int8")
        _, rec_fp = warm_export(fp)
        _, rec_q8 = warm_export(q8)
        ratio = len(encode_record(rec_fp)) / len(encode_record(rec_q8))
        assert ratio > 2.5, ratio
        fp.release_telemetry()
        q8.release_telemetry()

    def test_v1_legacy_fp_record_imports(self, lm):
        prompt = [2, 3, 4, 5, 2, 3]
        ref = make_engine(lm)
        want = greedy_tokens(ref, prompt, 12)
        a = make_engine(lm)
        b = make_engine(lm)
        req, rec = warm_export(a, prompt)
        pre = list(req.tokens)
        back = decode_record(encode_v1(rec))
        assert back["kv_dtype"] == "fp"  # defaulted, importer-checked
        assert back["version"] == 1
        adopted = b.import_request(back)
        while b.scheduler.has_work:
            b.step()
        toks = list(adopted.tokens)  # carries the pre-migration prefix
        assert toks[: len(pre)] == pre
        assert toks == want
        for eng in (ref, a, b):
            eng.release_telemetry()

    def test_refusal_matrix(self, lm):
        a = make_engine(lm, kv_dtype="int8")
        _, rec = warm_export(a)
        wire = encode_record(rec)
        # torn frames
        with pytest.raises(ValueError, match="magic"):
            decode_record(b"XMIG" + wire[4:])
        with pytest.raises(ValueError, match="truncated"):
            decode_record(wire[:20])  # header cut mid-JSON
        with pytest.raises(ValueError, match="truncated"):
            decode_record(wire[:-10])  # array section cut short
        with pytest.raises(ValueError, match="trailing"):
            decode_record(wire + b"\x00\x00")
        # version skew: patch the u16 version field to a future value
        skew = bytearray(wire)
        future = migration.VERSION + 1
        skew[4:6] = struct.pack("<H", future)
        with pytest.raises(ValueError, match=f"version {future}"):
            decode_record(bytes(skew))
        # engine-level version check (records can arrive as dicts via
        # the in-process router, not only off the wire); one reused
        # int8 target covers every import refusal — a failed
        # validation never mutates the engine
        tgt = make_engine(lm, kv_dtype="int8")
        bad_ver = dict(rec, version=7)
        with pytest.raises(ValueError, match="version"):
            tgt.import_request(bad_ver)
        # kv_dtype mismatch, both directions
        fp_eng = make_engine(lm)
        with pytest.raises(ValueError, match="kv_dtype"):
            fp_eng.import_request(decode_record(wire))
        _, rec_fp = warm_export(fp_eng)
        with pytest.raises(ValueError, match="kv_dtype"):
            tgt.import_request(rec_fp)
        # wrong per-layer arity: scales stripped from a quant record
        torn = dict(rec, rows={
            name: leaves[:2] for name, leaves in rec["rows"].items()
        })
        with pytest.raises(ValueError, match="arrays per layer"):
            tgt.import_request(torn)
        for eng in (a, tgt, fp_eng):
            eng.release_telemetry()

    def test_cold_record_crosses_dtypes(self, lm):
        """A COLD record (no K/V rows) re-prefills on the importer, so
        it is dtype-portable by construction — an fp replica's waiting
        request may land on a quantized one."""
        a = make_engine(lm)
        req = a.submit([2, 3, 4, 5], 6)  # never stepped: cold
        rec = a.export_request(req.rid)
        assert not rec.get("n_blocks")
        b = make_engine(lm, kv_dtype="int8")
        adopted = b.import_request(decode_record(encode_record(rec)))
        while b.scheduler.has_work:
            b.step()
        assert len(adopted.tokens) == 6
        a.release_telemetry()
        b.release_telemetry()


# -- cross-dtype quality on the trained stand-in ----------------------


@pytest.mark.slow  # trains the deeper d128L4 stand-in, compiles 3 engines
def test_token_agreement_vs_fp_oracle_trained():
    """The quality gate's substance: on the TRAINED d128L4 stand-in
    (periodic data → confident argmax), int8 greedy output agrees with
    the fp parity oracle >= 0.95 position-for-position: score() the
    fp oracle's own greedy completion on the quantized engine. An
    untrained model would test agreement between two argmax coin
    flips."""
    from elephas_tpu import SparkModel
    from elephas_tpu.models import transformer_lm

    maxlen, vocab = 128, 512
    model = transformer_lm(
        vocab_size=vocab, maxlen=maxlen, d_model=128, num_heads=4,
        num_layers=4, dropout=0.0, lr=1e-2, seed=0,
    )
    rng = np.random.default_rng(29)
    starts = rng.integers(2, 6, size=256)
    seq = (starts[:, None] + np.arange(maxlen + 1)) % 4 + 2
    x, y = seq[:, :-1].astype(np.int32), seq[:, 1:].astype(np.int32)
    SparkModel(model, num_workers=4).fit((x, y), epochs=4, batch_size=32)

    def engine(dt):
        from elephas_tpu.serving import InferenceEngine

        return InferenceEngine(
            model, num_slots=4, paged=True, block_size=16,
            num_blocks=64, kv_dtype=dt,
        )

    fp = engine("fp")
    prompts = [
        ((int(rng.integers(2, 6)) + np.arange(24)) % 4 + 2)
        .astype(np.int32).tolist()
        for _ in range(6)
    ]
    completions = [greedy_tokens(fp, p, 48) for p in prompts]
    agree = {}
    for dt in ("int8", "int4"):
        eng = engine(dt)
        scores = [
            eng.score(p, c)["agreement"]
            for p, c in zip(prompts, completions)
        ]
        agree[dt] = float(np.mean(scores))
        eng.release_telemetry()
    fp.release_telemetry()
    assert agree["int8"] >= 0.95, agree
    assert agree["int4"] >= 0.80, agree
