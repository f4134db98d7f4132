"""Wire codec (ISSUE 2): dtype-preserving round trips, int8 error
feedback, top-k sparsification, chunking, and the no-pickle hot-path
lint."""

import os
import re

import numpy as np
import pytest

from elephas_tpu.parameter import codec as wire


def _mixed_weights():
    import ml_dtypes

    rng = np.random.default_rng(0)
    return [
        rng.normal(size=(17, 9)).astype(np.float32),
        rng.normal(size=(33,)).astype(np.float16),
        rng.normal(size=(8, 3)).astype(ml_dtypes.bfloat16),
        np.arange(10, dtype=np.int64),
        np.arange(6, dtype=np.int32).reshape(2, 3),
        rng.normal(size=(5,)).astype(np.float64),
        np.array(3.5, dtype=np.float64),  # 0-d
        np.zeros((0, 4), np.float32),  # empty
    ]


@pytest.mark.parametrize("chunk_bytes", [4096, 1 << 20])
def test_dense_roundtrip_preserves_dtypes(chunk_bytes):
    ws = _mixed_weights()
    dec = wire.decode(wire.WireCodec(chunk_bytes=chunk_bytes).encode(ws))
    assert len(dec) == len(ws)
    for a, b in zip(ws, dec):
        assert a.dtype == b.dtype
        assert a.shape == b.shape
        np.testing.assert_array_equal(
            np.asarray(a, np.float64), np.asarray(b, np.float64)
        )


def test_int8_quantization_bounds_error():
    rng = np.random.default_rng(1)
    ws = [rng.normal(size=(100, 50)).astype(np.float32)]
    dec = wire.decode(wire.WireCodec(compression="int8").encode(ws))
    # symmetric per-chunk int8: error <= scale/2 = max|x|/254
    atol = np.abs(ws[0]).max() / 254 + 1e-7
    np.testing.assert_allclose(dec[0], ws[0], atol=atol)


def test_int8_preserves_integer_tensors_exactly():
    ws = [np.arange(7, dtype=np.int64), np.ones((4, 4), np.float32)]
    dec = wire.decode(wire.WireCodec(compression="int8").encode(ws))
    np.testing.assert_array_equal(dec[0], ws[0])
    assert dec[0].dtype == np.int64


def test_topk_keeps_largest_magnitudes():
    flat = np.zeros(100, np.float32)
    flat[[3, 50, 97]] = [10.0, -20.0, 5.0]
    flat[10] = 0.01  # below the cut
    dec = wire.decode(wire.WireCodec(topk=0.03).encode([flat]))
    np.testing.assert_allclose(dec[0][[3, 50, 97]], [10.0, -20.0, 5.0])
    assert dec[0][10] == 0.0


def test_error_feedback_carries_residual_forward():
    """The quantization error of round N must re-enter round N+1's
    push: summing decoded pushes converges to the summed true deltas
    (DGC's guarantee), which plain lossy pushes do not achieve."""
    rng = np.random.default_rng(2)
    codec = wire.WireCodec(compression="int8", topk=0.1)
    ef = wire.ErrorFeedback()
    true_sum = np.zeros((40, 30), np.float32)
    decoded_sum = np.zeros_like(true_sum)
    for _ in range(30):
        delta = rng.normal(size=(40, 30)).astype(np.float32) * 1e-2
        true_sum += delta
        decoded_sum += wire.decode(codec.encode([delta], ef))[0]
    # residual bounds the gap: decoded_sum + residual == true_sum
    np.testing.assert_allclose(
        decoded_sum + ef._residuals[0], true_sum, atol=1e-4
    )
    # and the running error stays bounded (one round's worth), far
    # smaller than the accumulated mass a feedback-free encoder drops
    gap = np.abs(decoded_sum - true_sum).max()
    assert gap < 0.05, gap


def test_error_feedback_shape_mismatch_raises():
    ef = wire.ErrorFeedback()
    ef.compensate([np.zeros(3, np.float32)])
    with pytest.raises(ValueError, match="error-feedback"):
        ef.compensate([np.zeros(3, np.float32), np.zeros(2, np.float32)])


def test_bad_magic_and_version_rejected():
    payload = bytearray(wire.WireCodec().encode([np.zeros(3, np.float32)]))
    bad_magic = bytearray(payload)
    bad_magic[4:8] = b"XXXX"
    with pytest.raises(ValueError, match="magic"):
        wire.decode(bytes(bad_magic))
    bad_version = bytearray(payload)
    bad_version[8] = 99  # version byte follows the 4-byte frame length
    with pytest.raises(ValueError, match="version"):
        wire.decode(bytes(bad_version))


def test_truncated_stream_raises():
    payload = wire.WireCodec().encode([np.ones((32, 32), np.float32)])
    with pytest.raises((ConnectionError, Exception)):
        wire.decode(payload[: len(payload) // 2])


def test_invalid_config_rejected():
    with pytest.raises(ValueError, match="compression"):
        wire.WireCodec(compression="zstd")
    with pytest.raises(ValueError, match="topk"):
        wire.WireCodec(topk=0.0)
    with pytest.raises(ValueError, match="topk"):
        wire.WireCodec(topk=1.5)


def test_all_zero_chunk_quantizes_exactly():
    ws = [np.zeros((64,), np.float32)]
    dec = wire.decode(wire.WireCodec(compression="int8").encode(ws))
    np.testing.assert_array_equal(dec[0], ws[0])


@pytest.mark.parametrize(
    "push_topk, floor", [(None, 3.5), (0.01, 4.0)],
    ids=["int8", "int8_topk"],
)
def test_compressed_sync_shrinks_wire_bytes(push_topk, floor):
    """The bytes claim of the compressed wire, counted on encoded
    frames: one sync is a pushed delta list plus a pulled weight list
    (the pull is never sparsified: it carries whole weights), and on a
    float32 MLP-shaped list int8 cuts its bytes at least 3.5 times,
    int8 with a top-1% push at least 4 times. A count of bytes, not a
    time: headers and per-chunk scales are in it."""
    rng = np.random.default_rng(0)
    shapes = [(256, 512), (512,), (512, 128), (128,)]
    weights = [rng.normal(size=s).astype(np.float32) for s in shapes]
    deltas = [(rng.normal(size=s) * 1e-3).astype(np.float32) for s in shapes]

    def sync_bytes(push, pull):
        return len(push.encode(deltas)) + len(pull.encode(weights))

    dense = sync_bytes(wire.WireCodec(), wire.WireCodec())
    small = sync_bytes(
        wire.WireCodec(compression="int8", topk=push_topk),
        wire.WireCodec(compression="int8"),
    )
    assert dense >= 2 * sum(w.nbytes for w in weights)  # nothing hidden
    assert dense / small >= floor, (dense, small)


# -- tooling satellite: the hot path must never re-grow pickle ----------

_HOT_PATH_FILES = [
    "elephas_tpu/parameter/codec.py",
    "elephas_tpu/parameter/client.py",
    "elephas_tpu/parameter/server.py",
    "elephas_tpu/parameter/native.py",
    "elephas_tpu/utils/sockets.py",
]
_PICKLE_USE = re.compile(r"pickle\.(loads|load)\s*\(")


def test_no_untagged_pickle_on_the_network_hot_path():
    """Grep-based lint (ISSUE 2 satellite): ``pickle.loads`` may appear
    in the PS wire modules ONLY on lines tagged (within two lines) as
    the negotiated legacy fallback — a new use on the hot path fails
    loudly here."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    offences = []
    for rel in _HOT_PATH_FILES:
        path = os.path.join(root, rel)
        with open(path) as f:
            lines = f.read().splitlines()
        for i, line in enumerate(lines):
            if not _PICKLE_USE.search(line):
                continue
            window = lines[max(0, i - 2) : i + 1]
            if not any("legacy-pickle" in w for w in window):
                offences.append(f"{rel}:{i + 1}: {line.strip()}")
    assert not offences, (
        "pickle.loads on the PS network hot path without a "
        "'legacy-pickle' fallback tag:\n" + "\n".join(offences)
    )
