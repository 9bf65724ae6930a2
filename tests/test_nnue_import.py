"""Round-trip and parity tests for the Stockfish .nnue importer.

No real .nnue files exist in this environment (the reference's engine
submodules are empty mount points), so the parser is validated against
its own writer: quantized arrays → file bytes → parsed net, raw and
LEB128-compressed, plus jax-vs-numpy forward parity.
"""
import numpy as np
import pytest

from fishnet_tpu.chess import Position
from fishnet_tpu.models import nnue_import as ni
from fishnet_tpu.ops.board import from_position

L1 = 64  # small for test speed; real nets use 1024-3072
RNG = np.random.default_rng(7)


def synthetic_quantized():
    nf = ni.NUM_FEATURES
    return {
        "ft_b": RNG.integers(-500, 500, L1).astype(np.int16),
        "ft_w": RNG.integers(-127, 128, (nf, L1)).astype(np.int16),
        "psqt": RNG.integers(-2000, 2000, (nf, 8)).astype(np.int32),
        "fc0_b": RNG.integers(-8000, 8000, (8, ni.FC0_OUT)).astype(np.int32),
        "fc0_w": RNG.integers(-127, 128, (8, ni.FC0_OUT, L1)).astype(np.int8),
        "fc1_b": RNG.integers(-8000, 8000, (8, ni.FC1_OUT)).astype(np.int32),
        "fc1_w": RNG.integers(-127, 128, (8, ni.FC1_OUT, ni.FC1_IN)).astype(np.int8),
        "fc2_b": RNG.integers(-8000, 8000, (8, 1)).astype(np.int32),
        "fc2_w": RNG.integers(-127, 128, (8, 1, ni.FC1_OUT)).astype(np.int8),
        "description": b"test net",
    }


@pytest.fixture(scope="module")
def quantized():
    return synthetic_quantized()


def test_roundtrip_raw(tmp_path, quantized):
    path = tmp_path / "test.nnue"
    ni.write_nnue(path, quantized)
    net = ni.dequantize(ni.load_nnue(path))  # L1 inferred from file size
    assert net.l1 == L1
    assert net.description == b"test net"
    np.testing.assert_allclose(net.ft_w, quantized["ft_w"] / ni.QA, atol=1e-6)
    np.testing.assert_allclose(net.ft_b, quantized["ft_b"] / ni.QA, atol=1e-6)
    np.testing.assert_allclose(
        net.fc0_w[3], quantized["fc0_w"][3] / ni.QB, atol=1e-6
    )
    np.testing.assert_allclose(
        net.fc2_b[0],
        quantized["fc2_b"][0] / (ni.NNUE2SCORE * ni.OUTPUT_SCALE),
        atol=1e-9,
    )
    np.testing.assert_allclose(
        net.fc2_w[0],
        quantized["fc2_w"][0] / (ni.NNUE2SCORE * ni.OUTPUT_SCALE / ni.QA),
        atol=1e-9,
    )


def test_roundtrip_leb128(tmp_path, quantized):
    raw = tmp_path / "raw.nnue"
    comp = tmp_path / "comp.nnue"
    ni.write_nnue(raw, quantized)
    ni.write_nnue(comp, quantized, compress_ft=True)
    assert comp.stat().st_size != raw.stat().st_size
    a = ni.load_nnue(raw)
    b = ni.load_nnue(comp, l1=L1)  # compressed: size inference unavailable
    np.testing.assert_array_equal(a.ft_w, b.ft_w)
    np.testing.assert_array_equal(a.fc1_w, b.fc1_w)


def test_leb128_codec_edges():
    vals = np.array([0, 1, -1, 63, 64, -64, -65, 127, -128, 32767, -32768])
    enc = ni._leb128_encode(vals)
    dec, used = ni._leb128_decode(memoryview(enc), len(vals))
    assert used == len(enc)
    np.testing.assert_array_equal(dec, vals)


def test_truncated_and_trailing_rejected(tmp_path, quantized):
    path = tmp_path / "test.nnue"
    ni.write_nnue(path, quantized)
    data = path.read_bytes()
    bad = tmp_path / "bad.nnue"
    bad.write_bytes(data[:-100])
    with pytest.raises(ni.UnsupportedNnueFormat):
        ni.load_nnue(bad)
    bad.write_bytes(data + b"\x00" * 8)
    with pytest.raises(ni.UnsupportedNnueFormat):
        ni.load_nnue(bad)


def test_forward_parity_jax_numpy(tmp_path, quantized):
    import jax

    path = tmp_path / "test.nnue"
    ni.write_nnue(path, quantized)
    net = ni.dequantize(ni.load_nnue(path))
    fens = [
        "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1",
        "r3k2r/p1ppqpb1/bn2pnp1/3PN3/1p2P3/2N2Q1p/PPPBBPPP/R3K2R w KQkq - 0 1",
        "8/2p5/3p4/KP5r/1R3p1k/8/4P1P1/8 b - - 0 1",
    ]
    for fen in fens:
        pos = Position.from_fen(fen)
        b = from_position(pos)
        got = float(jax.jit(ni.evaluate_sf)(net, b.board, b.stm))
        want = ni.evaluate_sf_reference(net, np.asarray(b.board), int(b.stm))
        assert got == pytest.approx(want, rel=1e-4, abs=0.5), fen


def test_search_with_sf_net(tmp_path, quantized):
    """A parsed Stockfish net drives the batched search's compat path."""
    import jax.numpy as jnp

    from fishnet_tpu.ops.board import stack_boards
    from fishnet_tpu.ops.search import MATE, search_batch_jit

    path = tmp_path / "test.nnue"
    ni.write_nnue(path, quantized)
    net = ni.load_nnue(path).as_device()
    roots = stack_boards(
        [from_position(Position.from_fen("6k1/5ppp/8/8/8/8/8/4R2K w - - 0 1"))]
    )
    out = search_batch_jit(net, roots, 2, 10_000, max_ply=3)
    assert int(out["score"][0]) == MATE - 1  # finds mate with any eval


def test_truncated_leb128_stream_rejected(tmp_path, quantized):
    comp = tmp_path / "comp.nnue"
    ni.write_nnue(comp, quantized, compress_ft=True)
    data = comp.read_bytes()
    bad = tmp_path / "bad.nnue"
    bad.write_bytes(data[: len(data) // 2])
    with pytest.raises(ni.UnsupportedNnueFormat):
        ni.load_nnue(bad, l1=L1)


def test_compressed_without_l1_gets_guidance(tmp_path, quantized):
    comp = tmp_path / "comp.nnue"
    ni.write_nnue(comp, quantized, compress_ft=True)
    with pytest.raises(ni.UnsupportedNnueFormat, match="pass l1="):
        ni.load_nnue(comp)
