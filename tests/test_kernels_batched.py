"""A batch of B stripes decoded through the ragged megakernel entries
(``ops.gf256_ragged`` / ``ops.xor_ragged``): every (b, m, k, n) case is
staged the way the gateway coalescer stages a window — one descriptor
row per output row, cut into fixed-width tiles with a ragged tail, the
chunk padded with null tiles — and each op must match the single-stripe
``gf256_matmul`` and the jnp oracle (kernels/ref.py). Covers source
counts up to K = 16, wider than any chip cell runs. No hypothesis
dependency — this file must run everywhere (it guards the coalescer's
kernels)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.coding import gf256
from repro.kernels import ops, ref
from repro.kernels.gf256_matmul import expand_coeff_bitplanes
from repro.kernels.ragged_decode import CHUNK_SMALL

TN = 512  # tile width: n = 128 / 1000 / 4096 gives 1, 2 and 8 tiles per row


def _tiles(b: int, m: int, n: int) -> list[tuple[int, int, int, int]]:
    """(stripe, output row, byte offset, valid bytes) per tile, padded
    with null tiles (stripe -1) up to a CHUNK_SMALL multiple."""
    tiles = [
        (i, r, off, min(TN, n - off))
        for i in range(b)
        for r in range(m)
        for off in range(0, n, TN)
    ]
    return tiles + [(-1, 0, 0, 0)] * (-len(tiles) % CHUNK_SMALL)


def _scatter(out: np.ndarray, tiles, b: int, m: int, n: int) -> np.ndarray:
    got = np.zeros((b, m, n), dtype=np.uint8)
    for slot, (i, r, off, valid) in enumerate(tiles):
        if i >= 0:
            got[i, r, off : off + valid] = out[slot, :valid]
    return got


def ragged_gf256_batch(coefs: np.ndarray, data: np.ndarray) -> np.ndarray:
    """out (B, M, N) = coefs (B, M, K) @ data (B, K, N) over GF(2^8), in
    ONE ragged launch: tile t of stripe i's row r carries row r's
    coefficient bit-planes."""
    b, m, k = coefs.shape
    n = data.shape[-1]
    tiles = _tiles(b, m, n)
    mc = np.zeros((k, len(tiles), 8), dtype=np.uint8)
    buf = np.zeros((k, len(tiles), TN), dtype=np.uint8)
    for slot, (i, r, off, valid) in enumerate(tiles):
        if i >= 0:
            mc[:, slot] = expand_coeff_bitplanes(coefs[i, r][None, :])[0]
            buf[:, slot, :valid] = data[i, :, off : off + valid]
    out = ops.gf256_ragged(mc, buf, interpret=True)
    return _scatter(out, tiles, b, m, n)


def ragged_xor_batch(data: np.ndarray) -> np.ndarray:
    """out (B, N) = XOR over the T rows of each data (B, T, N) stripe,
    in ONE ragged launch."""
    b, t, n = data.shape
    tiles = _tiles(b, 1, n)
    buf = np.zeros((t, len(tiles), TN), dtype=np.uint8)
    for slot, (i, _r, off, valid) in enumerate(tiles):
        if i >= 0:
            buf[:, slot, :valid] = data[i, :, off : off + valid]
    out = ops.xor_ragged(buf, interpret=True)
    return _scatter(out, tiles, b, 1, n)[:, 0]


@pytest.mark.parametrize("b,m,k", [(1, 1, 3), (2, 2, 6), (3, 1, 12), (5, 3, 6), (8, 2, 4)])
@pytest.mark.parametrize("n", [128, 512, 1000, 4096])
def test_batched_matches_single_stripe_loop(b, m, k, n):
    rng = np.random.default_rng(b * 10000 + m * 1000 + k * 10 + n)
    coefs = rng.integers(0, 256, size=(b, m, k), dtype=np.uint8)
    data = rng.integers(0, 256, size=(b, k, n), dtype=np.uint8)
    got = ragged_gf256_batch(coefs, data)
    want = np.stack(
        [
            np.asarray(ops.gf256_matmul(coefs[i], jnp.asarray(data[i]), interpret=True))
            for i in range(b)
        ]
    )
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("b,m,k,n", [(2, 2, 6, 777), (4, 1, 3, 2048), (3, 4, 16, 512)])
def test_batched_matches_numpy_reference(b, m, k, n):
    rng = np.random.default_rng(b + m + k + n)
    coefs = rng.integers(0, 256, size=(b, m, k), dtype=np.uint8)
    data = rng.integers(0, 256, size=(b, k, n), dtype=np.uint8)
    got = ragged_gf256_batch(coefs, data)
    for i in range(b):
        want = np.asarray(ref.gf256_matmul(jnp.asarray(coefs[i]), jnp.asarray(data[i])))
        np.testing.assert_array_equal(got[i], want)


@pytest.mark.parametrize("b,t,n", [(1, 2, 128), (3, 3, 512), (4, 5, 1000), (2, 13, 4096)])
def test_batched_xor_parity_matches_loop_and_reference(b, t, n):
    rng = np.random.default_rng(b * 100 + t * 10 + n)
    data = rng.integers(0, 256, size=(b, t, n), dtype=np.uint8)
    got = ragged_xor_batch(data)
    assert got.shape == (b, n)
    for i in range(b):
        single = np.asarray(ops.xor_parity(jnp.asarray(data[i]), interpret=True))
        want = np.asarray(ref.xor_parity(jnp.asarray(data[i])))
        np.testing.assert_array_equal(got[i], single)
        np.testing.assert_array_equal(got[i], want)


def test_batched_decode_recovers_rs_stripes():
    """End-to-end: B stripes with different erasure patterns decode in one
    ragged launch via per-stripe repair matrices."""
    from repro.coding import rs

    n_code, k = 9, 6
    q = 1024
    code = rs.make_rs(n_code, k)
    rng = np.random.default_rng(42)
    patterns = [(0,), (3,), (5,)]  # a different lost block per stripe
    coefs, survivors, want = [], [], []
    for missing in patterns:
        data = rng.integers(0, 256, size=(k, q), dtype=np.uint8)
        cw = np.asarray(code.encode(jnp.asarray(data)))
        avail = np.asarray([c for c in range(n_code) if c not in missing])
        row_ids, cf = code.repair_matrix(avail, np.asarray(missing))
        coefs.append(cf)
        survivors.append(cw[row_ids])
        want.append(cw[list(missing)])
    got = ragged_gf256_batch(np.stack(coefs), np.stack(survivors))
    np.testing.assert_array_equal(got, np.stack(want))


def test_batched_rejects_mismatched_shapes():
    """The ragged GF entry asserts that every tile has coefficient
    planes for each of its K source slabs."""
    mc = np.zeros((2, CHUNK_SMALL, 8), dtype=np.uint8)  # K = 2 planes
    data = np.zeros((3, CHUNK_SMALL, 128), dtype=np.uint8)  # K = 3 sources
    with pytest.raises(AssertionError):
        ops.gf256_ragged(mc, data, interpret=True)


def test_batched_gf256_used_by_vertical_equivalence():
    """XOR == GF(256) matmul with all-ones coefficients: the ragged GF
    entry with all-ones rows equals the ragged XOR entry, the identity
    the coalescer's V fast path relies on."""
    rng = np.random.default_rng(0)
    b, t, n = 3, 4, 512
    data = rng.integers(0, 256, size=(b, t, n), dtype=np.uint8)
    ones = np.ones((b, 1, t), dtype=np.uint8)
    via_gf = ragged_gf256_batch(ones, data)
    via_xor = ragged_xor_batch(data)
    np.testing.assert_array_equal(via_gf[:, 0], via_xor)
    np.testing.assert_array_equal(via_xor, np.bitwise_xor.reduce(data, axis=1))
    # sanity vs the scalar gf256 helper
    assert gf256.mul_scalar_np(1, 7) == 7
