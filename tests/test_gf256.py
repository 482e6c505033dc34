"""Field-axiom and table-consistency tests for GF(2^8)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.coding import gf256

bytes_ = st.integers(min_value=0, max_value=255)


def m(a, b):
    return int(gf256._MUL_NP[a, b])


@given(bytes_, bytes_)
def test_mul_commutative(a, b):
    assert m(a, b) == m(b, a)


@given(bytes_, bytes_, bytes_)
@settings(max_examples=200)
def test_mul_associative(a, b, c):
    assert m(m(a, b), c) == m(a, m(b, c))


@given(bytes_, bytes_, bytes_)
@settings(max_examples=200)
def test_distributive(a, b, c):
    assert m(a, b ^ c) == m(a, b) ^ m(a, c)


@given(bytes_)
def test_identity_and_zero(a):
    assert m(a, 1) == a
    assert m(a, 0) == 0


@given(st.integers(min_value=1, max_value=255))
def test_inverse(a):
    assert m(a, int(gf256._INV_NP[a])) == 1


def test_mul_matches_carryless_reference():
    # bit-by-bit carryless multiply + reduction, independent implementation
    def ref_mul(a, b):
        r = 0
        for i in range(8):
            if (b >> i) & 1:
                r ^= a << i
        for bit in range(15, 7, -1):
            if (r >> bit) & 1:
                r ^= gf256._POLY << (bit - 8)
        return r

    rng = np.random.default_rng(0)
    for _ in range(500):
        a, b = int(rng.integers(256)), int(rng.integers(256))
        assert m(a, b) == ref_mul(a, b)


def test_jnp_mul_matches_table():
    rng = np.random.default_rng(1)
    a = rng.integers(0, 256, size=(64,), dtype=np.uint8)
    b = rng.integers(0, 256, size=(64,), dtype=np.uint8)
    got = np.asarray(gf256.mul(jnp.asarray(a), jnp.asarray(b)))
    want = gf256._MUL_NP[a, b]
    np.testing.assert_array_equal(got, want)


def test_matmul_matches_np():
    rng = np.random.default_rng(2)
    a = rng.integers(0, 256, size=(5, 7), dtype=np.uint8)
    b = rng.integers(0, 256, size=(7, 3), dtype=np.uint8)
    got = np.asarray(gf256.matmul(jnp.asarray(a), jnp.asarray(b)))
    want = gf256.np_matmul(a, b)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize(
    "q", [gf256.MATMUL_SLICE - 100, 2 * gf256.MATMUL_SLICE + 100]
)
def test_sliced_matmul_matches_full_product_formula(q):
    """The sliced matmul is byte-identical to the formula it replaced,
    which built the whole (..., M, K, q) log/exp product at once: on a
    batched (t, K, q) input, with q inside one slice and with q past two
    whole slices and a ragged tail."""
    rng = np.random.default_rng(q)
    a = rng.integers(0, 256, size=(4, 3), dtype=np.uint8)
    a[0] = (0, 1, 255)
    b = jnp.asarray(rng.integers(0, 256, size=(2, 3, q), dtype=np.uint8))
    full = gf256._xor_reduce(gf256.mul(jnp.asarray(a)[:, :, None], b[..., None, :, :]), axis=-2)
    got = gf256.matmul(jnp.asarray(a), b)
    assert got.shape == (2, 4, q)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(full))


@pytest.mark.parametrize(
    "q", [gf256.MATMUL_SLICE - 100, 2 * gf256.MATMUL_SLICE + 100]
)
def test_block_matmul_matches_the_stacked_matmul(q):
    """``matmul_blocks`` over K separate (q,) blocks gives, block for
    block, the rows of ``matmul`` over their (K, q) stack, inside one
    slice and past two whole slices with a ragged tail; a (K, q) array
    is read row by row."""
    rng = np.random.default_rng(q + 1)
    a = rng.integers(0, 256, size=(2, 10), dtype=np.uint8)
    a[0, :3] = (0, 1, 255)
    blocks = [rng.integers(0, 256, size=q, dtype=np.uint8) for _ in range(10)]
    want = np.asarray(gf256.matmul(jnp.asarray(a), jnp.asarray(np.stack(blocks))))
    for arg in (blocks, np.stack(blocks)):
        got = gf256.matmul_blocks(a, arg)
        assert len(got) == 2 and all(b.shape == (q,) for b in got)
        np.testing.assert_array_equal(np.stack([np.asarray(b) for b in got]), want)


def test_np_inv_matrix_roundtrip():
    rng = np.random.default_rng(3)
    for n in (1, 2, 5, 12):
        while True:
            mt = rng.integers(0, 256, size=(n, n), dtype=np.uint8)
            try:
                minv = gf256.np_inv_matrix(mt)
                break
            except np.linalg.LinAlgError:
                continue
        eye = gf256.np_matmul(mt, minv)
        np.testing.assert_array_equal(eye, np.eye(n, dtype=np.uint8))


def test_xor_reduce():
    rng = np.random.default_rng(4)
    x = rng.integers(0, 256, size=(6, 33), dtype=np.uint8)
    got = np.asarray(gf256.xor_reduce(jnp.asarray(x), axis=0))
    want = np.bitwise_xor.reduce(x, axis=0)
    np.testing.assert_array_equal(got, want)
