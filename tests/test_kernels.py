"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracle
(interpret=True executes the Pallas kernel body on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.coding import gf256, rs
from repro.kernels import ops, ref


@pytest.mark.parametrize("m,k", [(1, 2), (2, 12), (3, 6), (4, 16), (6, 6)])
@pytest.mark.parametrize("n", [128, 512, 1000, 2048, 4096, 5000])
def test_gf256_matmul_matches_ref(m, k, n):
    rng = np.random.default_rng(m * 1000 + k * 10 + n)
    coef = rng.integers(0, 256, size=(m, k), dtype=np.uint8)
    data = rng.integers(0, 256, size=(k, n), dtype=np.uint8)
    got = np.asarray(ops.gf256_matmul(coef, jnp.asarray(data), interpret=True))
    want = np.asarray(ref.gf256_matmul(jnp.asarray(coef), jnp.asarray(data)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("t", [2, 3, 5, 6, 13])
@pytest.mark.parametrize("n", [128, 777, 2048, 4096])
def test_xor_parity_matches_ref(t, n):
    rng = np.random.default_rng(t * 97 + n)
    data = rng.integers(0, 256, size=(t, n), dtype=np.uint8)
    got = np.asarray(ops.xor_parity(jnp.asarray(data), interpret=True))
    want = np.asarray(ref.xor_parity(jnp.asarray(data)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,k", [(9, 6), (14, 12)])
def test_rs_encode_kernel_end_to_end(n, k):
    """Kernel-encoded parities must agree with the LinearCode path."""
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, size=(k, 4096), dtype=np.uint8)
    pm = rs.parity_matrix(n, k)
    got = np.asarray(ops.rs_encode(pm, jnp.asarray(data), interpret=True))
    code = rs.make_rs(n, k)
    cw = np.asarray(code.encode(jnp.asarray(data)))
    np.testing.assert_array_equal(got, cw[k:])


def test_rs_decode_kernel_end_to_end():
    n, k = 9, 6
    rng = np.random.default_rng(6)
    data = rng.integers(0, 256, size=(k, 2048), dtype=np.uint8)
    code = rs.make_rs(n, k)
    cw = np.asarray(code.encode(jnp.asarray(data)))
    avail = np.asarray([0, 2, 4, 6, 7, 8])
    row_ids, inverse = code.decode_matrix(avail)
    survivors = cw[row_ids]
    got = np.asarray(ops.rs_decode(inverse, jnp.asarray(survivors), interpret=True))
    np.testing.assert_array_equal(got, data)


@settings(max_examples=20, deadline=None)
@given(
    st.integers(min_value=1, max_value=5),
    st.integers(min_value=1, max_value=12),
    st.integers(min_value=1, max_value=3000),
    st.integers(min_value=0, max_value=2**31 - 1),
)
def test_gf256_matmul_property(m, k, n, seed):
    rng = np.random.default_rng(seed)
    coef = rng.integers(0, 256, size=(m, k), dtype=np.uint8)
    data = rng.integers(0, 256, size=(k, n), dtype=np.uint8)
    got = np.asarray(ops.gf256_matmul(coef, jnp.asarray(data), interpret=True))
    want = gf256.np_matmul(coef, data)
    np.testing.assert_array_equal(got, want)


def test_block_n_variants():
    rng = np.random.default_rng(7)
    coef = rng.integers(0, 256, size=(2, 6), dtype=np.uint8)
    data = rng.integers(0, 256, size=(6, 4096), dtype=np.uint8)
    want = gf256.np_matmul(coef, data)
    for bn in (128, 256, 1024, 4096):
        got = np.asarray(
            ops.gf256_matmul(coef, jnp.asarray(data), block_n=bn, interpret=True)
        )
        np.testing.assert_array_equal(got, want, err_msg=f"block_n={bn}")


@pytest.mark.parametrize("backend, interpret", [("tpu", False), ("cpu", True), ("gpu", None)])
def test_auto_interpret_only_on_cpu(monkeypatch, backend, interpret):
    """interpret=None compiles on a TPU, interprets on the CPU, and
    refuses any other backend instead of hiding it behind the
    interpreter."""
    from repro.kernels import backend as be

    monkeypatch.setattr(be.jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            be.resolve_interpret(None)
    else:
        assert be.resolve_interpret(None) is interpret
    assert be.resolve_interpret(True) is True  # an explicit choice stands
