"""The uint32-word GF(2^8) kernel (4 bytes per lane) vs the jnp oracle —
shape sweep incl. sizes that are not a multiple of the tile (ops.py
pads to the tile)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.coding import rs
from repro.kernels import ops, ref


@settings(max_examples=12, deadline=None)
@given(
    n=st.sampled_from([9, 14, 6]),
    k=st.sampled_from([6, 12, 4]),
    q=st.sampled_from([128, 1000, 4096, 70000]),
    seed=st.integers(0, 3),
)
def test_packed_kernel_matches_oracle(n, k, q, seed):
    if k >= n:
        return
    parity = rs.parity_matrix(n, k)
    rng = np.random.default_rng(seed)
    data = jnp.asarray(rng.integers(0, 256, (k, q), dtype=np.uint8))
    got = np.asarray(ops.gf256_matmul(parity, data))
    want = np.asarray(ref.gf256_matmul(jnp.asarray(parity), data))
    assert np.array_equal(got, want)
