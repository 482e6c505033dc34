"""Pallas TPU kernel: vertical XOR parity / repair (the CORE fast path).

out (N,) = XOR over the T rows of data (T, N). Pure byte-XOR: this is
the paper's cheap vertical operation — bandwidth-bound, VPU-trivial. The
kernel exists so the repair fast path never leaves VMEM-tiled streaming
form on TPU (HBM -> VMEM tiles -> XOR tree -> out), and to make the
compute-cost asymmetry vs RS decode (gf256_matmul) explicit in profiles.

Grid: 1-D over N. The full T x BN tile sits in VMEM (T <= ~16 rows of a
CORE group, BN = 2048 -> 32 KiB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

DEFAULT_BLOCK_N = 65536


def _xor_kernel(data_ref, out_ref, *, t: int):
    data = data_ref[...]  # (T, BN)
    acc = data[0]
    for r in range(1, t):
        acc = jnp.bitwise_xor(acc, data[r])
    out_ref[...] = acc[None, :]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def xor_parity(
    data: jnp.ndarray, *, block_n: int = DEFAULT_BLOCK_N, interpret: bool | None = None
) -> jnp.ndarray:
    """data: (T, N) uint8 -> (N,) XOR of rows. N % block_n == 0.

    interpret=None auto-detects the backend (kernels/backend.py)."""
    interpret = resolve_interpret(interpret)
    t, n = data.shape
    assert n % block_n == 0, (n, block_n)
    out = pl.pallas_call(
        functools.partial(_xor_kernel, t=t),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.uint8),
        grid=(n // block_n,),
        in_specs=[pl.BlockSpec((t, block_n), lambda j: (0, j))],
        out_specs=pl.BlockSpec((1, block_n), lambda j: (0, j)),
        interpret=interpret,
    )(data)
    return out[0]
