"""Public jit'd wrappers around the Pallas kernels.

Handles padding to tile boundaries, host-side coefficient bit-plane
expansion, the host staging of the GF and ragged kernels' uint32 word
layout (their results come back as uint8 numpy), and interpret-mode
selection (interpret=True executes the kernel body in Python on CPU; on
a real TPU backend pass ``interpret=False`` / rely on the default).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import gf256_matmul as _gfk
from repro.kernels import ragged_decode as _rdk
from repro.kernels import ragged_encode as _rek
from repro.kernels import xor_parity as _xpk
from repro.kernels.backend import resolve_interpret
from repro.obs.host import span


def _pad_to(x: jnp.ndarray, mult: int, axis: int) -> tuple[jnp.ndarray, int]:
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x, n
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad), n


def gf256_matmul(
    coef: np.ndarray,
    data,
    *,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> np.ndarray:
    """C (M, N) = coef (M, K) @ data (K, N) over GF(2^8), Pallas-backed.

    ``coef`` is a host-side numpy matrix (generator/repair coefficients);
    ``data`` is staged on the host as words (N padded to a block_n
    multiple, a unit axis per source) with the coefficient bit-planes
    splatted source-major, and the result comes back as (M, N) uint8
    numpy.
    """
    interpret = resolve_interpret(interpret)
    data = np.asarray(data, dtype=np.uint8)
    n = data.shape[-1]
    if block_n is None:
        block_n = min(_gfk.DEFAULT_BLOCK_N, _next_pow2(n))
    rem = (-n) % block_n
    if rem:
        data = np.pad(data, [(0, 0), (0, rem)])
    words = _gfk.stage_words(data)[:, None, :]  # (K, 1, N/4)
    planes = _gfk.expand_coeff_bitplanes(coef)  # (M, K, 8)
    planes = _gfk.stage_planes(np.swapaxes(planes, 0, 1))  # (K, M, 8)
    out = _gfk.gf256_matmul_planes(
        jnp.asarray(planes), jnp.asarray(words), block_n=block_n, interpret=interpret
    )
    return np.asarray(out).view(np.uint8)[:, :n]


def xor_parity(
    data: jnp.ndarray, *, block_n: int | None = None, interpret: bool | None = None
) -> jnp.ndarray:
    """data (T, N) uint8 -> (N,) XOR over rows, Pallas-backed."""
    interpret = resolve_interpret(interpret)
    n = data.shape[-1]
    if block_n is None:
        block_n = min(_xpk.DEFAULT_BLOCK_N, _next_pow2(n))
    data = data.astype(jnp.uint8)
    data_p, orig_n = _pad_to(data, block_n, axis=-1)
    out = _xpk.xor_parity(data_p, block_n=block_n, interpret=interpret)
    return out[:orig_n]


def _ragged(entry, mc, data, interpret, tile_block) -> np.ndarray:
    """Host side of one ragged launch: view the (K, C, TN) uint8 staging
    buffer as words, splat the planes (GF entries only), launch, and view
    the (C, TN/4) result words as (C, TN) bytes on the host."""
    interpret = resolve_interpret(interpret)
    _, c, tn = data.shape
    if tile_block is None:
        tile_block = _rdk.tile_block_for(c, tn, interpret)
    with span("stage.h2d", tiles=c, bytes=data.nbytes):
        planes = () if mc is None else (jnp.asarray(_gfk.stage_planes(mc)),)
        planes, words = jax.block_until_ready(
            (planes, jnp.asarray(_gfk.stage_words(data)))
        )
    with span("kernel.run", tiles=c):
        out = entry(*planes, words, tile_block=tile_block, interpret=interpret)
        out.block_until_ready()
    with span("stage.d2h", tiles=c):
        return np.asarray(out).view(np.uint8)


def gf256_ragged(
    mc: np.ndarray,
    data: np.ndarray,
    *,
    interpret: bool | None = None,
    tile_block: int | None = None,
) -> np.ndarray:
    """Ragged megakernel entry: ONE launch over C fixed-width tiles of
    MIXED GF(256) decode ops (the gateway coalescer's whole-window decode
    set — see kernels/ragged_decode.py for the descriptor layout).

    mc: (K, C, 8) per-tile coefficient bit-planes; data: (K, C, TN)
    per-tile source slabs, source-major -> (C, TN) uint8. ``tile_block``
    (tiles per grid step) defaults to the whole chunk under the
    interpreter and to ``tile_block_for``'s VMEM cap on TPU."""
    return _ragged(_rdk.ragged_gf256_tiles, mc, data, interpret, tile_block)


def xor_ragged(
    data: np.ndarray,
    *,
    interpret: bool | None = None,
    tile_block: int | None = None,
) -> np.ndarray:
    """Ragged megakernel entry for vertical XOR repairs: data (K, C, TN)
    per-tile source slabs -> (C, TN), one launch for a whole window's
    mixed tile set (zero-padded K rows / tail bytes are XOR-identity)."""
    return _ragged(_rdk.ragged_xor_tiles, None, data, interpret, tile_block)


def gf256_ragged_encode(
    mc: np.ndarray,
    data: np.ndarray,
    *,
    interpret: bool | None = None,
    tile_block: int | None = None,
) -> np.ndarray:
    """Ragged ENCODE megakernel entry: ONE launch over C fixed-width
    tiles of MIXED GF(256) parity encodes (a PUT window's RS parity-row
    generation, coefficients from coding/rs.py's ``parity_matrix`` — see
    kernels/ragged_encode.py). Same tile contract as ``gf256_ragged``
    but a separate jit signature pool, so encode K-cap growth never
    retraces the decode kernels."""
    return _ragged(_rek.ragged_gf256_encode_tiles, mc, data, interpret, tile_block)


def xor_ragged_encode(
    data: np.ndarray,
    *,
    interpret: bool | None = None,
    tile_block: int | None = None,
) -> np.ndarray:
    """Ragged ENCODE megakernel entry for XOR-delta parity folds: data
    (K, C, TN) per-tile slabs (stored parity + old/new row deltas, any
    fold depth) -> (C, TN), one launch per PUT window. Zero-padded K
    rows / tail bytes are the XOR identity."""
    return _ragged(_rek.ragged_xor_encode_tiles, None, data, interpret, tile_block)


def rs_encode(parity_matrix: np.ndarray, data: jnp.ndarray, **kw) -> jnp.ndarray:
    """RS parity blocks (m, q) from data blocks (k, q)."""
    return gf256_matmul(parity_matrix, data, **kw)


def rs_decode(inverse: np.ndarray, survivors: jnp.ndarray, **kw) -> jnp.ndarray:
    """Message blocks (k, q) = decode-inverse (k, k) @ survivors (k, q)."""
    return gf256_matmul(inverse, survivors, **kw)


def _next_pow2(n: int) -> int:
    p = 128
    while p < n:
        p *= 2
    return p
