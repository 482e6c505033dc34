"""Public jit'd wrappers around the Pallas kernels.

Handles padding to tile boundaries, host-side coefficient bit-plane
expansion, the host staging of the GF and ragged kernels' uint32 word
layout (their results come back as uint8 numpy), and interpret-mode
selection (interpret=True executes the kernel body in Python on CPU; on
a real TPU backend pass ``interpret=False`` / rely on the default).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels import gf256_matmul as _gfk
from repro.kernels import ragged_decode as _rdk
from repro.kernels import ragged_encode as _rek
from repro.kernels import xor_parity as _xpk
from repro.kernels.backend import resolve_interpret


def _pad_to(x: jnp.ndarray, mult: int, axis: int) -> tuple[jnp.ndarray, int]:
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x, n
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad), n


def _stage_gf(coefs: np.ndarray, data, block_n: int | None):
    """Host staging for the word GF kernels: pad N to a block_n multiple,
    view the bytes as words with a unit axis per source, and splat the
    coefficient bit-planes source-major. Returns (planes, words, n,
    block_n)."""
    data = np.asarray(data, dtype=np.uint8)
    n = data.shape[-1]
    if block_n is None:
        block_n = min(_gfk.DEFAULT_BLOCK_N, _next_pow2(n))
    rem = (-n) % block_n
    if rem:
        data = np.pad(data, [(0, 0)] * (data.ndim - 1) + [(0, rem)])
    words = _gfk.stage_words(data)[..., None, :]  # (..., K, 1, N/4)
    planes = np.stack(
        [_gfk.expand_coeff_bitplanes(c) for c in coefs.reshape(-1, *coefs.shape[-2:])]
    ).reshape(*coefs.shape, 8)
    planes = _gfk.stage_planes(np.swapaxes(planes, -3, -2))  # (..., K, M, 8)
    return jnp.asarray(planes), jnp.asarray(words), n, block_n


def gf256_matmul(
    coef: np.ndarray,
    data,
    *,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> np.ndarray:
    """C (M, N) = coef (M, K) @ data (K, N) over GF(2^8), Pallas-backed.

    ``coef`` is a host-side numpy matrix (generator/repair coefficients);
    ``data`` is staged on the host as words, and the result comes back
    as (M, N) uint8 numpy.
    """
    interpret = resolve_interpret(interpret)
    planes, words, n, block_n = _stage_gf(np.asarray(coef, np.uint8), data, block_n)
    out = _gfk.gf256_matmul_planes(planes, words, block_n=block_n, interpret=interpret)
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


def gf256_matmul_batched(
    coefs: np.ndarray,
    data,
    *,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> np.ndarray:
    """Stacked decode: out (B, M, N) = coefs (B, M, K) @ data (B, K, N),
    each batch element an independent GF(2^8) product, in ONE kernel
    launch (the gateway coalescer's batched degraded-read decode).

    ``coefs`` is host-side numpy (per-stripe repair/decode matrices);
    ``data`` is staged on the host as words; the result is uint8 numpy.
    """
    interpret = resolve_interpret(interpret)
    coefs = np.asarray(coefs, dtype=np.uint8)
    assert coefs.shape[0] == np.shape(data)[0], (coefs.shape, np.shape(data))
    planes, words, n, block_n = _stage_gf(coefs, data, block_n)
    out = _gfk.gf256_matmul_planes_batched(
        planes, words, block_n=block_n, interpret=interpret
    )
    return np.asarray(out).view(np.uint8)[..., :n]


def xor_parity_batched(
    data: jnp.ndarray, *, block_n: int | None = None, interpret: bool | None = None
) -> jnp.ndarray:
    """data (B, T, N) uint8 -> (B, N): batched XOR over rows, one launch."""
    interpret = resolve_interpret(interpret)
    n = data.shape[-1]
    if block_n is None:
        block_n = min(_xpk.DEFAULT_BLOCK_N, _next_pow2(n))
    data = data.astype(jnp.uint8)
    data_p, orig_n = _pad_to(data, block_n, axis=-1)
    out = _xpk.xor_parity_batched(data_p, block_n=block_n, interpret=interpret)
    return out[..., :orig_n]


def _ragged(entry, mc, data, interpret, tile_block) -> np.ndarray:
    """Host side of one ragged launch: view the (K, C, TN) uint8 staging
    buffer as words, splat the planes (GF entries only), launch, and view
    the (C, TN/4) result words as (C, TN) bytes on the host."""
    interpret = resolve_interpret(interpret)
    _, c, tn = data.shape
    if tile_block is None:
        tile_block = _rdk.tile_block_for(c, tn, interpret)
    planes = () if mc is None else (jnp.asarray(_gfk.stage_planes(mc)),)
    out = entry(
        *planes,
        jnp.asarray(_gfk.stage_words(data)),
        tile_block=tile_block,
        interpret=interpret,
    )
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
