"""GF(2^8) arithmetic, vectorized for JAX.

The field is F_{2^8} with the standard AES/Rijndael reduction polynomial
x^8 + x^4 + x^3 + x + 1 (0x11B). Elements are uint8. Addition is XOR.
Multiplication uses log/exp tables generated once at import time with
numpy (host-side), then captured as jnp constants inside jitted code.

Conventions used throughout the codebase:
  * ``LOG[0]`` is never read on the fast path — multiplication masks zero
    operands explicitly.
  * ``EXP`` is doubled (length 510) so ``EXP[LOG[a] + LOG[b]]`` needs no
    modular reduction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Table generation (host-side, numpy)
# ---------------------------------------------------------------------------

_POLY = 0x11B  # x^8 + x^4 + x^3 + x + 1
_GENERATOR = 0x03  # 3 is a primitive element for 0x11B


def _build_tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(510, dtype=np.uint8)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        # multiply x by the generator (0x03 = x + 1): x*3 = (x<<1) ^ x
        x = (x << 1) ^ x
        if x & 0x100:
            x ^= _POLY
    exp[255:510] = exp[0:255]
    return exp, log


_EXP_NP, _LOG_NP = _build_tables()

# Full 256x256 multiplication table (64 KiB) — used by the reference paths
# and for building per-matrix lookup tables. Host-side only.
_MUL_NP = np.zeros((256, 256), dtype=np.uint8)
_nz = np.arange(1, 256)
_MUL_NP[1:, 1:] = _EXP_NP[(_LOG_NP[_nz][:, None] + _LOG_NP[_nz][None, :])]

_INV_NP = np.zeros(256, dtype=np.uint8)
_INV_NP[1:] = _EXP_NP[255 - _LOG_NP[_nz]]


# ---------------------------------------------------------------------------
# JAX-facing API
# ---------------------------------------------------------------------------

def exp_table() -> jnp.ndarray:
    return jnp.asarray(_EXP_NP)


def log_table() -> jnp.ndarray:
    return jnp.asarray(_LOG_NP)


def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Field addition == XOR (also subtraction)."""
    return jnp.bitwise_xor(a, b)


def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Elementwise field multiplication via log/exp tables."""
    a = a.astype(jnp.uint8)
    b = b.astype(jnp.uint8)
    la = jnp.asarray(_LOG_NP)[a.astype(jnp.int32)]
    lb = jnp.asarray(_LOG_NP)[b.astype(jnp.int32)]
    prod = jnp.asarray(_EXP_NP)[la + lb]
    zero = (a == 0) | (b == 0)
    return jnp.where(zero, jnp.uint8(0), prod)


def inv(a: jnp.ndarray) -> jnp.ndarray:
    """Elementwise multiplicative inverse. inv(0) := 0 (never used)."""
    return jnp.asarray(_INV_NP)[a.astype(jnp.int32)]


def pow_(a: int, e: int) -> int:
    """Host-side scalar power (for generator-matrix construction)."""
    if e == 0:
        return 1
    if a == 0:
        return 0
    return int(_EXP_NP[(int(_LOG_NP[a]) * e) % 255])


def mul_scalar_np(a: int, b: int) -> int:
    return int(_MUL_NP[a, b])


# Byte width of one slice of the last axis in ``matmul``: it works slice
# by slice, so its temporaries stay bounded whatever the block size.
MATMUL_SLICE = 1 << 16


def _xtime(x: jnp.ndarray) -> jnp.ndarray:
    """x * 2 in GF(2^8): shift left (uint8 drops the carry), then reduce
    by the polynomial where the carry was set."""
    shifted = jnp.left_shift(x, jnp.uint8(1))
    return jnp.where(x >= 0x80, jnp.bitwise_xor(shifted, jnp.uint8(_POLY & 0xFF)), shifted)


def _matmul_block_slices(a: jnp.ndarray, xs: list) -> list:
    """(M, K) x K slices of W bytes -> M slices, by the shift-and-add of
    ``_matmul_slice`` on each slice as it lies (no (K, W) stack)."""
    m, kk = a.shape
    acc = [jnp.zeros_like(xs[0]) for _ in range(m)]
    for bit in range(8):
        mask = jnp.uint8(0) - jnp.bitwise_and(jnp.right_shift(a, jnp.uint8(bit)), jnp.uint8(1))
        for i in range(m):
            for k in range(kk):
                acc[i] = jnp.bitwise_xor(acc[i], jnp.bitwise_and(xs[k], mask[i, k]))
        xs = [_xtime(x) for x in xs]
    return acc


def _matmul_slice(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(M, K) x (..., K, W) -> (..., M, W) by shift-and-add over the bits
    of ``a``: a*x = XOR over bits i of a of x*2^i."""
    m, kk = a.shape
    acc = jnp.zeros(b.shape[:-2] + (m, b.shape[-1]), jnp.uint8)
    x = b
    for bit in range(8):
        # 0xFF where bit ``bit`` of a[m, k] is set, else 0
        mask = jnp.uint8(0) - jnp.bitwise_and(jnp.right_shift(a, jnp.uint8(bit)), jnp.uint8(1))
        for k in range(kk):
            acc = jnp.bitwise_xor(
                acc, jnp.bitwise_and(x[..., None, k, :], mask[:, k, None])
            )
        x = _xtime(x)
    return acc


@jax.jit
def matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """GF(2^8) matrix multiply: C[i,j] = XOR_k a[i,k]*b[k,j].

    a: (M, K) uint8, b: (..., K, N) uint8 -> (..., M, N) uint8 (batched
    over b's leading dims). Pure-jnp implementation (the Pallas kernel in
    repro.kernels is the TPU-optimized version; this is the oracle and
    the whole-matrix encode and verify path; the load and repair paths
    take ``matmul_blocks``). N is walked in MATMUL_SLICE-byte
    slices, so beyond its operands and result it holds
    O(M * K * MATMUL_SLICE) bytes, never an (..., M, K, N) product.
    """
    a = a.astype(jnp.uint8)
    b = b.astype(jnp.uint8)
    n = b.shape[-1]
    slice_n = max(1, min(MATMUL_SLICE, n))
    full = n // slice_n

    def body(i, out):
        part = jax.lax.dynamic_slice_in_dim(b, i * slice_n, slice_n, axis=-1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, _matmul_slice(a, part), i * slice_n, axis=-1
        )

    out = jnp.zeros(b.shape[:-2] + (a.shape[0], n), jnp.uint8)
    out = jax.lax.fori_loop(0, full, body, out)
    if n > full * slice_n:
        out = out.at[..., full * slice_n :].set(
            _matmul_slice(a, b[..., full * slice_n :])
        )
    return out


@jax.jit
def matmul_blocks(a: jnp.ndarray, blocks) -> tuple:
    """GF(2^8) product of a (M, K) matrix with K blocks: a tuple of M
    (N,) uint8 blocks, ``out[i] = XOR_k a[i, k] * blocks[k]``.

    ``blocks`` is a sequence of K (N,) uint8 arrays (a (K, N) array is
    read row by row). They are never stacked: each is read where it
    lies, one MATMUL_SLICE-byte slice at a time, so the device holds no
    (K, N) copy and no 2-D tiled layout of the bytes, and beyond its
    operands and results it holds O((M + K) * MATMUL_SLICE) bytes.
    """
    a = a.astype(jnp.uint8)
    blocks = [jnp.asarray(b, jnp.uint8) for b in blocks]
    n = blocks[0].shape[0]
    slice_n = max(1, min(MATMUL_SLICE, n))
    full = n // slice_n

    def body(i, outs):
        parts = [jax.lax.dynamic_slice_in_dim(b, i * slice_n, slice_n) for b in blocks]
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(o, r, i * slice_n, axis=0)
            for o, r in zip(outs, _matmul_block_slices(a, parts))
        )

    outs = tuple(jnp.zeros(n, jnp.uint8) for _ in range(a.shape[0]))
    outs = jax.lax.fori_loop(0, full, body, outs)
    if n > full * slice_n:
        tail = _matmul_block_slices(a, [b[full * slice_n :] for b in blocks])
        outs = tuple(o.at[full * slice_n :].set(r) for o, r in zip(outs, tail))
    return outs


def _xor_reduce(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    return jax.lax.reduce(
        x, jnp.uint8(0), jax.lax.bitwise_xor, dimensions=(axis % x.ndim,)
    )


def xor_reduce(x: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
    """XOR-reduce along ``axis`` (vertical-parity primitive)."""
    return _xor_reduce(x, axis)


# ---------------------------------------------------------------------------
# Host-side matrix helpers over GF(2^8) (numpy; used for generator matrices
# and erasure-decoding matrix inversion — all small: n, k <= a few dozen)
# ---------------------------------------------------------------------------

def np_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Host-side GF matmul for small matrices: (M,K) @ (K,N)."""
    a = a.astype(np.uint8)
    b = b.astype(np.uint8)
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for k in range(a.shape[1]):
        out ^= _MUL_NP[a[:, k][:, None], b[k, :][None, :]]
    return out


def np_inv_matrix(m: np.ndarray) -> np.ndarray:
    """Host-side Gauss-Jordan inversion over GF(2^8). Raises if singular."""
    m = m.astype(np.uint8).copy()
    n = m.shape[0]
    assert m.shape == (n, n)
    aug = np.concatenate([m, np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        pivot = None
        for row in range(col, n):
            if aug[row, col] != 0:
                pivot = row
                break
        if pivot is None:
            raise np.linalg.LinAlgError("singular GF(256) matrix")
        if pivot != col:
            aug[[col, pivot]] = aug[[pivot, col]]
        pinv = _INV_NP[aug[col, col]]
        aug[col] = _MUL_NP[pinv, aug[col]]
        for row in range(n):
            if row != col and aug[row, col] != 0:
                aug[row] ^= _MUL_NP[aug[row, col], aug[col]]
    return aug[:, n:]
