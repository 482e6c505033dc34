"""Plain numpy reference for the benchmark's comparison.

GF(2^8) arithmetic, the systematic (n, k) Reed-Solomon code and the CORE
(n, k, t) product code, written from their published definitions and
importing nothing of the system under test:

  * GF(2^8) modulo x^8 + x^4 + x^3 + x + 1 (0x11B), addition is XOR;
  * RS(n, k): the Vandermonde matrix V[i, j] = (i + 1)^j, made systematic
    as G = V @ inv(V[:k]) = [I_k; P]; a stripe stores data then P @ data;
  * CORE(n, k, t): t RS rows (one object each) plus one row that is the
    XOR of the t rows, data and parity columns alike (arXiv:1302.5192).

Block contents are compared exactly, so every function here returns
bytes, never digests.
"""

from __future__ import annotations

import functools

import numpy as np

POLY = 0x11B


def _mul_slow(a: int, b: int) -> int:
    """Shift-and-add product in GF(2^8)."""
    out = 0
    while b:
        if b & 1:
            out ^= a
        a <<= 1
        if a & 0x100:
            a ^= POLY
        b >>= 1
    return out


@functools.lru_cache(maxsize=1)
def mul_table() -> np.ndarray:
    """(256, 256) uint8 table of every product."""
    return np.array(
        [[_mul_slow(a, b) for b in range(256)] for a in range(256)], dtype=np.uint8
    )


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(np.flatnonzero(mul_table()[a] == 1)[0])


def gf_pow(a: int, e: int) -> int:
    out = 1
    for _ in range(e):
        out = _mul_slow(out, a)
    return out


def gf_matmul_small(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Product of two small uint8 matrices over GF(2^8)."""
    mul = mul_table()
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for i in range(a.shape[0]):
        for j in range(b.shape[1]):
            acc = 0
            for t in range(a.shape[1]):
                acc ^= int(mul[a[i, t], b[t, j]])
            out[i, j] = acc
    return out


def gf_inv_matrix(m: np.ndarray) -> np.ndarray:
    """Gauss-Jordan inverse of a square uint8 matrix over GF(2^8)."""
    mul = mul_table()
    n = m.shape[0]
    aug = np.concatenate([m.astype(np.uint8), np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        pivot = next(r for r in range(col, n) if aug[r, col])
        aug[[col, pivot]] = aug[[pivot, col]]
        aug[col] = mul[gf_inv(int(aug[col, col]))][aug[col]]
        for r in range(n):
            if r != col and aug[r, col]:
                aug[r] ^= mul[int(aug[r, col])][aug[col]]
    return aug[:, n:]


@functools.lru_cache(maxsize=None)
def rs_generator(n: int, k: int) -> np.ndarray:
    """(n, k) systematic generator [I_k; P]."""
    vand = np.array(
        [[gf_pow(i + 1, j) for j in range(k)] for i in range(n)], dtype=np.uint8
    )
    return gf_matmul_small(vand, gf_inv_matrix(vand[:k]))


@functools.lru_cache(maxsize=64)
def _pair_table(c: int) -> np.ndarray:
    """c times both bytes of every uint16, so one lookup multiplies two
    bytes (byte order does not matter: each byte maps on its own)."""
    row = mul_table()[c].astype(np.uint16)
    return ((row[:, None] << 8) | row[None, :]).reshape(-1)


def gf_combine(coeffs, blocks) -> np.ndarray:
    """sum_i coeffs[i] * blocks[i] over GF(2^8): K blocks of q -> (q,)."""
    q = len(blocks[0])
    out = np.zeros(q, dtype=np.uint8)
    wide = q % 2 == 0
    for c, blk in zip(coeffs, blocks):
        c = int(c)
        if c == 0:
            continue
        if c == 1:
            out ^= blk
        elif wide:
            out.view(np.uint16)[:] ^= _pair_table(c)[blk.view(np.uint16)]
        else:
            out ^= mul_table()[c][blk]
    return out


def rs_encode(n: int, k: int, data: np.ndarray) -> np.ndarray:
    """data (k, q) -> stripe (n, q)."""
    gen = rs_generator(n, k)
    parity = [gf_combine(gen[j], data) for j in range(k, n)]
    return np.concatenate([data, np.stack(parity)]) if parity else data.copy()


def rs_decode(n: int, k: int, cols, blocks: np.ndarray) -> np.ndarray:
    """Recover the (k, q) data from the k stripe blocks ``blocks`` stored
    at columns ``cols``."""
    inv = gf_inv_matrix(rs_generator(n, k)[list(cols)])
    return np.stack([gf_combine(inv[i], blocks) for i in range(k)])


def core_encode(n: int, k: int, t: int, objects: np.ndarray) -> np.ndarray:
    """objects (t, k, q) -> group (t + 1, n, q)."""
    rows = np.stack([rs_encode(n, k, obj) for obj in objects])
    return np.concatenate([rows, np.bitwise_xor.reduce(rows, axis=0)[None]])


def xor_repair(survivors: np.ndarray) -> np.ndarray:
    """The one missing block of a CORE column from its t survivors."""
    return np.bitwise_xor.reduce(survivors, axis=0)


def expected_block(
    family: str, n: int, k: int, t: int, objects, row: int, col: int
) -> np.ndarray:
    """What one stored block must hold. ``objects`` are the group's
    objects, t of (k, q) for CORE and one for RS; only the block asked
    for is computed (an RS parity block is linear in the data, so CORE's
    XOR row at a parity column is the parity of the XORed data)."""
    if family == "core":
        xor_row = row == t
    elif family == "rs":
        if row != 0:
            raise ValueError(f"an RS stripe has one row, not row {row}")
        xor_row = False
    else:
        raise ValueError(f"no reference for code family {family!r}")

    def data(c: int) -> np.ndarray:
        if xor_row:
            return functools.reduce(np.bitwise_xor, [obj[c] for obj in objects])
        return objects[row][c]

    if col < k:
        return np.array(data(col), copy=True)
    return gf_combine(rs_generator(n, k)[col], [data(c) for c in range(k)])
