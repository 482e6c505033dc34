"""Plain numpy reference for the HDFS-Xorbas LRC(n, k) (arXiv:1301.3791
§2), written from its equations and importing nothing of the system
under test; GF(2^8) comes from reference.py (0x11B):

  * a stripe stores [X_1..X_k, P_1..P_r, S_1, S_2], r = n - k - 2;
  * the P's are the systematic parities of the cyclic RS(n - 2, k) code
    with generator g(x) = (x - a^0)(x - a^1)...(x - a^(r-1)), a = 3: with
    d(x) = X_1 + X_2 x + ... + X_k x^(k-1), P_(i+1) is the coefficient of
    x^i in x^r d(x) mod g(x), computed here block-wise by the division
    register of a cyclic encoder;
  * S_1 = X_1 + ... + X_(k/2), S_2 = X_(k/2+1) + ... + X_k, and since
    g(1) = 0 the P's sum to S_1 + S_2 (the implied parity), so the local
    groups are the two halves with their S, and {P_1..P_r, S_1, S_2}.
"""

from __future__ import annotations

import functools
import itertools

import numpy as np

import reference


def generator_poly(r: int) -> list[int]:
    """g(x), lowest degree first."""
    mul = reference.mul_table()
    g = [1]
    for i in range(r):
        root = reference.gf_pow(3, i)
        g = [0] + g  # x * g(x)
        for j in range(len(g) - 1):
            g[j] ^= int(mul[root, g[j + 1]])  # + root * g(x)
    return g


def encode(n: int, k: int, data: np.ndarray) -> np.ndarray:
    """data (k, q) -> stripe (n, q)."""
    r = n - k - 2
    g = generator_poly(r)
    reg = [np.zeros_like(data[0]) for _ in range(r)]  # remainder, low first
    for j in range(k - 1, -1, -1):  # highest degree first
        fb = data[j] ^ reg[-1]
        reg = [reference.gf_combine([g[0]], [fb])] + [
            reg[i - 1] ^ reference.gf_combine([g[i]], [fb]) for i in range(1, r)
        ]
    s1 = np.bitwise_xor.reduce(data[: k // 2], axis=0)
    s2 = np.bitwise_xor.reduce(data[k // 2 :], axis=0)
    return np.concatenate([data, np.stack(reg), s1[None], s2[None]])


def local_groups(n: int, k: int) -> list[list[int]]:
    half = k // 2
    return [
        list(range(half)) + [n - 2],
        list(range(half, k)) + [n - 1],
        list(range(k, n)),
    ]


def local_repair(n: int, k: int, col: int, stripe: dict) -> np.ndarray:
    """Block ``col`` from the other members of a group of it that
    ``stripe`` ({column: block}) holds whole."""
    for grp in local_groups(n, k):
        others = [c for c in grp if c != col]
        if col in grp and all(c in stripe for c in others):
            return np.bitwise_xor.reduce(np.stack([stripe[c] for c in others]), axis=0)
    raise ValueError(f"no local group of block {col} is whole")


@functools.lru_cache(maxsize=None)
def generator(n: int, k: int) -> np.ndarray:
    """(n, k): the stripe of the k unit data vectors."""
    return encode(n, k, np.eye(k, dtype=np.uint8))


def decode(n: int, k: int, stripe: dict) -> np.ndarray:
    """The (k, q) data from the blocks ``stripe`` ({column: block})
    holds: the first k of them whose generator rows are independent."""
    gen = generator(n, k)
    for cols in itertools.combinations(sorted(stripe), k):
        try:
            inv = reference.gf_inv_matrix(gen[list(cols)])
        except StopIteration:  # singular: no pivot in some column
            continue
        blocks = [stripe[c] for c in cols]
        return np.stack([reference.gf_combine(inv[i], blocks) for i in range(k)])
    raise ValueError(f"columns {sorted(stripe)} do not decode")
