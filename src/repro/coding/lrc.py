"""(n, k) Local Reconstruction Codes: a systematic linear code plus local
groups, each an index set of stored blocks that XOR to zero, so a block
that is the only loss of one of its groups is the XOR of the others.

Two constructions share the class:

* ``make_lrc(n, k)``, Azure-style, per the paper's §3.3: a systematic
  global (n-2, k) MDS code contributing n-k-2 global parities and two
  local (k/2+1, k/2) single-parity codes, one per half of the object.
  Layout (paper Fig. 2): [o_1, o_2, p_1, p_2, p_g]

    index 0 .. k/2-1   : first data half  (local group 0)
    index k/2 .. k-1   : second data half (local group 1)
    index k            : p_1 (XOR of group 0)
    index k+1          : p_2 (XOR of group 1)
    index k+2 .. n-1   : global parities, in no local group

* ``make_xorbas(n, k)``, HDFS-Xorbas (arXiv:1301.3791 §2), where the
  RS parities have a local group too, through a parity that is implied
  and never stored. See ``xorbas_generator``.
"""

from __future__ import annotations

import functools
import itertools
from dataclasses import dataclass

import numpy as np

from repro.coding import gf256, rs
from repro.coding.linear import LinearCode

Groups = tuple[tuple[int, ...], ...]


def _check_halves(n: int, k: int, extra: int) -> None:
    if k % 2 != 0:
        raise ValueError("LRC requires even k")
    if n < k + 2 + extra:
        raise ValueError(f"LRC requires n >= k + {2 + extra}")


def _halves(k: int) -> Groups:
    """The two data halves, with local parities at k and k + 1."""
    half = k // 2
    return (tuple(range(half)) + (k,), tuple(range(half, k)) + (k + 1,))


@functools.lru_cache(maxsize=None)
def generator_matrix(n: int, k: int) -> np.ndarray:
    """Azure-style generator (layout in the module docstring)."""
    _check_halves(n, k, 0)
    half = k // 2
    gen = np.zeros((n, k), dtype=np.uint8)
    gen[:k] = np.eye(k, dtype=np.uint8)
    gen[k, :half] = 1  # p_1
    gen[k + 1, half:] = 1  # p_2
    if n > k + 2:
        gen[k + 2 :] = rs.parity_matrix(n - 2, k)  # global parities
    return gen


@functools.lru_cache(maxsize=None)
def make_lrc(n: int, k: int) -> "LRC":
    return LRC(gen=generator_matrix(n, k), groups=_halves(k))


def cyclic_generator_poly(r: int) -> tuple[int, ...]:
    """g(x) = prod_{i<r} (x - alpha^i) over GF(2^8), alpha = 3, lowest
    degree first (r = 4: (85, 120, 36, 8, 1))."""
    g = [1]
    for i in range(r):
        root = gf256.pow_(3, i)
        # g(x) * (x + root): subtraction is addition in characteristic 2
        g = [
            (g[j - 1] if j else 0) ^ (gf256.mul_scalar_np(root, g[j]) if j < len(g) else 0)
            for j in range(len(g) + 1)
        ]
    return tuple(g)


def _poly_mod(num: list[int], g: tuple[int, ...]) -> list[int]:
    """num(x) mod g(x) for a monic g, coefficients lowest degree first."""
    num = list(num)
    deg = len(g) - 1
    for i in range(len(num) - 1, deg - 1, -1):
        c = num[i]
        if c:
            for j, gj in enumerate(g):
                num[i - deg + j] ^= gf256.mul_scalar_np(c, gj)
    return num[:deg]


@functools.lru_cache(maxsize=None)
def xorbas_generator(n: int, k: int) -> np.ndarray:
    """HDFS-Xorbas LRC(n, k) generator, layout [X_1..X_k, P_1..P_r, S_1, S_2]
    with r = n - k - 2.

    The P's are the parities of a shortened cyclic RS(n-2, k) code with
    generator g(x) = prod_{i=0}^{r-1} (x - alpha^i), alpha = 3 in the
    repo's GF(2^8) (0x11B). Encoding is systematic: with the data
    polynomial d(x) = sum_j X_{j+1} x^j, P_{i+1} is the coefficient of
    x^i in p(x) = x^r d(x) mod g(x), and x^r d(x) + p(x) is a multiple
    of g(x), so an RS(n-2, k) code of distance r + 1 (MDS).

    The local parities are S_1 = X_1 + ... + X_{k/2} and
    S_2 = X_{k/2+1} + ... + X_k. Since g(1) = 0, every RS codeword
    c(x) has c(1) = 0: P_1 + ... + P_r = X_1 + ... + X_k = S_1 + S_2.
    That sum is the implied parity S_3, never stored, and it gives the
    RS parities a local group {P_1..P_r, S_1, S_2} of their own. At
    (16, 10) every block is rebuilt by XOR of 5 others, and any 4
    losses decode (distance 5).

    One departure: HDFS-RAID's own RS code may use another field
    polynomial and primitive element, so these bytes are not
    HDFS-RAID's bit for bit; the equations above are.
    """
    _check_halves(n, k, 1)
    r = n - k - 2
    g = cyclic_generator_poly(r)
    gen = np.zeros((n, k), dtype=np.uint8)
    gen[:k] = np.eye(k, dtype=np.uint8)
    for j in range(k):  # the parities of the data block X_{j+1} alone
        gen[k : k + r, j] = _poly_mod([0] * (r + j) + [1], g)
    gen[n - 2, : k // 2] = 1  # S_1
    gen[n - 1, k // 2 :] = 1  # S_2
    return gen


@functools.lru_cache(maxsize=None)
def make_xorbas(n: int, k: int) -> "LRC":
    half = k // 2
    s1, s2 = n - 2, n - 1
    groups = (
        tuple(range(half)) + (s1,),
        tuple(range(half, k)) + (s2,),
        tuple(range(k, n)),  # P_1..P_r, S_1, S_2: the implied parity's group
    )
    return LRC(gen=xorbas_generator(n, k), groups=groups)


@dataclass(frozen=True)
class LRC(LinearCode):
    """LinearCode plus its local groups and local-first repair planning."""

    groups: Groups

    def __post_init__(self):
        for grp in self.groups:
            if np.bitwise_xor.reduce(self.gen[list(grp)], axis=0).any():
                raise ValueError(f"local group {grp} does not XOR to zero")

    def local_groups(self, i: int) -> list[tuple[int, ...]]:
        """Every local group that holds block i."""
        return [grp for grp in self.groups if i in grp]

    def local_group(self, i: int) -> list[int] | None:
        """Blocks of i's first local group (incl. i), or None for a block
        in no group (an Azure global parity)."""
        grps = self.local_groups(i)
        return list(grps[0]) if grps else None

    def local_cost(self, i: int) -> int | None:
        """Source blocks of i's cheapest local repair, or None."""
        grps = self.local_groups(i)
        return min(len(g) for g in grps) - 1 if grps else None

    @functools.cached_property
    def tolerance(self) -> int:
        """The largest e such that every pattern of e erasures decodes,
        found by trying every pattern (n <= 16 holds a few thousand)."""
        for e in range(1, self.n - self.k + 1):
            for lost in itertools.combinations(range(self.n), e):
                if not self.decodable(np.setdiff1d(np.arange(self.n), lost)):
                    return e - 1
        return self.n - self.k

    def repair_plan(
        self, failed: set[int]
    ) -> list[tuple[str, list[int], list[int]]] | None:
        """Greedy local-first repair plan.

        Returns a list of steps ``(kind, sources, repaired)`` where kind is
        'local' (XOR of the other members of a group holding exactly one
        loss) or 'global' (full decode from k sources), or None if the
        pattern is unrecoverable. A block rebuilt locally may close
        another group to one loss, so local steps repeat until none fits.
        """
        failed = set(failed)
        steps: list[tuple[str, list[int], list[int]]] = []
        while failed:
            step = next(
                (
                    ("local", [g for g in grp if g != i], [i])
                    for i in sorted(failed)
                    for grp in self.local_groups(i)
                    if sum(g in failed for g in grp) == 1
                ),
                None,
            )
            if step is not None:
                steps.append(step)
                failed.discard(step[2][0])
                continue
            # fall back to one global decode repairing everything at once
            available = [i for i in range(self.n) if i not in failed]
            if not self.decodable(np.asarray(available)):
                return None
            row_ids, _ = self.decode_matrix(np.asarray(available))
            steps.append(("global", [int(r) for r in row_ids], sorted(failed)))
            failed = set()
        return steps

    @staticmethod
    def plan_traffic(steps: list[tuple[str, list[int], list[int]]]) -> int:
        """Number of block transfers implied by a repair plan."""
        return sum(len(src) for _, src, _ in steps)


def avg_single_repair_cost(n: int, k: int) -> float:
    """Paper §3.3, Azure-style: (2kn - k^2 - 2k) / 2n blocks on average."""
    return (2 * k * n - k * k - 2 * k) / (2 * n)
