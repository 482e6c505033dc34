"""The (n, k, t) CORE product code (paper §4).

Horizontal code: systematic MDS (n, k) Reed-Solomon per object (row).
Vertical code: (t+1, t) single parity check across objects (columns).
Codeword matrix: (t+1) rows x n columns of q-byte blocks; rows 0..t-1 are
the encoded objects, row t is the column-wise XOR parity.

By linearity of both codes the parity row is itself a valid RS(n, k)
codeword (of the XOR of the t objects), so horizontal repair applies to
the parity row too. This property is what makes scheduling (§6.3)
two-dimensional.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.coding import gf256, rs, spc
from repro.coding.linear import LinearCode


@dataclass(frozen=True)
class CoreCode:
    """Parameters of a (n, k, t) CORE product code."""

    n: int
    k: int
    t: int

    def __post_init__(self):
        if not (0 < self.k <= self.n):
            raise ValueError(f"bad (n={self.n}, k={self.k})")
        if self.t < 1:
            raise ValueError("t >= 1 required")

    @property
    def m(self) -> int:
        return self.n - self.k

    @property
    def rows(self) -> int:
        return self.t + 1

    @property
    def stretch(self) -> float:
        return (self.n * (self.t + 1)) / (self.k * self.t)

    @property
    def horizontal(self) -> LinearCode:
        return rs.make_rs(self.n, self.k)

    # -- costs used by scheduling / analysis (block reads) ------------------
    @property
    def vertical_cost(self) -> int:
        return self.t

    @property
    def horizontal_cost(self) -> int:
        return self.k


@jax.jit
def _xor_rows(m: jnp.ndarray) -> jnp.ndarray:
    return gf256.xor_reduce(m, axis=0)


@jax.jit
def _parity_and_xor_row(pgen: jnp.ndarray, objects: list) -> tuple:
    """t rows of k data blocks -> (t rows of m parity blocks, the XOR
    row's n blocks), every block a (q,) array."""
    parity = [gf256.matmul_blocks(pgen, row) for row in objects]
    rows = [[*d, *p] for d, p in zip(objects, parity)]
    return parity, [functools.reduce(jnp.bitwise_xor, col) for col in zip(*rows)]


@dataclass(frozen=True)
class CoreCodec:
    """Encode / repair engine for a CORE product code over block arrays."""

    code: CoreCode

    def encode(self, objects: jnp.ndarray) -> jnp.ndarray:
        """objects: (t, k, q) uint8 -> full CORE matrix (t+1, n, q).

        Mirrors the paper's implementation: horizontal RS per object first,
        then one vertical XOR parity row across data AND parity columns.
        """
        c = self.code
        if objects.shape[:2] != (c.t, c.k):
            raise ValueError(f"expected {(c.t, c.k)} leading dims, got {objects.shape}")
        horiz = self.code.horizontal.encode(objects)  # (t, n, q)
        parity_row = _xor_rows(horiz)  # (n, q)
        return jnp.concatenate([horiz, parity_row[None]], axis=0)

    def encode_computed(self, objects) -> tuple[list, list]:
        """objects: (at most t, k, q) uint8 -> the blocks the code
        computes, on the device, each a (q,) array: t rows of m parity
        blocks and the XOR row's n blocks. Each data block is uploaded
        as it lies; a missing object is a zero row (a short group's
        padding)."""
        c = self.code
        num, k, q = objects.shape
        if num > c.t or k != c.k:
            raise ValueError(f"expected at most {c.t} objects of {c.k} blocks, got {objects.shape}")
        rows = [list(obj) for obj in objects]
        rows += [[jnp.zeros(q, jnp.uint8)] * k] * (c.t - num)
        return _parity_and_xor_row(rs.parity_matrix(c.n, c.k), rows)

    def decode_object(self, row_blocks: jnp.ndarray, available: np.ndarray) -> jnp.ndarray:
        """Recover one object's (k, q) data from >=k available blocks of its row."""
        return self.code.horizontal.decode(available, row_blocks)

    def repair_vertical(self, column_blocks: jnp.ndarray) -> jnp.ndarray:
        """Repair the single missing block of a column from its t survivors.

        column_blocks: (t, q) — the surviving blocks of that column.
        """
        c = self.code
        if column_blocks.shape[0] != c.t:
            raise ValueError(f"vertical repair needs exactly t={c.t} survivors")
        return spc.repair(column_blocks, axis=0)

    def repair_horizontal(
        self, row_blocks: jnp.ndarray, available: np.ndarray, missing: np.ndarray
    ) -> jnp.ndarray:
        """Repair ``missing`` blocks of a row from >=k available blocks."""
        return self.code.horizontal.repair(available, row_blocks, missing)

    def verify(self, matrix: jnp.ndarray) -> bool:
        """Check product-code consistency of a full (t+1, n, q) matrix."""
        c = self.code
        ok_v = bool(jnp.all(_xor_rows(matrix) == 0))
        reenc = self.code.horizontal.encode(matrix[:, : c.k])
        ok_h = bool(jnp.all(reenc == matrix))
        return ok_v and ok_h
