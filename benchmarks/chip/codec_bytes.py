"""Bytes each codec must move, from the shapes of the decodes the
algorithm needs: K source blocks read once and M target blocks written,
per operation. Staging, padding, tiling and fusion do not enter, so a
change that stages, pads, fuses or replaces a kernel reads the same work.

An operation is (kind, K, M): kind "xor" (a CORE column rebuilt from its
t survivors) or "gf256" (an RS row decode, M targets from k sources).
"""

from __future__ import annotations


def op_bytes(sources: int, targets: int, block_bytes: int) -> int:
    """HBM bytes of one decode: every source read once, every target
    written once."""
    return (sources + targets) * block_bytes


def decode_ops(family: str, k: int, t: int, lost: dict) -> dict:
    """Operations that rebuild one group's lost blocks, by row: ``lost``
    maps row -> set of lost columns. CORE takes a column's t survivors
    while the column lost one block and t per block costs no more than
    the row's k (the paper's Table 1); every other row is one row decode."""
    in_col: dict[int, int] = {}
    for cols in lost.values():
        for c in cols:
            in_col[c] = in_col.get(c, 0) + 1
    ops: dict[int, list] = {}
    for row, cols in lost.items():
        if not cols:
            continue
        vertical = (
            family == "core"
            and all(in_col[c] == 1 for c in cols)
            and t * len(cols) <= k
        )
        ops[row] = (
            [("xor", t, 1)] * len(cols) if vertical else [("gf256", k, len(cols))]
        )
    return ops


def add_bytes(work: dict, prefix: str, ops: list, block_bytes: int) -> None:
    """Accumulate ``ops``' bytes into ``work`` under ``prefix.kind``."""
    for kind, sources, targets in ops:
        key = f"{prefix}.{kind}"
        work[key] = work.get(key, 0) + op_bytes(sources, targets, block_bytes)
