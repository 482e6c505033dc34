"""Distributed (pipelined) vertical XOR repair — the paper's footnote 3,
done properly on a TPU/accelerator mesh (beyond-paper, DESIGN.md §3.2).

The paper's implementation downloads all t survivor blocks to one
repair node (serialized by that node's NIC). On a mesh, the XOR
reduction runs as a log2(t)-round ppermute butterfly under shard_map:
each round halves the number of live partials, every link carries at
most one block per round, so the critical path is

    ceil(log2 t) x (block/link_bw)   vs   t x (block/node_bw)

— for (14,12,5): 3 rounds instead of 5 serialized transfers, and the
XOR compute itself is spread over all t hosts.

Works on any mesh axis (the repair group maps onto the 'data' axis of
the training mesh in the checkpoint layer). Padding to the next
power of two with zero blocks keeps the butterfly exact (XOR identity).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def _butterfly_rounds(n: int) -> int:
    r = 0
    while (1 << r) < n:
        r += 1
    return r


def distributed_xor_repair(blocks: jnp.ndarray, mesh, axis: str = "data"):
    """blocks: (t, q) uint8, one survivor block per mesh shard along
    ``axis`` (t must equal the axis size; pad with zero rows otherwise).
    Returns the repaired block (q,) — XOR of all rows — replicated.
    """
    n = mesh.shape[axis]
    t = blocks.shape[0]
    if t != n:
        pad = np.zeros((n - t, blocks.shape[1]), np.uint8)
        blocks = jnp.concatenate([blocks, jnp.asarray(pad)], axis=0)
    rounds = _butterfly_rounds(n)

    def local(b):
        acc = b[0]  # (q,) — this shard's survivor block
        for r in range(rounds):
            shift = 1 << r
            perm = [(i, i ^ shift) for i in range(n)]
            partner = jax.lax.ppermute(acc, axis, perm)
            acc = jnp.bitwise_xor(acc, partner)
        return acc[None]

    out = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=P(axis, None),
        out_specs=P(axis, None),
        check_vma=False,
    )(blocks)
    # after log2(n) butterfly rounds every shard holds the full XOR
    return out[0]


def xor_repair_critical_path(t: int, block_bytes: int, link_bw: float,
                             node_bw: float) -> tuple[float, float]:
    """(butterfly_seconds, paper_centralized_seconds) — the analytic
    contrast reported in EXPERIMENTS.md §Perf."""
    butterfly = _butterfly_rounds(t) * block_bytes / link_bw
    centralized = t * block_bytes / node_bw
    return butterfly, centralized
