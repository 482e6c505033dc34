"""Simulated distributed block store (the HDFS analogue).

Blocks are addressed by (group_id, row, col) — a cell of a CORE matrix
(for plain RS groups, row is always 0). Placement is anti-colocating like
HDFS-RAID's RaidNode policy: all blocks of a group land on distinct
nodes, so a node failure costs each group at most one block — the failure
model under which the paper's per-column/-row analysis holds.

Rack awareness (XORing Elephants, 1301.3791): when ``nodes_per_rack``
is set, nodes are partitioned into failure domains of that size and
placement lifts the anti-colocation invariant from nodes to racks — no
two blocks of the same row OR column share a rack, so a whole-rack
failure (ToR switch, PDU) still costs each stripe and each vertical
group at most one block. With ``nodes_per_rack=None`` every node is its
own rack and the classic layout is byte-identical to before.

Data lives in host numpy (this is the "disk"); codec math runs in JAX.

Integrity plane: every stored block carries a crc32 digest computed at
PUT time (``checksums``). ``verify`` recomputes a block's digest against
the stored one — a mismatch means SILENT corruption (a bit flip or torn
write injected by ``corrupt_block`` leaves the stored digest stale on
purpose, exactly like a disk returning bad bytes under a good extent
map). The gateway reclassifies a verify failure as an erasure:
``quarantine`` removes the bytes from the readable set while keeping the
placement and the reference digest, so repair re-places the block in
situ and the repaired bytes can be checked against the original digest.
"""

from __future__ import annotations

import functools
import os
import threading
import zlib
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

from repro.obs.host import span

BlockKey = tuple[str, int, int]  # (group_id, row, col)

# A block of at least two chunks is digested chunk by chunk on a thread
# pool (zlib.crc32 releases the GIL) and the chunks' crc32s are combined
# into the whole block's; a smaller block is digested serially in place.
CRC32_CHUNK_BYTES = 8 << 20
CRC32_MAX_WORKERS = 8
_CRC32_POLY = 0xEDB88320  # zlib's crc32 polynomial, bit-reflected

_crc32_pool: ThreadPoolExecutor | None = None
_crc32_pool_lock = threading.Lock()


def _crc32_workers() -> int:
    return min(CRC32_MAX_WORKERS, os.cpu_count() or 1)


def crc32_splits(nbytes: int) -> bool:
    """Whether ``crc32`` digests ``nbytes`` in chunks on the pool."""
    return nbytes >= 2 * CRC32_CHUNK_BYTES and _crc32_workers() > 1


def _crc32_executor() -> ThreadPoolExecutor:
    """The process's digest pool, made on first use."""
    global _crc32_pool
    with _crc32_pool_lock:
        if _crc32_pool is None:
            _crc32_pool = ThreadPoolExecutor(_crc32_workers(), thread_name_prefix="crc32")
        return _crc32_pool


def _gf2_times(mat: tuple[int, ...], vec: int) -> int:
    """A 32 x 32 GF(2) matrix (one int per column) times a 32-bit vector."""
    out, i = 0, 0
    while vec:
        if vec & 1:
            out ^= mat[i]
        vec >>= 1
        i += 1
    return out


def _gf2_mul(a: tuple[int, ...], b: tuple[int, ...]) -> tuple[int, ...]:
    return tuple(_gf2_times(a, col) for col in b)


@functools.lru_cache(maxsize=16)
def _crc32_zeros_op(nbytes: int) -> tuple[int, ...]:
    """The operator that advances a crc32 register over ``nbytes`` zero
    bytes, built by squaring (zlib's crc32_combine)."""
    op = (_CRC32_POLY, *(1 << i for i in range(31)))  # one zero bit
    for _ in range(3):  # one zero byte
        op = _gf2_mul(op, op)
    out = tuple(1 << i for i in range(32))
    while nbytes:
        if nbytes & 1:
            out = _gf2_mul(op, out)
        nbytes >>= 1
        if nbytes:
            op = _gf2_mul(op, op)
    return out


def crc32_combine(crc1: int, crc2: int, len2: int) -> int:
    """crc32 of A + B from crc32(A), crc32(B) and len(B)."""
    return _gf2_times(_crc32_zeros_op(len2), crc1) ^ crc2


def crc32(buf: np.ndarray) -> int:
    """``zlib.crc32`` of a flat uint8 array, split across the pool when
    it holds two chunks or more and the host has cores to spare."""
    n = buf.nbytes
    if not crc32_splits(n):
        return zlib.crc32(buf)
    starts = range(0, n, CRC32_CHUNK_BYTES)
    crcs = list(
        _crc32_executor().map(zlib.crc32, (buf[s : s + CRC32_CHUNK_BYTES] for s in starts))
    )
    out = crcs[0]
    for s, c in zip(starts[1:], crcs[1:]):
        out = crc32_combine(out, c, min(CRC32_CHUNK_BYTES, n - s))
    return out


class PlacementError(RuntimeError):
    pass


@dataclass
class BlockStore:
    num_nodes: int
    nodes_per_rack: int | None = None
    blocks: dict[BlockKey, np.ndarray] = field(default_factory=dict)
    placement: dict[BlockKey, int] = field(default_factory=dict)
    failed_nodes: set[int] = field(default_factory=set)
    checksums: dict[BlockKey, int] = field(default_factory=dict)
    # bytes digested since the store was made, cumulative: the integrity
    # plane's work, read as a difference over a window; of those, the
    # bytes digested in chunks on the pool (crc32's split path)
    crc32_bytes: int = 0
    crc32_split_bytes: int = 0
    _group_counter: int = 0

    # -- failure domains -------------------------------------------------------
    def rack_of(self, node: int) -> int:
        """Failure-domain id of ``node``. With no rack map configured,
        every node is its own rack (node-level anti-colocation only)."""
        if self.nodes_per_rack is None:
            return int(node)
        return int(node) // self.nodes_per_rack

    # -- integrity -------------------------------------------------------------
    def digest(self, data: np.ndarray) -> int:
        """zlib crc32 of a block's bytes, read where they lie (only a
        non-contiguous block is copied, once); the one crc32 site of the
        store, counted in ``crc32_bytes``."""
        buf = np.ascontiguousarray(data).reshape(-1).view(np.uint8)
        self.crc32_bytes += buf.nbytes
        with span("store.crc32", bytes=buf.nbytes):
            if crc32_splits(buf.nbytes):
                self.crc32_split_bytes += buf.nbytes
            return crc32(buf)

    # -- placement -----------------------------------------------------------
    def _place_group(self, group_id: str, rows: int, cols: int) -> None:
        """Anti-colocated placement of a (rows x cols) group.

        All-distinct when the cluster is big enough; otherwise a
        latin-square-style layout — node(r,c) = (off + c + K*r) mod N —
        guaranteeing no two blocks of the same row OR column share a
        node (one node failure => at most one failure per stripe and
        per vertical group), which is the paper's placement requirement
        for its 20-node clusters."""
        need = rows * cols
        alive = [n for n in range(self.num_nodes) if n not in self.failed_nodes]
        # crc32, not hash(): placement must be stable across processes
        # (PYTHONHASHSEED randomizes str hashes per run)
        salt = zlib.crc32(group_id.encode()) ^ self._group_counter
        offset = salt % len(alive)
        self._group_counter += 1
        if self.nodes_per_rack is not None:
            self._place_group_rack_aware(group_id, rows, cols, alive, salt)
            return
        if need <= len(alive):
            chosen = [alive[(offset + i) % len(alive)] for i in range(need)]
            i = 0
            for r in range(rows):
                for c in range(cols):
                    self.placement[(group_id, r, c)] = chosen[i]
                    i += 1
            return
        n = len(alive)
        if max(rows, cols) > n:
            raise PlacementError(
                f"group {group_id} needs >= {max(rows, cols)} nodes for "
                f"row/column anti-colocation, {n} alive"
            )
        k_step = next(
            (k for k in range(1, n) if all((k * d) % n for d in range(1, rows))),
            None,
        )
        if k_step is None:
            raise PlacementError(f"no anti-colocating stride for {rows}x{cols} on {n}")
        for r in range(rows):
            for c in range(cols):
                self.placement[(group_id, r, c)] = alive[(offset + c + k_step * r) % n]

    def _place_group_rack_aware(
        self, group_id: str, rows: int, cols: int, alive: list[int], salt: int
    ) -> None:
        """Latin-square layout over RACKS instead of nodes: rack(r, c) =
        racks[(off + c + step*r) mod R]. With R >= cols the racks within
        a row are all distinct, and an anti-colocating stride keeps the
        racks within a column distinct — one whole-rack failure costs
        each stripe and each vertical group at most one block. Within a
        rack, a per-group rotation spreads blocks over the rack's alive
        nodes (distinct nodes whenever capacity allows)."""
        racks: dict[int, list[int]] = {}
        for n in alive:
            racks.setdefault(self.rack_of(n), []).append(n)
        rack_ids = sorted(racks)
        n_racks = len(rack_ids)
        if n_racks < cols:
            raise PlacementError(
                f"group {group_id}: rack-aware placement needs >= {cols} racks "
                f"with alive nodes (one rack per stripe block), {n_racks} available"
            )
        step = next(
            (s for s in range(1, n_racks) if all((s * d) % n_racks for d in range(1, rows))),
            None,
        )
        if step is None:
            raise PlacementError(
                f"no anti-colocating rack stride for {rows}x{cols} over {n_racks} racks"
            )
        off = salt % n_racks
        used: set[int] = set()
        spin: dict[int, int] = {}
        for r in range(rows):
            for c in range(cols):
                rid = rack_ids[(off + c + step * r) % n_racks]
                members = racks[rid]
                start = (salt + spin.get(rid, 0)) % len(members)
                spin[rid] = spin.get(rid, 0) + 1
                node = next(
                    (
                        members[(start + i) % len(members)]
                        for i in range(len(members))
                        if members[(start + i) % len(members)] not in used
                    ),
                    members[start],
                )
                used.add(node)
                self.placement[(group_id, r, c)] = node

    # -- block API ------------------------------------------------------------
    def put_group(self, group_id: str, matrix: np.ndarray) -> None:
        """Store a full (rows, cols, q) group."""
        rows, cols = matrix.shape[:2]
        self._place_group(group_id, rows, cols)
        for r in range(rows):
            for c in range(cols):
                blk = np.asarray(matrix[r, c])
                self.blocks[(group_id, r, c)] = blk
                self.checksums[(group_id, r, c)] = self.digest(blk)

    def put_block(self, key: BlockKey, data: np.ndarray, node: int | None = None) -> None:
        cur = self.placement.get(key)
        if node is not None:
            self.placement[key] = node
        elif cur is None or cur in self.failed_nodes:
            # (re-)place on a fresh alive node not already used by the group
            alive = [n for n in range(self.num_nodes) if n not in self.failed_nodes]
            used = {
                self.placement[k]
                for k in self.placement
                if k[0] == key[0] and self.available(k)
            }
            free = [n for n in alive if n not in used]
            if free:
                if self.nodes_per_rack is not None:
                    # keep the rack invariant on repair write-back: avoid
                    # racks already hosting a live block of this row/col
                    gid, row, col = key
                    bad_racks = {
                        self.rack_of(self.placement[k])
                        for k in self.placement
                        if k[0] == gid
                        and k != key
                        and (k[1] == row or k[2] == col)
                        and self.available(k)
                    }
                    rack_ok = [n for n in free if self.rack_of(n) not in bad_racks]
                    if rack_ok:
                        free = rack_ok
                self.placement[key] = free[0]
            else:
                # dense cluster: every alive node already hosts a group
                # block. Fall back to the weaker-but-essential invariant
                # (the paper's placement requirement): never co-locate
                # with another live block of the same ROW or COLUMN, so
                # one node failure still costs each stripe and each
                # vertical group at most one block.
                gid, row, col = key
                conflict = {
                    self.placement[k]
                    for k in self.placement
                    if k[0] == gid
                    and k != key
                    and (k[1] == row or k[2] == col)
                    and self.available(k)
                }
                if self.nodes_per_rack is not None:
                    # rack-level anti-colocation first, node-level fallback
                    bad_racks = {self.rack_of(n) for n in conflict}
                    cands = [n for n in alive if self.rack_of(n) not in bad_racks]
                    if not cands:
                        cands = [n for n in alive if n not in conflict]
                else:
                    cands = [n for n in alive if n not in conflict]
                if not cands:
                    cands = alive
                # crc32-keyed pick (process-stable, like _place_group):
                # always taking the first candidate would funnel every
                # dense re-placement onto the lowest alive ids and turn
                # them into post-repair hotspots
                self.placement[key] = cands[
                    zlib.crc32(repr(key).encode()) % len(cands)
                ]
        blk = np.asarray(data)
        self.blocks[key] = blk
        self.checksums[key] = self.digest(blk)

    def node_of(self, key: BlockKey) -> int:
        return self.placement[key]

    def available(self, key: BlockKey) -> bool:
        return (
            key in self.blocks
            and self.placement.get(key) is not None
            and self.placement[key] not in self.failed_nodes
        )

    def get(self, key: BlockKey) -> np.ndarray:
        if not self.available(key):
            raise KeyError(f"block {key} unavailable (node failed or missing)")
        return self.blocks[key]

    def verify(self, key: BlockKey) -> bool:
        """Recompute ``key``'s digest against the one stored at PUT.
        False means silent corruption. Blocks with no stored digest
        (pre-integrity writers) pass vacuously."""
        want = self.checksums.get(key)
        if want is None or key not in self.blocks:
            return True
        return self.digest(self.blocks[key]) == want

    def checksum_ok(self, key: BlockKey, data: np.ndarray) -> bool | None:
        """Check reconstructed ``data`` against ``key``'s reference digest
        (decode-output verification). None when no digest is on file."""
        want = self.checksums.get(key)
        if want is None:
            return None
        return self.digest(data) == want

    def keys_on_node(self, node: int) -> list[BlockKey]:
        """All block keys currently placed on ``node`` (whether or not the
        node is alive) — the unit a node-level fault event acts on."""
        return [k for k, n in self.placement.items() if n == node]

    # -- failures --------------------------------------------------------------
    def fail_nodes(self, nodes: set[int] | list[int]) -> None:
        self.failed_nodes.update(int(n) for n in nodes)

    def heal_node(self, node: int) -> None:
        """Transient failure over: the node rejoins with its blocks
        intact (a reboot / network partition, not a disk loss)."""
        self.failed_nodes.discard(int(node))

    def lose_node_blocks(self, node: int) -> list[BlockKey]:
        """Permanent capacity loss: the node's blocks are destroyed (disk
        failure). The node itself rejoins the alive set empty — only a
        repair write-back can bring the data back. Returns the lost keys."""
        lost = self.keys_on_node(node)
        for key in lost:
            self.blocks.pop(key, None)
            self.placement.pop(key, None)
            self.checksums.pop(key, None)
        self.failed_nodes.discard(int(node))
        return lost

    # -- corruption ------------------------------------------------------------
    def corrupt_block(self, key: BlockKey, mode: str = "bitflip") -> bool:
        """Damage one stored block in place — the single implementation
        behind both enforced-failure-pattern tests and the scenario
        engine's ``CorruptionEvent``.

        ``bitflip`` flips one bit at a key-derived offset; ``torn``
        zeroes the trailing half (a torn write); both leave the stored
        digest STALE, so the damage is silent until a fetch or scrub
        verifies. ``erase`` destroys the bytes outright (the old
        ``drop_block`` semantics). Returns False (no-op) when the block
        holds no bytes to damage. Always writes a fresh array — callers
        (the cache, test expectations) may hold references to the old
        one."""
        blk = self.blocks.get(key)
        if blk is None:
            return False
        if mode == "erase":
            self.blocks.pop(key, None)
            return True
        flat = np.asarray(blk).copy().reshape(-1).view(np.uint8)
        if flat.size == 0:
            return False
        if mode == "bitflip":
            pos = zlib.crc32(repr(key).encode()) % flat.size
            flat[pos] ^= 1 << (zlib.crc32(repr(key).encode(), 7) % 8)
        elif mode == "torn":
            flat[flat.size // 2 :] = 0
        else:
            raise ValueError(f"unknown corruption mode {mode!r}")
        self.blocks[key] = flat.view(np.asarray(blk).dtype).reshape(
            np.asarray(blk).shape
        )
        return True

    def quarantine(self, key: BlockKey) -> None:
        """Detection outcome: pull corrupt bytes out of the readable set.
        Placement and the reference digest survive, so repair re-puts the
        block on its original node and the repaired bytes can be verified
        against the original content digest."""
        self.blocks.pop(key, None)

    def drop_block(self, key: BlockKey) -> None:
        """Targeted single-block erasure (for enforced failure patterns).
        Thin wrapper over the unified corruption path."""
        self.corrupt_block(key, mode="erase")

    def failure_matrix(self, group_id: str, rows: int, cols: int) -> np.ndarray:
        fm = np.zeros((rows, cols), dtype=bool)
        for r in range(rows):
            for c in range(cols):
                fm[r, c] = not self.available((group_id, r, c))
        return fm

    def alive_nodes(self) -> list[int]:
        return [n for n in range(self.num_nodes) if n not in self.failed_nodes]
