"""The one traffic generator: turns a mix's data file into the faults
set up before the window and the closed-loop units the window drives.

A unit is one ``ObjectGateway.serve`` call. Two unit kinds exist:

  * ``get``: one GET, objects taken round-robin in an order drawn from
    the seed (objects with a planted corruption first, so warm-up is
    where the integrity plane finds them);
  * ``node_loss``: one node, drawn from the seed among the nodes that
    hold the fewest blocks (one as a rule, two once every node that held
    one has been lost), loses its disks (``CapacityLossEvent``: it
    rejoins empty); with ``repair_on_failure`` the call returns only
    after the gateway's background repair has rebuilt what it held. The
    units listed in ``corrupt_units`` also flip a bit in one surviving
    block of a group they repair, away from the lost block's row and
    column, so the repair's source check has something to find.

Set-up faults (``setup_faults``), applied at simulated time 0:

  * ``corrupt_objects``: that many objects, drawn from the seed, get one
    data block silently corrupted; the integrity plane finds it on the
    first read and reads around it from then on;
  * ``crash_one_data_block_per_object`` (true or false): nodes are
    crashed so that each other object misses one data block and no row
    or column of a group loses two, so every object misses exactly one
    data block.

Every choice comes from the seed; the same seed gives the same units
for the same store. Lost columns per object are recorded for the work
accounting (codec_bytes.py).
"""

from __future__ import annotations

import dataclasses

import numpy as np

# Simulated seconds between units: far longer than any unit's fabric
# and repair timeline, so no unit queues behind the previous one.
UNIT_SPACING = 1000.0


@dataclasses.dataclass
class Unit:
    index: int
    requests: list
    events: list
    gets: list  # object ids this unit reads
    damaged: list  # block keys this unit destroys or corrupts


class Mix:
    """``params``: the mix's data file; ``gw``: the loaded gateway."""

    def __init__(self, params: dict, gw, seed: int):
        from repro.gateway import (
            CapacityLossEvent,
            CorruptionEvent,
            FailureEvent,
            Request,
        )

        self._ev = dict(
            loss=CapacityLossEvent, corrupt=CorruptionEvent, crash=FailureEvent
        )
        self._request = Request
        self.params = params
        self.kind = params["unit"]
        if self.kind not in ("get", "node_loss"):
            raise ValueError(f"unknown unit kind {self.kind!r}")
        self.gw = gw
        self.rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
        self.family = gw.family.name
        self.rows = gw.family.rows
        self.n, self.k = gw.code.n, gw.code.k
        self.objects = sorted(gw.meta.objects)
        self.lost_cols: dict[int, set[int]] = {oid: set() for oid in self.objects}
        self.corrupt_objects: list[int] = []
        self.xor_row_lost: dict[str, set[int]] = {}  # CORE XOR-row losses
        self.order: list[int] = []

    # -- set-up ----------------------------------------------------------------
    def setup_events(self) -> list:
        faults = self.params.get("setup_faults", {})
        store = self.gw.store
        events = []
        groups: dict[str, list[int]] = {}
        for oid in self.objects:
            groups.setdefault(self.gw.meta.objects[oid][0], []).append(oid)
        n_corrupt = int(faults.get("corrupt_objects", 0))
        if faults.get("crash_one_data_block_per_object"):
            nodes, self.corrupt_objects = self._crash_set(n_corrupt)
            rows = {v: oid for oid, v in self.gw.meta.objects.items()}
            for node in nodes:
                for gid, row, col in store.keys_on_node(node):
                    if (gid, row) in rows:
                        self.lost_cols[rows[(gid, row)]].add(col)
                    else:
                        self.xor_row_lost.setdefault(gid, set()).add(col)
                events.append(self._ev["crash"](0.0, node))
        else:
            perm = self.rng.permutation(len(self.objects))
            self.corrupt_objects = [self.objects[i] for i in perm[:n_corrupt]]
        for oid in self.corrupt_objects:
            gid, row = self.gw.meta.objects[oid]
            taken = {c for o in groups[gid] for c in self.lost_cols[o]}
            taken |= self.xor_row_lost.get(gid, set())
            cands = [c for c in range(self.k) if c not in taken]
            col = int(self.rng.choice(cands))
            key = (gid, row, col)
            self.lost_cols[oid].add(col)
            events.append(self._ev["corrupt"](0.0, store.node_of(key), blocks=(key,)))
        rest = [o for o in self.objects if o not in self.corrupt_objects]
        self.order = self.corrupt_objects + [
            rest[i] for i in self.rng.permutation(len(rest))
        ]
        return events

    def _crash_set(self, spare: int) -> tuple[list[int], list[int]]:
        """Nodes to crash so that all objects but ``spare`` of them lose
        exactly one data block and no row or column of a group loses two
        (a crashed node may also hold a block of a CORE XOR row, never an
        object's own parity), and the spared objects, which lose theirs
        to a corruption instead. A depth-first search in a seeded order."""
        store = self.gw.store
        owner = {}
        for oid in self.objects:
            gid, row = self.gw.meta.objects[oid]
            for c in range(self.n):
                owner[(gid, row, c)] = oid
        order = [self.objects[i] for i in self.rng.permutation(len(self.objects))]

        def search(used: set, chosen: list, spared: list):
            todo = [o for o in order if ("obj", o) not in used]
            if not todo:
                return (chosen, spared) if len(spared) == spare else None
            gid, row = self.gw.meta.objects[todo[0]]
            for c in self.rng.permutation(self.k):
                node = store.node_of((gid, row, int(c)))
                keys = store.keys_on_node(node)
                marks = [("obj", owner[key]) for key in keys if key in owner]
                marks += [("row", g, r) for g, r, _c in keys]
                marks += [("col", g, cc) for g, _r, cc in keys]
                if (
                    any(key in owner and key[2] >= self.k for key in keys)
                    or len(set(marks)) != len(marks)
                    or used.intersection(marks)
                ):
                    continue
                found = search(used | set(marks), chosen + [node], spared)
                if found is not None:
                    return found
            if len(spared) < spare:
                return search(used | {("obj", todo[0])}, chosen, spared + [todo[0]])
            return None

        found = search(set(), [], [])
        if found is None:
            raise RuntimeError("no set of node crashes hits each object once")
        return found

    def lost_by_group(self) -> dict:
        """Set-up losses, {group: {row: lost columns}}."""
        out: dict[str, dict] = {}
        for oid, cols in self.lost_cols.items():
            gid, row = self.gw.meta.objects[oid]
            out.setdefault(gid, {})[row] = set(cols)
        for gid, cols in self.xor_row_lost.items():
            out.setdefault(gid, {})[self.rows - 1] = set(cols)
        return out

    def warmup_count(self) -> int:
        """Units set-up runs before the window: the mix's own count, and
        for GETs at least one per corrupted object."""
        return max(int(self.params.get("warmup_units", 1)), len(self.corrupt_objects))

    # -- units -------------------------------------------------------------------
    def unit(self, i: int) -> Unit:
        at = (i + 1) * UNIT_SPACING
        if self.kind == "get":
            warm = self.warmup_count()
            if i < warm and self.corrupt_objects:
                oid = self.corrupt_objects[i % len(self.corrupt_objects)]
            else:
                oid = self.order[(i - warm) % len(self.order)]
            return Unit(i, [self._request(time=at, object_id=oid)], [], [oid], [])
        return self._node_loss(i, at)

    def _node_loss(self, i: int, at: float) -> Unit:
        store = self.gw.store
        held: dict[int, int] = {}
        for key in store.blocks:
            if store.available(key):
                held[store.node_of(key)] = held.get(store.node_of(key), 0) + 1
        fewest = min(held.values())
        node = int(self.rng.choice(sorted(n for n, c in held.items() if c == fewest)))
        lost = sorted(k for k in store.keys_on_node(node) if k in store.blocks)
        events = []
        damaged = list(lost)
        if i in self.params.get("corrupt_units", ()):
            gid = lost[0][0]
            rows = {k[1] for k in lost if k[0] == gid}
            cols = {k[2] for k in lost if k[0] == gid}
            cands = sorted(
                (gid, r, c)
                for r in range(self.rows)
                for c in range(self.n)
                if store.available((gid, r, c))
                and store.node_of((gid, r, c)) != node
                and c not in cols
                and (self.rows == 1 or r not in rows)
            )
            key = cands[int(self.rng.integers(len(cands)))]
            events.append(self._ev["corrupt"](at, store.node_of(key), blocks=(key,)))
            damaged.append(key)
        events.append(self._ev["loss"](at, node))
        return Unit(i, [], events, [], damaged)
