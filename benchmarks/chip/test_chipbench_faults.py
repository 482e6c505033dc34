"""Each fault a cell can have, planted under a CPU rehearsal of the
cell, turns its result to not correct. A one-chip cell exchanges
nothing between chips, so that fault has no case here."""

from __future__ import annotations

import json
import time

import numpy as np
import pytest

import harness

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
SEED = 2**31 + 202
READS = ["core.degraded_read", "rs.degraded_read"]
REPAIRS = ["core.node_repair", "rs.node_repair"]


def flip(arr):
    out = np.array(arr, copy=True)
    out.reshape(-1)[0] ^= 1
    return out


def plant(monkeypatch, fault: str, kind: str) -> None:
    from repro.gateway.gateway import ObjectGateway
    from repro.storage.blockstore import BlockStore
    from repro.storage.repair import BlockFixer, RepairReport

    if kind == "read":
        assemble = ObjectGateway._assemble_payload
        if fault == "answer_altered":
            monkeypatch.setattr(
                ObjectGateway, "_assemble_payload", lambda self, *a: flip(assemble(self, *a))
            )
        elif fault == "half_left_out":
            monkeypatch.setattr(
                ObjectGateway,
                "_assemble_payload",
                lambda self, *a: assemble(self, *a)[: self.code.k // 2],
            )
        else:  # state_unchanged: the window's GETs are never served
            monkeypatch.setattr(ObjectGateway, "_flush", lambda self, batch, report: None)
        return
    if fault == "answer_altered":
        for name in ("_vertical_repair", "_horizontal_repair", "_family_global_repair"):
            orig = getattr(BlockFixer, name)
            monkeypatch.setattr(
                BlockFixer, name, lambda self, *a, _o=orig: flip(_o(self, *a))
            )
    elif fault == "half_left_out":
        put = BlockStore.put_block
        calls = {"n": 0}

        def every_other(self, key, data, node=None):
            calls["n"] += 1
            if calls["n"] % 2:
                put(self, key, data, node)

        monkeypatch.setattr(BlockStore, "put_block", every_other)
    else:  # state_unchanged: repair returns without rebuilding
        monkeypatch.setattr(
            BlockFixer, "fix_group", lambda self, gid, rows=None: RepairReport(mode="core")
        )


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out", "state_unchanged"])
@pytest.mark.parametrize("cell", READS + REPAIRS)
def test_fault_makes_the_cell_not_correct(monkeypatch, cell, fault):
    plant(monkeypatch, fault, "read" if cell in READS else "repair")
    result = harness.run_cell(
        BENCH, cell, SEED, 0.3, False, t0=time.perf_counter(),
        overrides={"block_bytes": 16384},
    )
    assert not result["correct"], result["check"]
    assert result["failed"] > 0
