"""CPU rehearsal of every cell through the harness's internal entry, at
16 KiB blocks: a sound run comes out correct, and the control (the
configuration's integrity check switched off) comes out not correct."""

from __future__ import annotations

import json
import time

import pytest

import harness

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
BENCH_CELL = {w["name"]: w for w in BENCH["workloads"]}
CELLS = list(BENCH_CELL)
SEED = 2**31 + 101


def run(cell: str, trace: bool = False, control: bool = False, seconds: float = 0.3):
    return harness.run_cell(
        BENCH, cell, SEED, seconds, trace, t0=time.perf_counter(),
        control=control, overrides={"block_bytes": 16384},
    )


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal_is_correct(cell):
    result = run(cell)
    assert result["correct"], result["check"]
    assert result["attempted"] > 0 and result["failed"] == 0
    want = {m["name"] for m in harness.metrics_for(BENCH, BENCH_CELL[cell], "end_to_end")}
    assert set(result["metrics"]) == want and "setup_s" in want and len(want) >= 2
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "check"
    assert all(c["value"] <= c["limit"] for c in result["check"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_cell_control_is_not_correct(cell):
    result = run(cell, control=True)
    assert not result["correct"]
    assert result["failed"] > 0


def test_traced_rehearsal_reads_no_device():
    """On the CPU the trace holds no TPU plane: the device readers find
    nothing and leave their metrics out; the counters still read."""
    result = run("core.degraded_read", trace=True)
    assert result["correct"]
    assert set(result["metrics"]) == {"launches_per_gib.read"}
    assert result["device"]["busy_s"] == 0.0 and result["device"]["window_s"] > 0

