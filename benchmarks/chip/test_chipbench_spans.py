"""The span breakdown (span_breakdown.py): the innermost-span reduction on
synthetic events, and CPU traced rehearsals at 16 KiB blocks in which
every reading reads a number, the spans account for the whole window,
and the crc32 bytes per byte are the count the plans give."""

from __future__ import annotations

import json
import time

import pytest

import harness
import span_breakdown as sb

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
SEED = 2**31 + 303
BLOCK = 16384
HOST = ("/host:CPU", "python3")


def test_runtime_events_count_to_the_span_around_them():
    events = [
        (*HOST, "bench.window", 0, 100),
        (*HOST, "bench.unit", 0, 100),
        (*HOST, "bench.serve", 0, 90),
        (*HOST, "gw.serve", 5, 80),
        (*HOST, "gw.fetch", 10, 30),
        (*HOST, "fabric.transfer#bytes=16384#", 12, 3),
        (*HOST, "store.crc32#bytes=16384#", 20, 10),
        (*HOST, "np.asarray(jax.Array)", 32, 6),  # a runtime event: not a span
        (*HOST, "kernel.run", 70, 40),  # cut at its parent's end (85)
        ("/host:CPU", "other thread", "store.crc32", 0, 100),
        ("/device:TPU:0", "XLA Ops", "%a.1 = x", 20, 5),
    ]
    ns = sb.span_ns(events)
    assert ns == {
        "store.crc32": 10,
        "fabric.transfer": 3,
        "gw.fetch": 17,
        "kernel.run": 15,
        "gw.serve": 35,
        "bench.serve": 10,
        "bench.unit": 10,
    }
    assert sum(ns.values()) == 100
    assert sb.share(ns, ("store.crc32", "fabric.transfer")) == pytest.approx(13.0)
    assert sb.share(ns, ("gw.handoff",)) is None


def test_time_under_no_span_is_kept_apart():
    ns = sb.self_ns([(10, 20, "gw.plan")], 0, 30)
    assert ns == {None: 20, "gw.plan": 10}


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        sb.span_ns([(*HOST, "gw.serve", 0, 1)])


def test_readings_name_the_programs_spans():
    from repro.obs.host import SPANS

    assert all(n.startswith(sb.PREFIXES) and not n.startswith("bench.") for n in SPANS)
    assert {n for names in sb.READINGS.values() for n in names} <= set(SPANS)


def crc32_blocks_by_hand(cell: str, unit: sb.UnitCount, gw_rows: int) -> int:
    """Blocks the integrity plane digests in one window unit, from the
    plans. A degraded GET checks each block it fetches (its k-1 direct
    blocks, then a CORE column's t sources or an RS row's parity) and
    its decode's output. A node repair checks every block its group
    still holds before the rebuild, and digests each block it writes
    back."""
    n, k, t = 9, 6, 3
    if cell.endswith("degraded_read"):
        fetched = (k - 1) + (t if cell.startswith("core") else 1)
        return (fetched + 1) * unit.payload_bytes // (k * BLOCK)
    traffic = json.loads((harness.HERE / "traffic" / "node_repair.json").read_text())
    corrupted = 1 if unit.index in traffic["corrupt_units"] else 0
    lost = unit.damaged[: len(unit.damaged) - corrupted]
    groups = {key[0] for key in unit.damaged}
    swept = sum(gw_rows * n - sum(key[0] == g for key in lost) for g in groups)
    return swept + len(unit.damaged)


@pytest.mark.parametrize(
    "cell", ["core.degraded_read", "core.node_repair", "rs.degraded_read", "rs.node_repair"]
)
def test_traced_rehearsal_breaks_the_window_down(cell):
    with sb.kept() as k:
        result = harness.run_cell(
            BENCH, cell, SEED, 0.3, True, t0=time.perf_counter(),
            overrides={"block_bytes": BLOCK},
        )
    assert result["correct"], result["check"]
    unit = "read" if cell.endswith("degraded_read") else "repair"
    got = sb.breakdown(k, unit)
    want = {name for name in sb.READINGS if name.endswith("." + unit)}
    assert set(got["readings"]) == want | {f"crc32_bytes_per_byte.{unit}"}
    assert all(v is not None and v > 0 for v in got["readings"].values()), got["readings"]
    # the spans account for the window, and the window is the harness's
    assert sum(s for _n, s in got["spans_s"]) == pytest.approx(got["window_s"])
    assert got["window_s"] == pytest.approx(result["device"]["window_s"])
    assert None not in dict(got["spans_s"])
    assert k.units
    rows = 4 if cell.startswith("core") else 1
    blocks = sum(crc32_blocks_by_hand(cell, u, rows) for u in k.units)
    assert got["crc32_bytes"] == blocks * BLOCK
    assert got["readings"][f"crc32_bytes_per_byte.{unit}"] == pytest.approx(
        blocks * BLOCK / got["delivered_bytes"]
    )
    # the harness's own result is the one a plain traced run gives
    spec = harness.cell_spec(BENCH, cell)[0]
    assert set(result["metrics"]) == {
        m["name"]
        for m in harness.metrics_for(BENCH, spec, "per_layer")
        if m["source"] == "program_counter"
    }
