"""The trace reduction on a small trace recorded on a TPU v5e."""

from __future__ import annotations

import json
import pathlib

import pytest

import trace_reduce

TRACE = pathlib.Path(__file__).resolve().parent / "testdata" / "core_degraded_read_trace.json"


@pytest.fixture(scope="module")
def events():
    return [tuple(e) for e in json.loads(TRACE.read_text())["events"]]


def test_recorded_trace_matches_a_plain_count(events):
    """Busy time, window and module time against a brute-force count."""
    got = trace_reduce.reduce_events(events)
    (w0, w1), = [(s, s + d) for _p, _l, n, s, d in events if n == "bench.window"]
    ops = sorted(
        (max(s, w0), min(s + d, w1))
        for p, line, _n, s, d in events
        if p == "/device:TPU:0" and line == "XLA Ops" and s + d > w0 and s < w1
    )
    busy, end = 0, None
    for s, e in ops:
        if end is None or s > end:
            busy += e - s
            end = e
        elif e > end:
            busy += e - end
            end = e
    mods = sum(d for p, line, n, _s, d in events if line == "XLA Modules")
    assert got.devices == 1
    assert got.window_s == pytest.approx(1e-9 * (w1 - w0))
    assert got.busy_s == pytest.approx(1e-9 * busy)
    assert got.module_seconds(("ragged_xor_tiles",)) == pytest.approx(1e-9 * mods)
    assert got.module_seconds(("_gf_matmul_jit",)) == 0.0
    # the readings that run printed on the chip
    assert got.window_s == pytest.approx(10.150735183)
    assert got.busy_s == pytest.approx(0.0020275)
    assert 99.9 < got.idle_percent() < 100.0
    assert got.top_ops[0][0] == "ragged_xor_tiles.1"
    assert got.idle_gaps[0][0] == "bench.serve"
    assert sum(s for _n, s in got.idle_gaps) == pytest.approx(got.window_s - got.busy_s)


def test_nested_host_events_label_the_gaps():
    tpu, host = "/device:TPU:0", ("/host:CPU", "python3")
    events = [
        (*host, "bench.window", 0, 100),
        (*host, "bench.serve", 0, 60),
        (*host, "np.asarray(jax.Array)", 10, 20),
        (*host, "bench.record", 70, 30),
        (tpu, "XLA Ops", "%a.1 = x", 5, 10),
        (tpu, "XLA Ops", "%b.2 = y", 12, 8),  # overlaps a.1: counted once
        (tpu, "XLA Modules", "jit_a(1)", 5, 15),
        (tpu, "XLA Ops", "%c.3 = z", 95, 20),  # clipped at the window's end
    ]
    got = trace_reduce.reduce_events(events)
    assert got.busy_s == pytest.approx(20e-9)
    assert dict((n, s) for n, s in got.top_ops) == pytest.approx(
        {"a.1": 10e-9, "b.2": 8e-9, "c.3": 5e-9}
    )
    # gaps: [0,5) in bench.serve, [20,95) midpoint 57 in bench.serve
    assert dict(got.idle_gaps) == pytest.approx({"bench.serve": 80e-9})


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_events([("/device:TPU:0", "XLA Ops", "%a = x", 0, 1)])
