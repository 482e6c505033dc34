"""Structural gates of the paper's claims, live at small block sizes.

Each test holds a property that no timing can move: every GET is served
and verified, a degraded read fetches exactly the Table-1 source count
(t per CORE vertical, k per horizontal, a local group for LRC), CORE's
repair traffic is at most 0.55x RS's under one shared fault trace, a
within-tolerance trace loses no block, the same-column double-failure
blend lands strictly between its two endpoints, and the integrity plane
repairs every corruption it detects. Decode billing is modeled
(``decode_cost``), so every outcome here is wall-clock-free; payloads
still run on the ragged kernels, and every GET is checked against
ground truth (``verify``, the default)."""

from __future__ import annotations

import numpy as np
import pytest

from repro.core.product_code import CoreCode
from repro.gateway import (
    CorruptionEvent,
    FailureEvent,
    GatewayConfig,
    ObjectGateway,
    WorkloadConfig,
    generate_requests,
)
from repro.scenario import ScenarioConfig, generate_scenario, run_scenario
from repro.storage.netmodel import ClusterProfile

CODE = CoreCode(9, 6, 3)  # even k, n >= k + 2: valid for every family
Q = 1024  # block bytes
NUM_NODES = 60


def _gateway(family: str, num_objects: int, seed: int, **cfg_kw) -> ObjectGateway:
    cfg = GatewayConfig(
        code_family=family, batch_window=0.01, decode_cost=0.002, **cfg_kw
    )
    gw = ObjectGateway(CODE, ClusterProfile.network_critical(), NUM_NODES, cfg)
    rng = np.random.default_rng(seed)
    gw.load_objects(rng.integers(0, 256, (num_objects, CODE.k, Q), dtype=np.uint8))
    return gw


def _all_served(report) -> None:
    """Every request completed, and every completed GET was verified
    against ground truth (no wrong byte was served)."""
    assert len(report.completed) == len(report.records)
    gets = sum(1 for r in report.completed if r.kind == "get")
    assert report.metrics.counter_total("verified_gets") == gets


# ---------------------------------------------------------------------------
# degraded reads: every GET served, Table-1 source counts
# ---------------------------------------------------------------------------

# f node crashes per family, up to the family's tolerance (HDFS-Xorbas
# built on a (9, 6) row survives one arbitrary loss)
READ_CASES = [
    (fam, f)
    for fam, tol in (("core", 2), ("rs", 2), ("lrc", 2), ("xorbas", 1))
    for f in range(tol + 1)
]


@pytest.mark.parametrize("family,failures", READ_CASES)
def test_degraded_reads_serve_all_and_fetch_table1_sources(family, failures):
    """Gates ``all_done``, ``clean`` and ``vert_ok``: with f nodes down,
    every GET is served; GETs are degraded only when f > 0; a local
    (XOR) rebuild reads exactly the family's single-repair cost (t for
    CORE, the local group for LRC and Xorbas) and a row decode exactly
    k sources."""
    gw = _gateway(family, num_objects=12, seed=failures)
    # nodes holding data blocks of objects 0 and 1, in distinct columns
    victims = [
        gw.store.node_of((*gw._objects[obj], col))
        for obj, col in ((0, 0), (1, 2))[:failures]
    ]
    events = [FailureEvent(time=0.005, node=n) for n in victims]
    reqs = generate_requests(
        WorkloadConfig(
            num_objects=12, num_requests=90, arrival_rate=1500.0, seed=failures
        )
    )
    report = gw.serve(reqs, events)
    _all_served(report)
    assert bool(report.degraded_gets) == (failures > 0)
    st = gw.coalescer.stats
    if failures == 0:
        assert st.decode_ops == 0
        return
    local_cost = {gw.family.single_repair_cost(c) for c in range(CODE.k)}
    assert len(local_cost) == 1  # every data column costs the same
    if family == "rs":
        assert set(st.ops_by_kind) == {"H"}  # RS has no local rebuild
    else:
        assert st.ops_by_kind.get("V", 0) > 0
        assert st.sources_per_op("V") == local_cost.pop()
    if "H" in st.ops_by_kind:
        assert st.sources_per_op("H") == CODE.k


@pytest.mark.parametrize("row", range(CODE.t))
def test_broken_column_forces_k_source_horizontal_decode(row):
    """Gate ``horiz_ok``: with column 0 of a CORE group broken in every
    row, the vertical path is impossible and each degraded GET of that
    column's object decodes its row from exactly k sources."""
    gw = _gateway("core", num_objects=CODE.t, seed=11)
    for r in range(CODE.rows):
        gw.store.drop_block(("g0", r, 0))
    oid = next(o for o, (gid, r) in gw._objects.items() if (gid, r) == ("g0", row))
    reqs = [
        r for r in generate_requests(
            WorkloadConfig(num_objects=CODE.t, num_requests=30,
                           arrival_rate=1500.0, seed=row)
        )
        if r.object_id == oid
    ]
    assert reqs
    report = gw.serve(reqs, [])
    _all_served(report)
    assert len(report.degraded_gets) == len(reqs)
    st = gw.coalescer.stats
    assert set(st.ops_by_kind) == {"H"}
    assert st.sources_per_op("H") == CODE.k
    assert report.reconstruction_blocks_per_degraded_get == CODE.k


# ---------------------------------------------------------------------------
# repair traffic and durability under a shared Weibull fault trace
# ---------------------------------------------------------------------------

def _weibull_trace(max_down: int, seed: int):
    """Bursty Weibull(0.7) crash inter-arrivals (1309.0186): transient
    crashes for degraded reads plus permanent capacity losses for
    repair traffic, never more than ``max_down`` nodes down at once."""
    return generate_scenario(
        ScenarioConfig(
            duration=0.4,
            num_nodes=NUM_NODES,
            nodes_per_rack=3,
            max_concurrent_failures=max_down,
            crash_rate=20.0,
            mean_downtime=0.08,
            transient_fraction=0.6,
            interarrival="weibull",
            interarrival_shape=0.7,
            seed=seed,
        )
    )


@pytest.fixture(scope="module")
def faulted_run():
    """``faulted_run(family, max_down, seed)``: one family through the
    shared fault trace with repair on — the gateway, its scenario
    result, and the trace's fault-event count — run once per module."""
    runs = {}

    def run(family: str, max_down: int, seed: int):
        key = (family, max_down, seed)
        if key not in runs:
            trace = _weibull_trace(max_down, seed)
            gw = _gateway(
                family, num_objects=24, seed=seed,
                repair_on_failure=True, repair_delay=0.02,
            )
            res = run_scenario(
                gw, trace,
                WorkloadConfig(num_objects=24, num_requests=150,
                               arrival_rate=400.0, seed=seed),
            )
            runs[key] = (gw, res, len(trace.fault_events()))
        return runs[key]

    return run


def _fetch_per_repaired(run) -> float:
    """Repair source blocks fetched per repaired block over a run."""
    reports = run[1].report.repair_reports
    repaired = sum(r.blocks_repaired for r in reports)
    assert repaired > 0
    return sum(r.blocks_fetched for r in reports) / repaired


@pytest.mark.parametrize("family,bound", [("core", 0.55), ("lrc", 1.0), ("xorbas", 1.0)])
def test_repair_traffic_against_rs_under_one_fault_trace(faulted_run, family, bound):
    """Gates ``ratio_ok`` and ``lrc_ok``: on single-node failures (the
    paper's 50 %-less-repair-traffic regime), CORE fetches at most 0.55x
    RS's source blocks per repaired block, and the LRC families fetch
    strictly fewer than RS, under the same fault trace with nothing
    lost."""
    runs = [faulted_run(fam, 1, 29) for fam in (family, "rs")]
    ratio = _fetch_per_repaired(runs[0]) / _fetch_per_repaired(runs[1])
    assert 0 < ratio < 1.0
    assert ratio <= bound
    for _gw, res, fault_events in runs:
        assert fault_events > 0
        assert res.blocks_lost == 0


@pytest.mark.parametrize(
    "family,max_down", [("core", 2), ("rs", 2), ("lrc", 2), ("xorbas", 1)]
)
def test_within_tolerance_trace_loses_nothing(faulted_run, family, max_down):
    """Gate ``dur_ok``: a Weibull fault trace bounded at the family's
    tolerance loses no block and leaves no object unreadable, and every
    request is served with verified bytes."""
    gw, res, fault_events = faulted_run(family, max_down, 23)
    assert fault_events > 0
    assert res.trace.max_concurrent_down() <= max_down <= gw.family.tolerance
    assert res.blocks_lost == 0
    assert res.durability["unreadable_objects"] == 0
    _all_served(res.report)


# ---------------------------------------------------------------------------
# double failures: the paper's 15 % claim, as a traffic blend
# ---------------------------------------------------------------------------

def _double_failure_traffic(family: str) -> tuple[float, ObjectGateway]:
    """20 CORE groups each take one erase incident: 3 of 20 (15 %) lose
    two data blocks of the SAME column (both rows must decode
    horizontally at k), the rest one block (vertical at t). The RS run
    erases the same objects' blocks. Returns source blocks read per
    degraded GET over one GET trace, repair off."""
    t = CODE.t
    n_groups = 20
    gw = _gateway(family, num_objects=n_groups * t, seed=43)
    events = []
    for g in range(n_groups):
        col = g % CODE.k
        victims = [(0, col), (1, col)] if g % 7 == 3 else [(g % t, col)]
        for row, c in victims:
            key = (f"g{g}", row, c) if family == "core" else (f"g{g * t + row}", 0, c)
            events.append(
                CorruptionEvent(
                    time=1e-4, node=gw.store.node_of(key), blocks=(key,),
                    mode="erase",
                )
            )
    reqs = generate_requests(
        WorkloadConfig(num_objects=n_groups * t, num_requests=150,
                       arrival_rate=500.0, seed=43)
    )
    report = gw.serve(reqs, events)
    _all_served(report)
    assert report.degraded_gets
    return report.reconstruction_blocks_per_degraded_get, gw


def test_same_column_double_failures_blend_between_endpoints():
    """Gate ``df_ok``: at 15 % same-column doubles, CORE's degraded-read
    traffic relative to RS lands strictly between the all-vertical
    endpoint t/k and the all-horizontal endpoint 1.0; CORE's verticals
    read exactly t and its horizontals exactly k, and RS always reads k."""
    assert sum(1 for g in range(20) if g % 7 == 3) == 3  # 15 % of groups
    core, core_gw = _double_failure_traffic("core")
    rs, _ = _double_failure_traffic("rs")
    assert rs == CODE.k
    assert CODE.t / CODE.k < core / rs < 1.0
    st = core_gw.coalescer.stats
    assert st.sources_per_op("V") == CODE.t
    assert st.sources_per_op("H") == CODE.k


# ---------------------------------------------------------------------------
# integrity plane: corruption detected, repaired, never served
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["core", "rs"])
def test_corruption_detected_by_read_and_scrub_and_repaired(family):
    """Gate ``integ_ok``: under silent corruption, fail-slow nodes and
    crashes bounded at the code's tolerance, both detectors fire (the
    read path's digest check and the background scrubber), every
    detected block is repaired by the end of the run, and no GET returns
    a wrong byte."""
    trace = generate_scenario(
        ScenarioConfig(
            duration=0.4,
            num_nodes=NUM_NODES,
            nodes_per_rack=3,
            max_concurrent_failures=2,
            crash_rate=4.0,
            mean_downtime=0.08,
            transient_fraction=0.5,
            corruption_rate=12.0,
            corruption_blocks=2,
            slow_rate=5.0,
            slow_factor=0.2,
            mean_slow_time=0.1,
            seed=47,
        )
    )
    gw = _gateway(
        family, num_objects=24, seed=47,
        cache_bytes=8 * Q,
        repair_on_failure=True,
        repair_delay=0.03,
        # paced so the read path wins some detection races too
        scrub_interval=0.1,
        scrub_blocks_per_run=48,
    )
    res = run_scenario(
        gw, trace,
        WorkloadConfig(num_objects=24, num_requests=150, arrival_rate=400.0,
                       seed=47),
    )
    m = res.report.metrics
    assert m.counter_total("corruption_detected", source="read") > 0
    assert m.counter_total("corruption_detected", source="scrub") > 0
    assert res.durability["missing_blocks"] == 0  # every detection repaired
    assert res.blocks_lost == 0
    _all_served(res.report)
