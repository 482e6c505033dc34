"""Observability-plane tests (repro.obs): streaming-estimator accuracy
bounds, bounded-memory guarantees, span parenting/ordering invariants on
real gateway traces, sampling policies, the observation-only contract
(tracing on/off is byte-identical), critical-path additivity, and the
chrome-tracing exporter + validator.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from repro.core.product_code import CoreCode
from repro.gateway import (
    GatewayConfig,
    ObjectGateway,
    WorkloadConfig,
    generate_requests,
)
from repro.gateway.gateway import RECENT_CAP
from repro.gateway.workload import FailureEvent
from repro.obs import (
    NULL_TRACER,
    STAGES,
    BoundedLog,
    BoundedSamples,
    MetricsRegistry,
    P2Quantile,
    StreamHist,
    Tracer,
    critical_path,
    launch_amortization,
    stage_shares,
    to_chrome_trace,
    validate_chrome_trace,
    write_chrome_trace,
)
from repro.scenario import (
    correlated_surge_setup,
    deterministic_fingerprint,
    run_scenario,
)
from repro.storage.netmodel import ClusterProfile


# ---------------------------------------------------------------------------
# streaming estimators: accuracy vs exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dist", ["uniform", "lognormal", "exponential"])
@pytest.mark.parametrize("q", [0.5, 0.9, 0.99])
def test_p2_quantile_tracks_exact(dist, q):
    rng = np.random.default_rng(7)
    xs = {
        "uniform": rng.uniform(0.001, 1.0, 20000),
        "lognormal": rng.lognormal(-3.0, 1.0, 20000),
        "exponential": rng.exponential(0.05, 20000),
    }[dist]
    est = P2Quantile(q)
    for x in xs:
        est.observe(float(x))
    exact = float(np.quantile(xs, q))
    # P2 is approximate; on smooth unimodal streams it lands within a
    # modest relative band of the exact quantile
    assert est.count == len(xs)
    assert abs(est.value - exact) / exact < 0.15


def test_p2_quantile_exact_below_five_samples():
    est = P2Quantile(0.5)
    for x in (3.0, 1.0, 2.0):
        est.observe(x)
    assert est.value == 2.0  # exact median of {1,2,3}
    with pytest.raises(ValueError):
        P2Quantile(1.5)


@pytest.mark.parametrize("dist", ["uniform", "lognormal"])
def test_streamhist_quantile_relative_error_bound(dist):
    """Log-spaced bins bound RELATIVE quantile error by the bin growth
    factor (plus one bin of rank slack at the ends)."""
    rng = np.random.default_rng(11)
    xs = {
        "uniform": rng.uniform(0.001, 2.0, 20000),
        "lognormal": rng.lognormal(-2.0, 1.5, 20000),
    }[dist]
    h = StreamHist()
    for x in xs:
        h.observe(float(x))
    for q in (0.1, 0.5, 0.9, 0.99):
        exact = float(np.quantile(xs, q))
        got = h.quantile(q)
        # one bin of rank slack can shift the answer a neighbouring bin:
        # allow 2x the single-bin relative width
        assert abs(got - exact) / exact < 2 * (h.growth - 1.0), (q, got, exact)
    # exact streaming scalars ride alongside
    assert h.count == len(xs)
    assert h.min == float(xs.min()) and h.max == float(xs.max())
    assert h.quantile(0.0) == h.min and h.quantile(1.0) == h.max
    assert h.cdf(h.max) == 1.0
    assert h.cdf(h.min - 1e-12) == 0.0


def test_streamhist_merge_matches_union():
    rng = np.random.default_rng(3)
    a, b = rng.exponential(0.1, 5000), rng.exponential(0.3, 5000)
    ha, hb, hu = StreamHist(), StreamHist(), StreamHist()
    for x in a:
        ha.observe(float(x))
        hu.observe(float(x))
    for x in b:
        hb.observe(float(x))
        hu.observe(float(x))
    ha.merge(hb)
    assert ha.count == hu.count and ha.bins == hu.bins
    assert ha.quantile(0.9) == hu.quantile(0.9)


# ---------------------------------------------------------------------------
# bounded containers + registry: memory stays O(1) in samples
# ---------------------------------------------------------------------------

def test_bounded_samples_memory_and_exact_scalars():
    bs = BoundedSamples(cap=64)
    xs = np.random.default_rng(5).uniform(0.0, 10.0, 100_000)
    for x in xs:
        bs.append(float(x))
    assert len(bs) == 100_000  # len() = TOTAL observed, list-compatible
    assert bs.resident() == 64  # memory bounded by the cap
    assert list(bs) == [float(x) for x in xs[:64]]
    assert bs.mean == pytest.approx(float(xs.mean()))
    assert bs.max == float(xs.max()) and bs.min == float(xs.min())
    assert bool(bs) and not bool(BoundedSamples())


def test_bounded_log_keeps_tail():
    log = BoundedLog(cap=16)
    for i in range(1000):
        log.append((i, i * 2))
    assert len(log) == 1000
    assert log.resident() == 16
    assert list(log)[0] == (984, 1968) and list(log)[-1] == (999, 1998)


def test_metrics_registry_bounded_and_queryable():
    m = MetricsRegistry()
    for i in range(50_000):
        m.counter("requests", tenant="a").inc()
        m.histogram("latency", kind="get", tenant="a").observe(0.01)
        m.histogram("latency", kind="get", tenant="b").observe(0.5)
    assert m.counter_total("requests") == 50_000
    # resident memory is per-SERIES, never per-sample
    before = m.resident_samples()
    m.histogram("latency", kind="get", tenant="a").observe(0.01)
    assert m.resident_samples() == before
    merged = m.merged_histogram("latency", kind="get")
    assert merged is not None and merged.count == 100_001
    assert merged.quantile(0.25) == pytest.approx(0.01, rel=0.2)
    snap = m.snapshot()
    assert snap["counters"]["requests{tenant=a}"] == 50_000
    assert "latency{kind=get,tenant=a}" in snap["histograms"]


# ---------------------------------------------------------------------------
# tracer: sampling policies + bounded ring
# ---------------------------------------------------------------------------

def _one_trace(tr: Tracer, latency: float) -> int:
    tid = tr.begin_trace()
    tr.span("fetch", 0.0, latency / 2, tid, tid)
    tr.root_span("request", 0.0, latency, tid)
    tr.end_trace(tid, latency=latency)
    return tid


def test_tracer_sampling_policies():
    head = Tracer(sample="head:3")
    for _ in range(10):
        _one_trace(head, 0.01)
    assert head.traces_kept == 3 and head.traces_dropped == 7

    tail = Tracer(sample="tail:0.1")
    kept = [_one_trace(tail, lat) for lat in (0.01, 0.5, 0.02, 0.2)]
    assert tail.traces_kept == 2  # slow traces are never dropped
    assert set(tail.trace_ids()) == {kept[1], kept[3]}

    combo = Tracer(sample="head:1,tail:0.1")
    for lat in (0.01, 0.02, 0.5):
        _one_trace(combo, lat)
    assert combo.traces_kept == 2  # head keeps the first, tail the slow one

    with pytest.raises(ValueError):
        Tracer(sample="p50")
    with pytest.raises(ValueError):
        Tracer(sample="")


def test_tracer_ring_buffer_bounded():
    tr = Tracer(sample="always", capacity=100)
    for _ in range(200):
        _one_trace(tr, 0.01)
    assert tr.resident() <= 100
    assert tr.stats()["spans_resident"] <= 100


def test_tracer_drops_spans_outside_open_traces():
    tr = Tracer()
    tid = tr.begin_trace()
    tr.end_trace(tid, latency=0.0)
    assert tr.span("late", 0.0, 1.0, tid, tid) == 0  # closed: dropped
    assert tr.span("bogus", 0.0, 1.0, 999999) == 0  # never opened
    assert NULL_TRACER.begin_trace() == 0 and not NULL_TRACER.enabled


def test_tracer_replay_preserves_stream():
    # replay_into (the overhead bench's measured workload) must re-emit
    # the exact committed stream: same span count, names, intervals,
    # tracks and attrs, with parenting preserved per trace
    tr = Tracer()
    for lat in (0.01, 0.2):
        _one_trace(tr, lat)
    sink = Tracer(sample=tr.sample, capacity=tr.capacity)
    n = tr.replay_into(sink)
    assert n == len(tr.spans) == len(sink.spans)
    assert sink.traces_kept == tr.traces_kept
    strip = lambda spans: sorted(
        (s.name, s.start, s.end, s.track, tuple(sorted(s.attrs.items())))
        for s in spans
    )
    assert strip(sink.spans) == strip(tr.spans)
    roots = [s for s in sink.spans if s.span_id == s.trace_id]
    assert len(roots) == sink.traces_kept
    for s in sink.spans:
        if s.parent_id is not None and s.span_id != s.trace_id:
            assert s.parent_id == s.trace_id  # reparented onto new root


# ---------------------------------------------------------------------------
# gateway traces: parenting/ordering invariants + critical path
# ---------------------------------------------------------------------------

def _traced_gateway_run(**cfg_kw):
    code = CoreCode(9, 6, 3)
    cfg = GatewayConfig(
        batch_window=0.02,
        decode_cost=0.002,
        repair_on_failure=True,
        repair_delay=0.05,
        background_share=0.5,
        tracing=True,
        **cfg_kw,
    )
    gw = ObjectGateway(code, ClusterProfile.network_critical(), 60, cfg)
    rng = np.random.default_rng(9)
    gw.load_objects(rng.integers(0, 256, (12, code.k, 2048), dtype=np.uint8))
    reqs = generate_requests(
        WorkloadConfig(num_objects=12, num_requests=200, arrival_rate=500.0, seed=5)
    )
    victim = gw.store.node_of(("g0", 0, 0))
    report = gw.serve(reqs, [FailureEvent(time=0.02, node=victim)])
    return gw, report


def test_gateway_span_parenting_and_ordering():
    gw, report = _traced_gateway_run()
    tr = gw.tracer
    assert tr.traces_kept > 0
    request_roots = 0
    for tid in tr.trace_ids():
        spans = tr.trace(tid)
        by_id = {s.span_id: s for s in spans}
        roots = [s for s in spans if s.parent_id is None]
        assert len(roots) == 1  # exactly one root per trace
        root = roots[0]
        assert root.span_id == tid  # trace id doubles as the root span id
        if root.name == "request":
            request_roots += 1
        for s in spans:
            assert s.end >= s.start
            if s.parent_id is not None:
                parent = by_id[s.parent_id]
                # children nest within their parent on the sim clock
                assert parent.start <= s.start + 1e-9
                assert s.end <= parent.end + 1e-9
        # a decode's sources land before its launch barrier opens and
        # its engine time starts: fetch -> staging -> decode ordering
        for d in (s for s in spans if s.name == "decode"):
            assert d.attrs["op_ready"] <= d.attrs["ready"] + 1e-9
            assert d.attrs["ready"] <= d.start + 1e-9
        # every fetch ends no later than the request completes
        for f in (s for s in spans if s.name == "fetch"):
            assert f.end <= root.end + 1e-9
    assert request_roots == len(report.completed)


def test_gateway_critical_path_additive():
    gw, _ = _traced_gateway_run()
    tr = gw.tracer
    degraded_seen = 0
    for tid in tr.trace_ids():
        spans = tr.trace(tid)
        root = next((s for s in spans if s.name == "request"), None)
        if root is None:
            continue  # repair.run trace
        bd = critical_path(spans)
        assert bd is not None
        assert set(bd.stages) == set(STAGES)
        assert all(v >= 0.0 for v in bd.stages.values())
        # the six stages sum EXACTLY to the request's latency
        assert sum(bd.stages.values()) == pytest.approx(bd.latency, abs=1e-12)
        if root.attrs.get("degraded"):
            degraded_seen += 1
            assert bd.gated_by in ("decode", "fetch")
    assert degraded_seen > 0
    sh = stage_shares(tr)
    assert sh["traces"] > 0
    assert sum(sh["shares"].values()) == pytest.approx(1.0, abs=1e-9)
    amort = launch_amortization(tr)
    assert amort["launches"] > 0
    assert amort["ops_per_launch"] >= 1.0


def test_gateway_repair_trace_emitted():
    gw, report = _traced_gateway_run()
    assert report.repair_reports
    tr = gw.tracer
    names = {s.name for s in tr.spans}
    assert {"repair.run", "repair.fetch", "repair.group", "repair.heal"} <= names
    runs = [s for s in tr.spans if s.name == "repair.run"]
    for run in runs:
        children = [
            s for s in tr.trace(run.trace_id) if s.span_id != run.span_id
        ]
        assert children  # fetch/decode/heal ride inside the repair trace


def test_gateway_metrics_surface_jit_and_autotune():
    gw, report = _traced_gateway_run()
    snap = report.metrics.snapshot()
    assert "jit_retraces{}" in snap["gauges"]
    assert "jit_entries{}" in snap["gauges"]
    for key in ("autotune_memory_hits{}", "autotune_disk_hits{}", "autotune_sweeps{}"):
        assert key in snap["gauges"]
    assert "traces_kept{}" in snap["gauges"]


# ---------------------------------------------------------------------------
# observation-only contract: tracing cannot change the simulation
# ---------------------------------------------------------------------------

def _fingerprint_run(**extra_kw):
    code = CoreCode(9, 6, 3)
    setup = correlated_surge_setup(code, num_requests=120)
    cfg = GatewayConfig(
        record_payloads=True,
        **setup["gateway_kwargs"],
        **extra_kw,
    )
    gw = ObjectGateway(
        code, ClusterProfile.network_critical(), setup["num_nodes"], cfg
    )
    rng = np.random.default_rng(setup["seed"])
    gw.load_objects(
        rng.integers(
            0, 256, (setup["num_objects"], code.k, setup["block_bytes"]),
            dtype=np.uint8,
        )
    )
    return run_scenario(gw, setup["trace"], setup["workload"])


def test_tracing_disabled_is_byte_identical():
    """Tracing must be observation-only: the golden fingerprint (which
    covers per-request payload digests) is identical with tracing off,
    on, and on-with-sampling."""
    base = deterministic_fingerprint(_fingerprint_run())
    traced = deterministic_fingerprint(_fingerprint_run(tracing=True))
    sampled = deterministic_fingerprint(
        _fingerprint_run(tracing=True, trace_sample="head:5,tail:0.1")
    )
    assert base == traced == sampled


def test_streaming_mode_bounded_and_aggregates_agree():
    """record_requests=False keeps NO per-request records; aggregates
    fall back to the registry and stay close to the exact answers."""
    full = _fingerprint_run().report
    stream = _fingerprint_run(record_requests=False).report
    assert len(stream.records) == 0
    assert stream.resident_samples() <= full.resident_samples()
    assert stream.resident_samples() < 10_000  # bounded, not per-request
    exact_p99 = full.latency_percentile(99)
    sketch_p99 = stream.latency_percentile(99)
    assert sketch_p99 == pytest.approx(exact_p99, rel=0.25)
    assert stream.throughput == pytest.approx(full.throughput, rel=1e-6)
    # pacer inputs ride the bounded deque, capped
    assert len(stream.recent) <= RECENT_CAP


def test_long_trace_streaming_keeps_resident_memory_bounded():
    """A 2000-request run of the canonical scenario (10x its usual
    length) in streaming mode with tail-biased trace sampling keeps no
    per-request record; resident samples and spans stay within their
    caps however long the trace runs."""
    code = CoreCode(9, 6, 3)
    setup = correlated_surge_setup(code, num_requests=2000)
    cfg = GatewayConfig(
        repair_pacing=True,
        tracing=True,
        trace_sample=f"head:64,tail:{setup['slo']}",
        record_requests=False,
        **setup["gateway_kwargs"],
    )
    gw = ObjectGateway(
        code, ClusterProfile.network_critical(), setup["num_nodes"], cfg
    )
    rng = np.random.default_rng(setup["seed"])
    gw.load_objects(
        rng.integers(
            0, 256, (setup["num_objects"], code.k, setup["block_bytes"]),
            dtype=np.uint8,
        )
    )
    rep = run_scenario(gw, setup["trace"], setup["workload"]).report
    assert rep.metrics.counter_total("requests") >= 2000
    assert rep.metrics.counter_total("completed") == rep.metrics.counter_total(
        "requests"
    )
    assert len(rep.records) == 0
    assert rep.resident_samples() < 50_000
    assert gw.tracer.resident() <= cfg.trace_capacity


# ---------------------------------------------------------------------------
# chrome-tracing export + validation
# ---------------------------------------------------------------------------

def test_chrome_export_round_trip(tmp_path):
    gw, _ = _traced_gateway_run()
    path = tmp_path / "trace.json"
    doc = write_chrome_trace(str(path), gw.tracer.spans)
    assert validate_chrome_trace(doc) == len(doc["traceEvents"])
    reloaded = json.loads(path.read_text())
    assert validate_chrome_trace(reloaded) == len(doc["traceEvents"])
    # track layout: every track group renders as one named process
    groups = {
        ev["args"]["name"]
        for ev in reloaded["traceEvents"]
        if ev["ph"] == "M" and ev["name"] == "process_name"
    }
    assert {"tenant", "engine", "fabric", "repair"} <= groups
    # intervals are complete events with durations; instants are marked
    for ev in reloaded["traceEvents"]:
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
        elif ev["ph"] == "i":
            assert ev["s"] == "t"


def test_chrome_validator_rejects_malformed():
    ok = to_chrome_trace(
        [  # minimal valid doc built from a hand-rolled span
        ]
    )
    assert validate_chrome_trace(ok) == 0
    with pytest.raises(ValueError):
        validate_chrome_trace([])  # not an object
    with pytest.raises(ValueError):
        validate_chrome_trace({})  # no traceEvents
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "X"}]})  # missing fields
    with pytest.raises(ValueError):
        validate_chrome_trace(
            {"traceEvents": [{"name": "x", "ph": "Z", "pid": 1, "tid": 1}]}
        )
    with pytest.raises(ValueError):
        validate_chrome_trace(
            {"traceEvents": [{"name": "x", "ph": "X", "pid": 1, "tid": 1, "ts": -1}]}
        )
    with pytest.raises(ValueError):
        validate_chrome_trace(
            {"traceEvents": [{"name": "x", "ph": "X", "pid": 1, "tid": 1, "ts": 0}]}
        )  # X without dur


def test_every_wall_clock_span_is_in_the_table():
    """The program opens a wall-clock span only under a name of
    ``repro.obs.host.SPANS``, and every name there is opened somewhere."""
    import pathlib
    import re

    from repro.obs.host import SPANS

    src = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
    used = {
        m
        for path in src.rglob("*.py")
        for m in re.findall(r'(?<![.\w])span\(\s*"([^"]+)"', path.read_text())
    }
    assert used == set(SPANS)


def test_wall_clock_spans_record_only_under_a_trace(tmp_path):
    """A span is a profiler annotation: with no trace active it records
    nothing and returns what its body returns; under a trace it lands on
    the host line, nested in its parent, with its name as given."""
    import jax

    from repro.obs.host import span

    with span("gw.serve", object_id=1):
        assert 2 + 2 == 4
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with span("gw.serve"):
        with span("store.crc32", bytes=16384, key=("g0", 0, 1)):
            pass
    jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    got = {
        ev.name.split("#", 1)[0]: (ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in data.planes
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith(("gw.", "store."))
    }
    assert set(got) == {"gw.serve", "store.crc32"}
    assert got["gw.serve"][0] <= got["store.crc32"][0] <= got["store.crc32"][1]
    assert got["store.crc32"][1] <= got["gw.serve"][1]



def _xorbas_gateway() -> tuple[ObjectGateway, list]:
    """HDFS-Xorbas LRC(16, 10) rows: object 0 misses one data block (a
    local group rebuilds it), object 1 two of one group (a global
    decode rebuilds both), object 2 none."""
    gw = ObjectGateway(
        CoreCode(16, 10, 1),
        ClusterProfile.network_critical(),
        40,
        GatewayConfig(code_family="xorbas", record_payloads=True),
    )
    gw.load_objects(
        np.random.default_rng(4).integers(0, 256, (3, 10, 1024), dtype=np.uint8)
    )
    keys = [(*gw._objects[0], 3), (*gw._objects[1], 6), (*gw._objects[1], 8)]
    for key in keys:
        gw.store.drop_block(key)
    return gw, keys


def test_plan_kind_counters_count_a_scripted_trace():
    """The GET path's CoalescerStats and the repair path's RepairReport
    count each reconstructed block, and the source blocks its step read,
    under its plan kind."""
    from repro.gateway import Request

    gw, _keys = _xorbas_gateway()
    report = gw.serve(
        [Request(time=0.1 * (i + 1), object_id=i) for i in (0, 1, 2)], []
    )
    assert [r.degraded for r in report.records] == [True, True, False]
    st = gw.coalescer.stats
    assert st.rebuilt_by_plan == {"local": 1, "global": 2}
    assert st.read_by_plan == {"local": 5, "global": 10}
    local = gw.fixer.fix_group(gw._objects[0][0])
    assert (local.rebuilt_by_plan, local.read_by_plan) == ({"local": 1}, {"local": 5})
    both = gw.fixer.fix_group(gw._objects[1][0])
    assert (both.rebuilt_by_plan, both.read_by_plan) == ({"global": 2}, {"global": 10})
    assert both.blocks_repaired == 2 and both.blocks_fetched == 10


def test_decode_span_carries_its_plan_kind_only_under_a_trace(tmp_path):
    """``gw.decode`` holds its window's plan kinds as the attr ``plan``,
    recorded like every wall-clock span only while a trace is active:
    the local GET served before the trace leaves nothing in it."""
    import jax

    from repro.gateway import Request

    gw, _keys = _xorbas_gateway()
    gw.serve([Request(time=0.1, object_id=0)], [])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    gw.serve([Request(time=0.2, object_id=1)], [])
    jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    plans = [
        dict(ev.stats).get("plan")
        for plane in data.planes
        for line in plane.lines
        for ev in line.events
        if ev.name == "gw.decode"
    ]
    assert plans == ["global"]
