"""Write dataplane tests: the ragged ENCODE megakernel (kernel-vs-
oracle), honest PUT-path physics (billed encode launches, transfer
causality, write admission), stripe sealing for small objects, deletes,
and the end-to-end churn consistency audits under fault traces."""

import numpy as np
import pytest

from repro.coding import rs
from repro.coding.gf256 import np_matmul
from repro.core.product_code import CoreCode
from repro.gateway import (
    GatewayConfig,
    ObjectGateway,
    StripeSealer,
    WorkloadConfig,
)
from repro.gateway.workload import Request
from repro.kernels import ops
from repro.scenario.engine import (
    ScenarioResult,
    deterministic_fingerprint,
)
from repro.scenario.trace import (
    CorruptionEvent,
    ScenarioTrace,
    rack_failure,
    scenario_requests,
)
from repro.storage.netmodel import ClusterProfile

from repro.kernels.ragged_decode import CHUNK_SMALL
from repro.kernels.gf256_matmul import expand_coeff_bitplanes


def _gateway(code, num_nodes=60, q=2048, num_objects=12, **cfg_kw):
    cfg_kw.setdefault("interpret", True)
    gw = ObjectGateway(
        code, ClusterProfile.network_critical(), num_nodes, GatewayConfig(**cfg_kw)
    )
    rng = np.random.default_rng(9)
    gw.load_objects(rng.integers(0, 256, (num_objects, code.k, q), dtype=np.uint8))
    return gw


# ---------------------------------------------------------------------------
# kernel level: the ragged ENCODE entries match host oracles
# ---------------------------------------------------------------------------

def test_ragged_gf256_encode_matches_parity_oracle():
    n, k, tn = 9, 6, 256
    rng = np.random.default_rng(3)
    pmat = rs.parity_matrix(n, k)  # (n - k, k)
    c = CHUNK_SMALL
    data = rng.integers(0, 256, (c, k, tn), dtype=np.uint8)
    # one tile per op; a single coefficient row per tile (the coalescer
    # splits multi-target EH ops into one tile per parity column)
    mc = np.stack(
        [expand_coeff_bitplanes(pmat[i % (n - k)][None, :]) [0] for i in range(c)]
    )
    out = np.asarray(
        ops.gf256_ragged_encode(
            mc.transpose(1, 0, 2), data.transpose(1, 0, 2), interpret=True
        )
    )
    for i in range(c):
        want = np_matmul(pmat[i % (n - k)][None, :], data[i])[0]
        assert np.array_equal(out[i], want)


def test_ragged_xor_encode_matches_fold_oracle():
    tn = 128
    rng = np.random.default_rng(4)
    c = CHUNK_SMALL
    kk = 5  # stored parity + two (old, new) delta pairs
    data = rng.integers(0, 256, (c, kk, tn), dtype=np.uint8)
    out = np.asarray(ops.xor_ragged_encode(data.transpose(1, 0, 2), interpret=True))
    for i in range(c):
        want = data[i][0].copy()
        for r in range(1, kk):
            want ^= data[i][r]
        assert np.array_equal(out[i], want)


# ---------------------------------------------------------------------------
# sealer unit behavior
# ---------------------------------------------------------------------------

def test_sealer_extents_never_span_rows_and_flush_pads():
    s = StripeSealer(k=2, q=64)  # 128-byte rows
    assert s.append(("a",), np.arange(100, dtype=np.uint8), "t") == []
    # 100 + 60 > 128: the open row seals EARLY (zero-padded tail) and
    # the new extent starts at offset 0 of the next row
    sealed = s.append(("b",), np.full(60, 7, np.uint8), "t")
    assert len(sealed) == 1
    seq, row, exts = sealed[0]
    assert seq == 0 and row.shape == (2, 64)
    assert [e.small_id for e in exts] == [("a",)]
    assert np.all(row.reshape(-1)[100:] == 0)  # zero-padded tail
    assert s.pending_extents == 1 and s.pending_bytes == 60
    (seq2, row2, exts2) = s.flush()[0]
    assert seq2 == 1 and exts2[0].offset == 0 and exts2[0].length == 60
    with pytest.raises(ValueError):
        s.append(("c",), np.zeros(129, np.uint8), "t")  # > one row


# ---------------------------------------------------------------------------
# PUT-path physics: billed encode, transfer causality, admission
# ---------------------------------------------------------------------------

def test_put_latency_includes_billed_encode_launches():
    code = CoreCode(9, 6, 3)
    enc = 0.004
    gw = _gateway(code, encode_cost=enc, decode_cost=0.002)
    reqs = [Request(time=0.001 * (i + 1), object_id=i % 6, kind="put")
            for i in range(6)]
    rep = gw.serve(reqs)
    puts = [r for r in rep.records if r.kind == "put"]
    assert len(puts) == 6
    # transfers may not start before the EH launch lands, so every PUT
    # pays at least one modeled encode launch of sim time
    assert all(r.latency is not None and r.latency > enc for r in puts)
    assert gw.coalescer.stats.encode_calls > 0
    assert rep.metrics.gauge("encode_launches").value > 0


def test_put_encode_rides_the_shared_engine_pool():
    code = CoreCode(9, 6, 3)
    gw = _gateway(code, encode_cost=0.05, decode_cost=0.002, num_engines=1)
    free0 = list(gw._pool.free)
    rep = gw.serve([Request(time=0.001, object_id=0, kind="put")])
    assert rep.records[0].latency > 0.05
    # the pool's timeline advanced: encode occupied a real engine slot
    assert max(gw._pool.free) > max(free0)


def test_put_admission_rejects_and_counts_per_tenant():
    code = CoreCode(9, 6, 3)
    gw = _gateway(
        code,
        decode_cost=0.002,
        admission="reject",
        tenant_slo_p99={"foreground": 1e-6},  # everything busts it
    )
    reqs = [Request(time=0.001 * (i + 1), object_id=i % 6, kind="put")
            for i in range(4)]
    rep = gw.serve(reqs)
    assert rep.put_rejections.get("foreground") == 4
    assert all(r.rejected and r.latency is None for r in rep.records)
    assert rep.metrics.counter("rejected_requests", tenant="foreground").value == 4


def test_write_pressure_feeds_get_admission_estimate():
    code = CoreCode(9, 6, 3)
    gw = _gateway(code, decode_cost=0.002)
    gid, row = gw._objects[0]
    plan = gw.planner.plan(gid, row, at=0.0)
    base = gw._estimate_service_time(plan, 0.0, "foreground")
    gw._put_inflight["foreground"] = [(5.0, 1e7)]  # committed write bytes
    loaded = gw._estimate_service_time(plan, 0.0, "foreground")
    assert loaded > base


# ---------------------------------------------------------------------------
# deletes
# ---------------------------------------------------------------------------

def test_delete_tombstones_and_put_resurrects():
    code = CoreCode(9, 6, 3)
    gw = _gateway(code, decode_cost=0.002)
    rep = gw.serve(
        [
            Request(time=0.001, object_id=0, kind="delete"),
            Request(time=0.002, object_id=0, kind="get"),
            Request(time=0.003, object_id=0, kind="put"),
            Request(time=0.010, object_id=0, kind="get"),
            Request(time=0.011, object_id=0, kind="delete"),
            Request(time=0.012, object_id=0, kind="delete"),  # double delete
        ]
    )
    by = {}
    for r in rep.records:
        by.setdefault(r.kind, []).append(r)
    assert by["delete"][0].latency == 0.0
    assert by["delete"][1].latency == 0.0
    assert by["delete"][2].latency is None  # already tombstoned
    assert by["get"][0].latency is None  # deleted => not found
    assert by["get"][1].latency is not None  # resurrected by the PUT
    assert gw.audit_parity()["stale_blocks"] == 0


# ---------------------------------------------------------------------------
# the ragged write path stores exactly the numpy encode
# ---------------------------------------------------------------------------

def test_sync_and_ragged_write_paths_store_identical_bytes():
    """After full overwrites and sealed small PUTs through the ragged
    ENCODE windows, every stored CORE group equals the plain numpy
    encode of its data blocks: each row's RS parity is
    ``np_matmul(rs.parity_matrix, data)`` and the last row is the XOR
    of the others. Overwritten objects store the acknowledged bytes."""
    code = CoreCode(9, 6, 3)
    reqs = []
    t = 0.001
    for i in range(8):
        reqs.append(Request(time=t, object_id=i % 5, kind="put"))
        t += 0.0005
    for i in range(6):
        reqs.append(Request(time=t, object_id=200 + i, kind="put", nbytes=4000))
        t += 0.0005
    gw = _gateway(code, decode_cost=0.002, batch_window=0.01)
    gw.serve(list(reqs))
    gw.seal_flush(t)
    assert gw.audit_parity()["stale_blocks"] == 0
    assert gw.audit_sealed_stripes()["extents_wrong"] == 0
    assert gw.coalescer.stats.encode_calls > 0
    p = rs.parity_matrix(code.n, code.k)
    groups: dict[str, dict] = {}
    for (gid, row, col), blk in gw.store.blocks.items():
        groups.setdefault(gid, {})[(row, col)] = blk
    assert any(gid.startswith("w") for gid in groups)  # sealed rows too
    for gid, blocks in groups.items():
        assert len(blocks) == code.rows * code.n, gid
        m = np.stack(
            [
                np.stack([blocks[(r, c)] for c in range(code.n)])
                for r in range(code.rows)
            ]
        )  # (t + 1, n, q)
        for r in range(code.t):
            np.testing.assert_array_equal(
                m[r, code.k :], np_matmul(p, m[r, : code.k]), err_msg=gid
            )
        np.testing.assert_array_equal(
            m[code.t], np.bitwise_xor.reduce(m[: code.t], axis=0), err_msg=gid
        )
    for oid in range(5):
        gid, row = gw._objects[oid]
        got = np.stack([groups[gid][(row, c)] for c in range(code.k)])
        np.testing.assert_array_equal(got, gw._expected[oid])


# ---------------------------------------------------------------------------
# sealed stripes decode through degraded paths
# ---------------------------------------------------------------------------

def test_sealed_small_puts_survive_node_failure_degraded():
    code = CoreCode(9, 6, 3)
    gw = _gateway(code, decode_cost=0.002, batch_window=0.01)
    t = 0.001
    reqs = []
    for i in range(40):  # enough small puts to seal several full rows
        reqs.append(Request(time=t, object_id=1000 + i, kind="put", nbytes=3000))
        t += 0.0004
    gw.serve(reqs)
    gw.seal_flush(t)
    assert gw._seal_group_seq >= 1
    clean = gw.audit_sealed_stripes()
    assert clean["extents_checked"] == 40 and clean["extents_wrong"] == 0
    # knock out a node holding a sealed data block: the audit must now
    # route those rows through a DEGRADED decode and still match digests
    victim = gw.store.node_of(("w0", 0, 0))
    gw.store.fail_nodes([victim])
    after = gw.audit_sealed_stripes()
    assert after["rows_degraded"] >= 1
    assert after["extents_wrong"] == 0 and after["rows_unreadable"] == 0


# ---------------------------------------------------------------------------
# churn consistency: faulted trace vs clean oracle + replay identity
# ---------------------------------------------------------------------------

def _churn_setup(code):
    num_nodes = 20
    trace = ScenarioTrace(num_nodes=num_nodes, nodes_per_rack=code.n - code.k)
    trace = rack_failure(trace, 0.05, rack=1, downtime=0.6)
    trace = ScenarioTrace(
        num_nodes=num_nodes,
        nodes_per_rack=code.n - code.k,
        events=tuple(
            sorted(
                list(trace.events)
                + [CorruptionEvent(time=0.12, node=14, count=2)],
                key=lambda e: e.time,
            )
        ),
        surges=trace.surges,
    )
    wl = WorkloadConfig(
        num_objects=24,
        num_requests=160,
        arrival_rate=300.0,
        zipf_s=0.6,
        put_fraction=0.35,
        delete_fraction=0.05,
        small_put_fraction=0.3,
        small_put_bytes=3000,
        seed=11,
    )
    kwargs = dict(
        batch_window=0.01,
        decode_cost=0.002,
        repair_on_failure=True,
        repair_delay=0.05,
        record_payloads=True,
        interpret=True,
    )
    return trace, wl, kwargs


def _run_churn(code, trace, wl, kwargs, faulted=True):
    gw = ObjectGateway(
        code,
        ClusterProfile.network_critical(),
        trace.num_nodes,
        GatewayConfig(**kwargs),
    )
    rng = np.random.default_rng(9)
    gw.load_objects(
        rng.integers(0, 256, (wl.num_objects, code.k, 2048), dtype=np.uint8)
    )
    reqs = scenario_requests(wl, trace)
    events = trace.cluster_events() if faulted else []
    report = gw.serve(reqs, events)
    gw.seal_flush(reqs[-1].time + 1.0)
    return gw, ScenarioResult(
        report=report, durability=gw.audit_durability(), trace=trace
    )


def test_churn_consistency_audit_under_within_tolerance_faults():
    code = CoreCode(9, 6, 3)
    trace, wl, kwargs = _churn_setup(code)
    gw, faulted = _run_churn(code, trace, wl, kwargs, faulted=True)
    _, clean = _run_churn(code, trace, wl, kwargs, faulted=False)

    # the trace stays within tolerance: nothing provably lost
    assert faulted.durability["blocks_lost"] == 0

    # every GET that completed in BOTH runs returned byte-identical
    # payloads (faulted reads go through degraded decode paths)
    def digests(res):
        return {
            (round(r.time, 9), r.object_id): r.payload_digest
            for r in res.report.records
            if r.kind == "get" and r.latency is not None
        }
    dx, dc = digests(faulted), digests(clean)
    shared = set(dx) & set(dc)
    assert shared, "no comparable GETs between faulted and clean runs"
    assert all(dx[key] == dc[key] for key in shared)

    # vertical parity never went stale through the whole churn trace,
    # and every sealed extent decodes byte-identically
    parity = gw.audit_parity()
    assert parity["stale_blocks"] == 0
    sealed = gw.audit_sealed_stripes()
    assert sealed["extents_wrong"] == 0 and sealed["extents_pending"] == 0

    # replay identity: modeled costs make the faulted run bit-for-bit
    # reproducible
    _, faulted2 = _run_churn(code, trace, wl, kwargs, faulted=True)
    assert deterministic_fingerprint(faulted) == deterministic_fingerprint(
        faulted2
    )


def test_encode_jit_signatures_stay_bounded_per_kind():
    code = CoreCode(9, 6, 3)
    gw = _gateway(code, decode_cost=0.002, batch_window=0.01)
    t = 0.001
    reqs = []
    for i in range(30):  # mixed window sizes: 1-PUT and many-PUT batches
        reqs.append(Request(time=t, object_id=i % 12, kind="put"))
        t += 0.0003 if i % 5 else 0.05
    gw.serve(reqs)
    by_kind = gw.coalescer.jit_entries_by_kind()
    assert by_kind.get("EH", 0) >= 1
    assert all(v <= 2 for k, v in by_kind.items() if k in ("EH", "EV")), by_kind
