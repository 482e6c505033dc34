"""Gray-failure hardening tests: the corruption-as-erasure integrity
plane (per-block digests, read/scrub/write/repair detection, tombstone
+ quarantine + repair), fail-slow injection through the fabric model,
hedged degraded reads, and the within-tolerance property that silent
corruption plus fail-slow never serves a wrong byte.

The property test uses hypothesis when installed and a seeded
parametrize fallback otherwise (same idiom as tests/test_scenario.py).
"""

from __future__ import annotations

import importlib
import importlib.util
import sys
import threading
import tracemalloc
import zlib

import numpy as np
import pytest

from repro.core.product_code import CoreCode, CoreCodec
from repro.gateway import (
    CorruptionEvent,
    GatewayConfig,
    ObjectGateway,
    SlowNicEvent,
    SlowNodeEvent,
    WorkloadConfig,
)
from repro.gateway.planner import DegradedReadPlanner
from repro.gateway.workload import Request
from repro.scenario import (
    ScenarioConfig,
    ScenarioTrace,
    deterministic_fingerprint,
    flapping_slow,
    generate_scenario,
    run_scenario,
    trace_from_jsonable,
)
from repro.storage import blockstore
from repro.storage.blockstore import BlockStore
from repro.storage.netmodel import ClusterProfile, NetSimulator, Transfer
from repro.storage.repair import Scrubber

_HYP = importlib.util.find_spec("hypothesis") is not None


def make_group(code, store, group_id="g0", q=1024, seed=0):
    rng = np.random.default_rng(seed)
    objects = rng.integers(0, 256, size=(code.t, code.k, q), dtype=np.uint8)
    store.put_group(group_id, np.asarray(CoreCodec(code).encode(objects)))
    return objects


def _gateway(code, num_nodes=60, q=2048, num_objects=12, seed=9, **cfg_kw):
    gw = ObjectGateway(
        code, ClusterProfile.network_critical(), num_nodes, GatewayConfig(**cfg_kw)
    )
    rng = np.random.default_rng(seed)
    gw.load_objects(rng.integers(0, 256, (num_objects, code.k, q), dtype=np.uint8))
    return gw


# ---------------------------------------------------------------------------
# block store: digests, corruption modes, quarantine
# ---------------------------------------------------------------------------

def test_put_records_digest_and_verify_passes_when_clean():
    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=30)
    make_group(code, store)
    assert len(store.checksums) == len(store.blocks)
    for key in list(store.blocks):
        assert store.verify(key)
        assert store.checksum_ok(key, store.get(key)) is True


def test_crc32_bytes_counts_exactly_the_digested_bytes():
    """Every digest the store takes (PUT, verify, decode-output check,
    repair write-back) adds its block's bytes to ``crc32_bytes``, and
    nothing else does."""
    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=60)
    make_group(code, store, q=1024)
    blocks = code.rows * code.n
    assert store.crc32_bytes == blocks * 1024
    store.get(("g0", 0, 0))
    store.quarantine(("g0", 1, 1))
    assert store.crc32_bytes == blocks * 1024
    assert store.verify(("g0", 0, 0)) and store.verify(("g0", 1, 1))  # quarantined: no digest
    assert store.checksum_ok(("g0", 0, 1), np.zeros(1024, np.uint8)) is False
    assert store.checksum_ok(("gX", 0, 0), np.zeros(1024, np.uint8)) is None
    store.put_block(("g0", 1, 1), np.zeros(512, np.uint8))
    assert store.crc32_bytes == (blocks + 2) * 1024 + 512


CHUNK = blockstore.CRC32_CHUNK_BYTES
# around the serial form's edges, the split path's threshold (two chunks)
# and a split with an odd tail
DIGEST_SIZES = [0, 1, CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK - 1, 2 * CHUNK, 2 * CHUNK + 1,
                3 * CHUNK + 12345]


def _random_bytes(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed + n).integers(0, 256, n, dtype=np.uint8)


def _layout(raw: np.ndarray, layout: str):
    """The bytes of ``raw`` (those of ``want`` below) in ``layout``."""
    if layout == "uint8":
        return raw
    if layout == "uint32":
        return raw[: raw.size - raw.size % 4].view(np.uint32)
    if layout == "strided":
        wide = np.zeros(2 * raw.size, np.uint8)
        wide[::2] = raw
        return wide[::2]
    import jax.numpy as jnp

    return jnp.asarray(raw)


@pytest.mark.parametrize("layout", ["uint8", "uint32", "strided", "jax"])
@pytest.mark.parametrize("n", DIGEST_SIZES)
def test_digest_is_zlib_crc32_of_the_bytes(n, layout):
    raw = _random_bytes(n)
    data = _layout(raw, layout)
    want = zlib.crc32(np.asarray(data).tobytes())
    assert BlockStore(num_nodes=3).digest(data) == want


@pytest.mark.parametrize("len1,len2", [(0, 0), (5, 0), (0, 7), (1, 1), (4096, 12345), (70000, 3)])
def test_crc32_combine_joins_two_crc32s(len1, len2):
    a, b = _random_bytes(len1, 1).tobytes(), _random_bytes(len2, 2).tobytes()
    assert blockstore.crc32_combine(zlib.crc32(a), zlib.crc32(b), len2) == zlib.crc32(a + b)


@pytest.mark.parametrize("where", ["first", "middle", "last"])
def test_one_flipped_bit_in_any_chunk_fails_verify(where):
    n = 3 * CHUNK + 12345
    pos = {"first": 0, "middle": CHUNK + CHUNK // 2, "last": n - 1}[where]
    store = BlockStore(num_nodes=3)
    key = ("g0", 0, 0)
    store.put_block(key, _random_bytes(n), node=0)
    assert store.verify(key)
    flipped = store.blocks[key].copy()
    flipped[pos] ^= 0x10
    store.blocks[key] = flipped
    assert not store.verify(key)


def test_crc32_split_bytes_counts_the_split_path_only():
    store = BlockStore(num_nodes=3)
    small, big = _random_bytes(16 << 10), _random_bytes(2 * CHUNK)
    store.digest(small)
    assert (store.crc32_bytes, store.crc32_split_bytes) == (small.nbytes, 0)
    store.digest(big)
    store.digest(big[: 2 * CHUNK - 1])
    assert store.crc32_bytes == small.nbytes + 4 * CHUNK - 1
    assert store.crc32_split_bytes == (2 * CHUNK if blockstore.crc32_splits(2 * CHUNK) else 0)


def test_one_core_hosts_digest_serially(monkeypatch):
    monkeypatch.setattr(blockstore.os, "cpu_count", lambda: 1)
    store = BlockStore(num_nodes=3)
    big = _random_bytes(3 * CHUNK + 12345)
    assert store.digest(big) == zlib.crc32(big.tobytes())
    assert (store.crc32_bytes, store.crc32_split_bytes) == (big.nbytes, 0)


def test_concurrent_first_digests_share_one_pool(monkeypatch):
    """More callers than cores, with frequent thread switches, all at the
    pool's first use: one pool is made and every digest is right."""
    monkeypatch.setattr(blockstore, "_crc32_pool", None)
    monkeypatch.setattr(blockstore.os, "cpu_count", lambda: 8)
    blocks = [_random_bytes(2 * CHUNK + i, seed=i) for i in range(3)]
    want = [zlib.crc32(b) for b in blocks]
    got, pools = {}, set()

    def digest(i):
        got[i] = blockstore.crc32(blocks[i % 3])
        pools.add(id(blockstore._crc32_pool))

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=digest, args=(i,)) for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    try:
        assert not any(t.is_alive() for t in threads)
        assert got == {i: want[i % 3] for i in range(16)}
        assert len(pools) == 1
    finally:
        blockstore._crc32_pool.shutdown()


def test_digest_of_a_contiguous_block_copies_nothing():
    store = BlockStore(num_nodes=3)
    block = _random_bytes(32 << 20)
    store.digest(block)  # makes the pool and the combine operator before the count
    tracemalloc.start()
    try:
        store.digest(block)
        _now, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < CHUNK // 16


def test_corrupt_block_modes_break_verify_but_not_checksum():
    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=30)
    make_group(code, store)
    for mode, key in (("bitflip", ("g0", 0, 0)), ("torn", ("g0", 0, 1))):
        digest_before = store.checksums[key]
        assert store.corrupt_block(key, mode=mode)
        # silent damage: the stored digest stays STALE (that is the
        # fault model), so verify now fails
        assert store.checksums[key] == digest_before
        assert not store.verify(key)
        assert store.checksum_ok(key, store.get(key)) is False
    # erase is a hard loss, not silent damage
    assert store.corrupt_block(("g0", 0, 2), mode="erase")
    assert not store.available(("g0", 0, 2))
    # corrupting an absent block is a no-op
    assert not store.corrupt_block(("g0", 0, 2), mode="bitflip")


def test_corrupt_block_writes_a_new_array_not_in_place():
    """Cached copies handed out before the corruption event must stay
    clean — the event replaces the stored array, it does not mutate the
    one previous readers hold."""
    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=30)
    make_group(code, store)
    key = ("g0", 1, 3)
    held = store.get(key)
    snapshot = held.copy()
    assert store.corrupt_block(key, mode="bitflip")
    np.testing.assert_array_equal(held, snapshot)
    assert not np.array_equal(store.get(key), snapshot)


def test_quarantine_keeps_placement_and_digest_drop_block_delegates():
    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=30)
    make_group(code, store)
    key = ("g0", 2, 4)
    node = store.node_of(key)
    store.quarantine(key)
    assert not store.available(key)
    # placement + trusted digest survive: repair can verify its rebuild
    assert store.node_of(key) == node
    assert key in store.checksums
    # the legacy test hook is now a thin wrapper over the erase path
    other = ("g0", 2, 5)
    store.drop_block(other)
    assert not store.available(other)


def test_scrubber_walks_the_store_and_reports_mismatches():
    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=30)
    make_group(code, store)
    bad_key = ("g0", 0, 4)
    store.corrupt_block(bad_key, mode="torn")
    scrubber = Scrubber(store, blocks_per_run=8)
    found = []
    for _ in range(len(store.blocks) // 8 + 2):  # full cursor lap
        found.extend(scrubber.scan(8))
    assert bad_key in found


# ---------------------------------------------------------------------------
# fabric model: fail-slow rates
# ---------------------------------------------------------------------------

def test_set_node_rate_validation_and_restore():
    sim = NetSimulator(ClusterProfile.network_critical())
    with pytest.raises(ValueError):
        sim.set_node_rate(3, 0.0)
    with pytest.raises(ValueError):
        sim.set_node_rate(3, 1.5)
    with pytest.raises(ValueError):
        sim.set_node_rate(3, 0.5, direction="up")
    sim.set_node_rate(3, 0.25, direction="send")
    assert sim.node_rate(3, "send") == 0.25
    assert sim.node_rate(3, "recv") == 1.0
    sim.set_node_rate(3, 1.0, direction="both")  # restore drops the entry
    assert sim.node_rate(3, "send") == 1.0
    assert not sim._node_rate


def test_slow_sender_stretches_transfer_by_rate_factor():
    prof = ClusterProfile.network_critical()
    sim = NetSimulator(prof)
    nbytes = 1 << 20
    healthy = sim.transfer(Transfer(0, 1, nbytes))
    sim.set_node_rate(2, 0.1)
    slow = sim.transfer(Transfer(2, 3, nbytes))
    assert slow == pytest.approx(healthy * 10, rel=1e-6)


def test_slow_inbound_stream_does_not_block_the_receivers_nic():
    """The gray-failure scheduling invariant: a trickling transfer from
    a fail-slow sender occupies the receiver's port only for the bytes'
    own wire time (tail-anchored), so a later healthy fetch into the
    same receiver lands in the head hole instead of queueing behind the
    slow stream — this is what makes hedging winnable at all."""
    prof = ClusterProfile.network_critical()
    sim = NetSimulator(prof)
    nbytes = 1 << 20
    wire = nbytes / prof.node_bandwidth
    sim.set_node_rate(5, 0.05)
    slow_end = sim.transfer(Transfer(5, 1, nbytes))
    healthy_end = sim.transfer(Transfer(6, 1, nbytes))
    assert slow_end == pytest.approx(20 * wire, rel=1e-6)
    # the healthy transfer completes in its own wire time, not after the
    # slow stream drains
    assert healthy_end < 3 * wire
    assert healthy_end < slow_end / 4


# ---------------------------------------------------------------------------
# planner: hedge alternate paths
# ---------------------------------------------------------------------------

def test_recovery_ops_orders_vertical_then_horizontal():
    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=30)
    make_group(code, store)
    planner = DegradedReadPlanner(store, code)
    ops = planner.recovery_ops("g0", 0, 0)
    assert [op.kind for op in ops] == ["V", "H"]
    assert len(ops[0].sources) == code.rows - 1
    assert len(ops[1].sources) == code.k
    assert ops[0].targets == ops[1].targets == (0,)
    assert planner.recovery_op("g0", 0, 0) == ops[0]
    # break the column: only the RS row path remains
    store.drop_block(("g0", 1, 0))
    ops = planner.recovery_ops("g0", 0, 0)
    assert [op.kind for op in ops] == ["H"]
    # starve the row below k survivors: no recovery path at all
    for c in range(1, code.n - code.k + 1):
        store.drop_block(("g0", 0, c))
    assert planner.recovery_ops("g0", 0, 0) == ()
    assert planner.recovery_op("g0", 0, 0) is None


# ---------------------------------------------------------------------------
# end to end: read-path detection, tombstones, repair heal
# ---------------------------------------------------------------------------

def test_read_detects_silent_corruption_and_serves_correct_bytes():
    code = CoreCode(9, 6, 3)
    gw = _gateway(
        code, batch_window=0.01, cache_bytes=4 * 1024 * 1024,
        repair_on_failure=True, repair_delay=0.02, record_payloads=True,
    )
    gid, row = gw._objects[0]
    bad = (gid, row, 2)
    events = [CorruptionEvent(time=0.005, node=gw.store.node_of(bad),
                              blocks=(bad,), mode="bitflip")]
    reqs = [Request(time=0.01 + 0.02 * i, object_id=0) for i in range(3)]
    report = gw.serve(reqs, events)
    m = report.metrics
    # the first GET trips the digest check mid-fetch, replans degraded,
    # and still completes with the right bytes (serve verifies payloads
    # against ground truth and would raise otherwise)
    assert all(r.latency is not None for r in report.records)
    first = report.records[0]
    assert first.degraded and first.reconstruction_blocks > 0
    assert m.counter_total("corruption_detected", source="read") >= 1
    assert m.counter_total("verified_gets") == 3
    # detection reclassified the corruption as an erasure and repair
    # healed it before the run drained
    assert gw.store.verify(bad)
    assert gw.audit_durability()["missing_blocks"] == 0
    assert report.corruption_latency.count >= 1
    assert all(s >= 0.0 for s in report.corruption_latency)


def test_corrupt_then_repaired_block_sheds_its_tombstone():
    """Satellite: a corrupt block is tombstoned in the negative cache at
    detection; once repair rewrites it the tombstone must be purged so
    later reads go direct again instead of riding the TTL."""
    code = CoreCode(9, 6, 3)
    gw = _gateway(
        code, batch_window=0.01, cache_bytes=2 * 2048,  # tiny: the
        # corrupt block cannot hide as a positive cache hit
        repair_on_failure=True, repair_delay=0.02,
    )
    gid, row = gw._objects[0]
    bad = (gid, row, 1)
    events = [CorruptionEvent(time=0.005, node=gw.store.node_of(bad),
                              blocks=(bad,), mode="torn")]
    reqs = [Request(time=0.01, object_id=0)]
    reqs += [Request(time=0.5 + 0.01 * i, object_id=0) for i in range(2)]
    report = gw.serve(reqs, events)
    assert all(r.latency is not None for r in report.records)
    assert report.records[0].degraded
    assert gw.store.verify(bad)
    assert gw.cache.negative_entries == 0
    # the post-heal reads are clean direct reads
    assert not report.records[-1].degraded


def test_scrub_detects_latent_corruption_without_a_read():
    """Blocks nobody fetches still get caught: the background scrubber
    walks stored digests on the simulated clock and feeds the same
    corruption-as-erasure path, giving a bounded MTTD."""
    code = CoreCode(9, 6, 3)
    gw = _gateway(
        code, batch_window=0.01, repair_on_failure=True, repair_delay=0.02,
        scrub_interval=0.05, scrub_blocks_per_run=256,
    )
    gid, row = gw._objects[0]
    bad = (gid, row, 3)
    events = [CorruptionEvent(time=0.01, node=gw.store.node_of(bad),
                              blocks=(bad,), mode="bitflip")]
    # the request stream never touches object 0 — only scrub can see it
    reqs = [Request(time=0.02 * (i + 1), object_id=1 + (i % 3)) for i in range(25)]
    report = gw.serve(reqs, events)
    m = report.metrics
    assert m.counter_total("corruption_detected", source="scrub") >= 1
    assert m.counter_total("scrub_blocks") > 0
    assert report.corruption_latency.count >= 1
    mttd = max(report.corruption_latency)
    assert 0.0 <= mttd < 0.5  # bounded by the scan cadence, not the run
    assert gw.store.verify(bad)


def test_slow_events_drive_the_fabric_rate_and_restore():
    code = CoreCode(9, 6, 3)
    gw = _gateway(code, batch_window=0.01)
    events = [
        SlowNodeEvent(time=0.0, node=7, rate_factor=0.2),
        SlowNicEvent(time=0.0, node=8, rate_factor=0.5, direction="recv"),
        SlowNodeEvent(time=0.05, node=7, rate_factor=1.0),
    ]
    reqs = [Request(time=0.01, object_id=0), Request(time=0.1, object_id=1)]
    report = gw.serve(reqs, events)
    assert report.metrics.counter_total("slow_events") == 3
    assert gw.sim.node_rate(7, "send") == 1.0  # restored mid-run
    assert gw.sim.node_rate(8, "recv") == 0.5
    assert gw.sim.node_rate(8, "send") == 1.0


# ---------------------------------------------------------------------------
# hedged degraded reads
# ---------------------------------------------------------------------------

def _fail_slow_run(hedge: bool, budget: float = 1.0):
    code = CoreCode(9, 6, 3)
    gw = _gateway(
        code, batch_window=0.005, decode_cost=0.0005,
        hedge=hedge, hedge_budget=budget,
    )
    slow = gw.store.node_of((gw._objects[0][0], gw._objects[0][1], 0))
    events = [SlowNodeEvent(time=0.0, node=slow, rate_factor=0.05)]
    reqs = [Request(time=0.01 * i, object_id=i % 12) for i in range(120)]
    return gw, gw.serve(reqs, events)


def test_hedged_reads_beat_unhedged_p99_under_fail_slow():
    _, base = _fail_slow_run(hedge=False)
    _, hedged = _fail_slow_run(hedge=True)
    m = hedged.metrics
    assert m.counter_total("hedge_launched") > 0
    assert m.counter_total("hedge_wins") > 0
    assert all(r.latency is not None for r in hedged.records)
    assert hedged.latency_percentile(99) < base.latency_percentile(99)
    # hedge decodes must still produce verified bytes (serve checks
    # payloads against ground truth), and wins reroute the plan
    assert m.counter_total("verified_gets") == len(hedged.records)


def test_hedge_byte_budget_is_a_structural_cap():
    gw, report = _fail_slow_run(hedge=True, budget=0.05)
    m = report.metrics
    hedge_bytes = m.counter_total("hedge_bytes")
    primary_bytes = sum(gw._fetch_bytes.values())
    assert primary_bytes > 0
    # the ledger admits a hedge only while spent + cost fits under
    # budget x primary bytes, so the final ratio cannot exceed it
    assert hedge_bytes <= 0.05 * primary_bytes + 1e-9
    if m.counter_total("hedge_budget_denied"):
        assert hedge_bytes > 0 or m.counter_total("hedge_launched") == 0


def test_tiny_hedge_budget_denies_every_hedge():
    _, report = _fail_slow_run(hedge=True, budget=1e-6)
    m = report.metrics
    assert m.counter_total("hedge_launched") == 0
    assert m.counter_total("hedge_budget_denied") > 0
    assert m.counter_total("hedge_bytes") == 0
    assert all(r.latency is not None for r in report.records)


# ---------------------------------------------------------------------------
# trace schema: gray events round-trip + generator tolerance
# ---------------------------------------------------------------------------

def test_gray_events_roundtrip_through_json():
    trace = ScenarioTrace(
        num_nodes=12, nodes_per_rack=4,
        events=(
            CorruptionEvent(time=0.1, node=3, blocks=(("g0", 0, 1),),
                            mode="torn"),
            SlowNodeEvent(time=0.2, node=5, rate_factor=0.25),
            SlowNicEvent(time=0.3, node=7, rate_factor=0.5, direction="recv"),
            SlowNodeEvent(time=0.4, node=5, rate_factor=1.0),
        ),
    )
    trace = flapping_slow(trace, node=9, start=0.5, period=0.1, count=2,
                          rate_factor=0.1)
    again = trace_from_jsonable(trace.to_jsonable())
    assert again.cluster_events() == trace.cluster_events()
    # block keys survive as tuples (JSON lists must be re-tupled)
    evt = next(e for e in again.events if isinstance(e, CorruptionEvent))
    assert evt.blocks == (("g0", 0, 1),)


def test_generated_gray_traces_are_deterministic_and_bounded():
    cfg = ScenarioConfig(
        duration=1.0, num_nodes=60, nodes_per_rack=3,
        max_concurrent_failures=3, crash_rate=8.0, mean_downtime=0.05,
        corruption_rate=6.0, slow_rate=6.0, mean_slow_time=0.1, seed=4,
    )
    trace = generate_scenario(cfg)
    assert any(isinstance(e, CorruptionEvent) for e in trace.events)
    assert any(isinstance(e, SlowNodeEvent) for e in trace.events)
    assert trace.max_concurrent_down() <= 3
    assert generate_scenario(cfg).cluster_events() == trace.cluster_events()
    again = trace_from_jsonable(trace.to_jsonable())
    assert again.cluster_events() == trace.cluster_events()


# ---------------------------------------------------------------------------
# property: within-tolerance gray mixes never serve a wrong byte
# ---------------------------------------------------------------------------

def _gray_gateway(code):
    return _gateway(
        code, batch_window=0.01, cache_bytes=4 * 1024 * 1024,
        repair_on_failure=True, repair_delay=0.03, record_payloads=True,
        scrub_interval=0.1, decode_cost=0.002,
    )


def _assert_correct_under_gray_trace(seed: int) -> None:
    """Random crash + corruption + fail-slow mix bounded at n - k
    concurrently-affected nodes: every GET completes and returns the
    same payload digest as a clean run of the identical request stream
    (zero wrong bytes), and the faulty run is replay-deterministic."""
    code = CoreCode(9, 6, 3)
    cfg = ScenarioConfig(
        duration=0.5, num_nodes=60, nodes_per_rack=3,
        max_concurrent_failures=code.n - code.k, crash_rate=6.0,
        mean_downtime=0.08, transient_fraction=0.5,
        corruption_rate=8.0, corruption_blocks=2,
        slow_rate=6.0, slow_factor=0.2, mean_slow_time=0.1,
        seed=seed,
    )
    trace = generate_scenario(cfg)
    wl = WorkloadConfig(
        num_objects=12, num_requests=100, arrival_rate=300.0, seed=seed
    )
    faulty = run_scenario(_gray_gateway(code), trace, wl)
    clean = run_scenario(
        _gray_gateway(code),
        ScenarioTrace(num_nodes=60, nodes_per_rack=3),
        wl,
    )
    assert all(r.latency is not None for r in faulty.report.records)
    assert faulty.blocks_lost == 0
    assert faulty.durability["unreadable_objects"] == 0
    got = [(r.object_id, r.payload_digest) for r in faulty.report.records
           if r.kind == "get"]
    want = [(r.object_id, r.payload_digest) for r in clean.report.records
            if r.kind == "get"]
    assert got == want
    # discrete outcomes (digests included) replay bit-for-bit
    replay = run_scenario(_gray_gateway(code), trace, wl)
    assert deterministic_fingerprint(replay) == deterministic_fingerprint(faulty)


if _HYP:
    _hyp = importlib.import_module("hypothesis")
    _st = importlib.import_module("hypothesis.strategies")

    @_hyp.settings(max_examples=4, deadline=None)
    @_hyp.given(seed=_st.integers(min_value=0, max_value=2**16))
    def test_gray_property_within_tolerance(seed):
        _assert_correct_under_gray_trace(seed)
else:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_gray_property_within_tolerance(seed):
        _assert_correct_under_gray_trace(seed)
