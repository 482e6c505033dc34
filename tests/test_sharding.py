"""Sharded multi-gateway scale-out tests: consistent-hash directory
properties (coverage, balance, minimal movement on shard death),
cross-shard cache coherence through the metadata plane, routing
identity (sharding changes WHERE a request decodes, never WHAT it
returns), deterministic replay, whole-shard-death failover with zero
loss, and the per-tile decode billing model's config validation."""

import numpy as np
import pytest

from repro.core import CoreCode
from repro.gateway import (
    GatewayConfig,
    LRUBlockCache,
    MetadataPlane,
    ShardDirectory,
    ShardedGateway,
    ShardFailEvent,
    TenantProfile,
    generate_tenant_requests,
    plan_failures,
    tenant_slo_map,
    tenant_weight_map,
)
from repro.storage import ClusterProfile
from repro.storage.netmodel import base_tenant, shard_tenant

CODE = CoreCode(9, 6, 3)
NUM_NODES = 60


def _mk_sharded(num_shards, num_objects=60, q=4096, seed=5, **cfg_kw):
    """A small decode-bound sharded cluster + matching request trace."""
    tenants = [
        TenantProfile("gold", arrival_rate=3000.0, weight=1.0, zipf_s=0.4)
    ]
    cfg = GatewayConfig(
        batch_window=0.005,
        decode_cost_per_tile=0.002,
        record_payloads=True,
        tenant_weights=tenant_weight_map(tenants),
        tenant_slo_p99=tenant_slo_map(tenants),
        **cfg_kw,
    )
    gw = ShardedGateway(
        CODE,
        ClusterProfile.computation_critical(),
        NUM_NODES,
        num_shards,
        cfg,
        vnodes=256,
    )
    rng = np.random.default_rng(seed)
    gw.load_objects(
        rng.integers(0, 256, (num_objects, CODE.k, q), dtype=np.uint8)
    )
    reqs = generate_tenant_requests(tenants, num_objects, 300, seed=seed)
    return gw, reqs


def _digests(rep):
    return {
        (r.time, r.object_id): r.payload_digest
        for r in rep.completed
        if r.kind == "get"
    }


# -- consistent-hash directory ------------------------------------------------


def test_directory_covers_and_balances():
    d = ShardDirectory(range(4), vnodes=256)
    owners = [d.shard_for(oid) for oid in range(2000)]
    counts = {sid: owners.count(sid) for sid in d.shards}
    assert set(counts) == {0, 1, 2, 3}
    assert all(c > 0 for c in counts.values())
    # the murmur-mixed ring keeps arcs sane: no shard owns a majority
    assert max(counts.values()) < 0.5 * len(owners)


def test_directory_minimal_movement_on_shard_death():
    d = ShardDirectory(range(4), vnodes=256)
    before = {oid: d.shard_for(oid) for oid in range(2000)}
    d.remove_shard(2)
    moved = 0
    for oid, owner in before.items():
        if owner == 2:
            moved += 1
            assert d.shard_for(oid) in {0, 1, 3}
        else:
            # survivors keep every object they already owned
            assert d.shard_for(oid) == owner
    assert moved > 0


def test_directory_refuses_to_remove_last_shard():
    d = ShardDirectory([0], vnodes=16)
    with pytest.raises(ValueError):
        d.remove_shard(0)


def test_group_ownership_partitions_repair_work():
    meta = MetadataPlane(shard_ids=range(4), vnodes=256)
    gids = [f"g{g}" for g in range(80)]
    for gid in gids:
        owners = [s for s in range(4) if meta.owns_group(s, gid)]
        assert len(owners) == 1  # exactly one live shard owns each group
    # the unsharded gateway (shard_id None) owns everything
    assert all(meta.owns_group(None, gid) for gid in gids)


# -- fabric tenant tagging ----------------------------------------------------


def test_shard_tenant_roundtrip():
    assert shard_tenant("gold", 2) == "gold@s2"
    assert base_tenant("gold@s2") == "gold"
    assert shard_tenant("gold", None) == "gold"
    assert base_tenant("gold") == "gold"
    # legacy int class ids pass through untouched
    assert shard_tenant(1, 2) == 1
    assert base_tenant(1) == 1


# -- cross-shard cache coherence ----------------------------------------------


def test_metadata_plane_fans_out_cache_coherence():
    meta = MetadataPlane(shard_ids=range(2), vnodes=16)
    c0, c1 = LRUBlockCache(1 << 20), LRUBlockCache(1 << 20)
    meta.register_cache(c0)
    meta.register_cache(c1)
    key = ("g0", 0, 0)
    blk = np.zeros(64, dtype=np.uint8)
    c0.put(key, blk)
    c1.put(key, blk)
    # a PUT overwrite / repair heal invalidates EVERY shard's copy
    meta.invalidate(key)
    assert c0.get(key) is None and c1.get(key) is None
    # a node failure tombstones the block in EVERY negative cache
    meta.put_negative(key, now=1.0, ttl=10.0)
    assert c0.is_negative(key, now=2.0) and c1.is_negative(key, now=2.0)
    # recovery purges both
    assert meta.purge_negative([key]) == 2
    assert not c0.is_negative(key, now=2.0)
    # an unregistered (dead) shard's cache drops out of the fan-out
    meta.unregister_cache(c1)
    meta.put_negative(key, now=3.0, ttl=10.0)
    assert c0.is_negative(key, now=3.5) and not c1.is_negative(key, now=3.5)


# -- routing identity + determinism -------------------------------------------


def test_sharded_serve_matches_unsharded_bytes():
    """1 shard vs 3 shards on the same trace + failures: byte-identical
    payloads per (time, object) — the tentpole's correctness gate."""
    failures = plan_failures(4, NUM_NODES, at_time=0.01, spacing=0.0, seed=5)
    gw1, reqs = _mk_sharded(1)
    rep1 = gw1.serve(reqs, failures)
    gw3, _ = _mk_sharded(3)
    rep3 = gw3.serve(reqs, failures)
    assert len(rep1.completed) == len(reqs)
    assert len(rep3.completed) == len(reqs)
    d1, d3 = _digests(rep1), _digests(rep3)
    assert d1 and d1 == d3


def test_sharded_serve_deterministic_replay():
    """Two fresh 3-shard runs of the same trace are bit-identical under
    per-tile decode billing (no measured-kernel wall-clock noise)."""
    failures = plan_failures(4, NUM_NODES, at_time=0.01, spacing=0.0, seed=5)

    def outcome():
        gw, reqs = _mk_sharded(3)
        rep = gw.serve(reqs, failures)
        return [
            (r.time, r.object_id, r.kind, r.latency, r.payload_digest)
            for r in rep.records
        ]

    assert outcome() == outcome()


# -- whole-shard death --------------------------------------------------------


def test_shard_death_failover_zero_loss():
    gw, reqs = _mk_sharded(3)
    span = max(r.time for r in reqs)
    before = {oid: gw.shard_of(oid) for oid in range(60)}
    failures = plan_failures(2, NUM_NODES, at_time=0.01, spacing=0.0, seed=5)
    rep = gw.serve(
        reqs, failures + [ShardFailEvent(time=span * 0.5, shard=1)]
    )
    assert gw.dead_shards == {1}
    assert gw.live_shards() == [0, 2]
    # every request still completes; storage was untouched so the
    # namespace stays fully durable
    assert len(rep.completed) == len(reqs)
    aud = gw.audit_durability()
    assert aud["blocks_lost"] == 0
    assert aud["unreadable_objects"] == 0
    # minimal movement: only the dead shard's objects re-route
    for oid, owner in before.items():
        if owner == 1:
            assert gw.shard_of(oid) in {0, 2}
        else:
            assert gw.shard_of(oid) == owner


def test_shard_death_events_validate():
    gw, reqs = _mk_sharded(1, num_objects=6)
    span = max(r.time for r in reqs)
    with pytest.raises(RuntimeError):
        gw.serve(list(reqs), [ShardFailEvent(time=span * 0.5, shard=0)])
    gw2, reqs2 = _mk_sharded(2, num_objects=6)
    with pytest.raises(ValueError):
        gw2.serve(list(reqs2), [ShardFailEvent(time=0.01, shard=7)])


# -- config validation --------------------------------------------------------


def test_sharded_gateway_rejects_zero_shards():
    with pytest.raises(ValueError):
        ShardedGateway(
            CODE, ClusterProfile.computation_critical(), NUM_NODES, 0
        )


def test_decode_cost_per_tile_validation():
    from repro.gateway import ObjectGateway

    def build(**cfg_kw):
        return ObjectGateway(
            CODE,
            ClusterProfile.computation_critical(),
            NUM_NODES,
            GatewayConfig(**cfg_kw),
        )

    with pytest.raises(ValueError):
        build(decode_cost_per_tile=-0.1)
    with pytest.raises(ValueError):
        build(decode_cost=0.01, decode_cost_per_tile=0.01)
    gw = build(decode_cost_per_tile=0.01)
    assert gw.config.decode_cost_per_tile == 0.01



def test_per_tile_billing_pins_the_tile_width():
    """Billed tiles must not follow the autotune sweep: per-tile billing
    fixes the ragged tile width, other billing leaves it autotuned."""
    from repro.gateway import ObjectGateway
    from repro.gateway.gateway import BILLED_TILE_N

    def build(**cfg_kw):
        return ObjectGateway(
            CODE,
            ClusterProfile.computation_critical(),
            NUM_NODES,
            GatewayConfig(**cfg_kw),
        )

    assert build(decode_cost_per_tile=0.01).coalescer.tile_n == BILLED_TILE_N
    assert build(decode_cost=0.01).coalescer.tile_n is None
