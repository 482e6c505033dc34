"""Storage substrate tests: placement, failure injection, BlockFixer modes
(paper §7/§8 semantics), degraded reads."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CoreCode, CoreCodec
from repro.storage import BlockFixer, BlockStore, ClusterProfile


def make_group(code: CoreCode, store: BlockStore, group_id="g0", q=1024, seed=0):
    rng = np.random.default_rng(seed)
    objects = rng.integers(0, 256, size=(code.t, code.k, q), dtype=np.uint8)
    matrix = np.asarray(CoreCodec(code).encode(jnp.asarray(objects)))
    store.put_group(group_id, matrix)
    return objects, matrix


def test_placement_anti_colocation():
    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=40)
    make_group(code, store)
    nodes = [store.node_of(("g0", r, c)) for r in range(4) for c in range(9)]
    assert len(set(nodes)) == len(nodes)  # every block on a distinct node


def test_node_failure_marks_blocks_unavailable():
    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=40)
    make_group(code, store)
    victim = store.node_of(("g0", 1, 2))
    store.fail_nodes([victim])
    assert not store.available(("g0", 1, 2))
    fm = store.failure_matrix("g0", 4, 9)
    assert fm.sum() == 1 and fm[1, 2]


@pytest.mark.parametrize("mode,expected_fetch", [
    ("hdfs_raid", 8),       # all remaining blocks of the stripe
    ("hdfs_raid_opt", 6),   # Opt1: exactly k
    ("core", 3),            # vertical: t blocks
])
def test_single_failure_fetch_counts_9_6_3(mode, expected_fetch):
    """Paper Fig 12 'X' pattern, (9,6,3): the fetch counts that produce
    the 50%-less-bandwidth headline."""
    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=60)
    _, matrix = make_group(code, store)
    store.fail_nodes([store.node_of(("g0", 1, 3))])
    fixer = BlockFixer(store, code, ClusterProfile.network_critical(), mode=mode)
    report = fixer.fix_group("g0")
    assert report.recovered
    assert report.blocks_fetched == expected_fetch
    np.testing.assert_array_equal(store.blocks[("g0", 1, 3)], matrix[1, 3])


@pytest.mark.parametrize("mode,expected_fetch", [
    ("hdfs_raid", 7 + 8),    # two sequential full-stripe fetches
    ("hdfs_raid_opt", 6),    # Opt2: one decode for both
    ("core", 6),             # two vertical repairs, t each
])
def test_double_failure_same_row_9_6_3(mode, expected_fetch):
    """Paper Fig 12 'XX' pattern (both failures on the same object)."""
    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=60)
    _, matrix = make_group(code, store)
    store.fail_nodes([store.node_of(("g0", 1, 3)), store.node_of(("g0", 1, 5))])
    fixer = BlockFixer(store, code, ClusterProfile.network_critical(), mode=mode)
    report = fixer.fix_group("g0")
    assert report.recovered
    assert report.blocks_fetched == expected_fetch
    np.testing.assert_array_equal(store.blocks[("g0", 1, 3)], matrix[1, 3])
    np.testing.assert_array_equal(store.blocks[("g0", 1, 5)], matrix[1, 5])


def test_double_failure_14_12_5_bandwidth_gap():
    """(14,12,5) XX: CORE 2t=10 vs optimized RS k=12 — the ~16% saving."""
    code = CoreCode(14, 12, 5)
    store = BlockStore(num_nodes=120)
    make_group(code, store)
    store.fail_nodes([store.node_of(("g0", 2, 1)), store.node_of(("g0", 2, 7))])
    core = BlockFixer(store, code, ClusterProfile.network_critical(), mode="core")
    r_core = core.fix_group("g0")
    assert r_core.blocks_fetched == 10
    # rebuild a fresh store for the RS comparison
    store2 = BlockStore(num_nodes=120)
    make_group(code, store2)
    store2.fail_nodes([store2.node_of(("g0", 2, 1)), store2.node_of(("g0", 2, 7))])
    opt = BlockFixer(store2, code, ClusterProfile.network_critical(), mode="hdfs_raid_opt")
    r_opt = opt.fix_group("g0")
    assert r_opt.blocks_fetched == 12
    assert 1 - r_core.blocks_fetched / r_opt.blocks_fetched == pytest.approx(1 / 6)


def test_core_repairs_beyond_rs_tolerance():
    """A row with m+1 failures is lost to plain RS but CORE recovers it
    via vertical parities (the paper's fault-tolerance bonus)."""
    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=60)
    _, matrix = make_group(code, store)
    cells = [(1, c) for c in range(4)]  # 4 > m = 3 failures in one row
    store.fail_nodes([store.node_of(("g0", r, c)) for r, c in cells])
    raid = BlockFixer(store, code, ClusterProfile.network_critical(), mode="hdfs_raid_opt")
    # RS alone cannot: row 1 has > m failures
    rep = raid.fix_group("g0")
    assert not rep.recovered
    fixer = BlockFixer(store, code, ClusterProfile.network_critical(), mode="core")
    report = fixer.fix_group("g0")
    assert report.recovered
    for r, c in cells:
        np.testing.assert_array_equal(store.blocks[("g0", r, c)], matrix[r, c])


def test_network_vs_compute_profiles():
    """Vertical XOR repair must beat RS decode on compute time; the
    network-critical profile must amplify network gaps."""
    code = CoreCode(14, 12, 5)
    q = 1 << 18  # 256 KiB blocks
    results = {}
    for mode in ("core", "hdfs_raid_opt"):
        store = BlockStore(num_nodes=120)
        make_group(code, store, q=q)
        store.fail_nodes([store.node_of(("g0", 2, 3))])
        fixer = BlockFixer(store, code, ClusterProfile.network_critical(), mode=mode)
        fixer.fix_group("g0")  # warm the jit caches
        store.fail_nodes([store.node_of(("g0", 2, 4))])
        results[mode] = fixer.fix_group("g0")
    assert results["core"].network_time < results["hdfs_raid_opt"].network_time
    assert results["core"].bytes_fetched < results["hdfs_raid_opt"].bytes_fetched


def test_degraded_read_with_vertical_repair():
    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=60)
    objects, _ = make_group(code, store)
    store.fail_nodes([store.node_of(("g0", 0, 2))])
    fixer = BlockFixer(store, code, ClusterProfile.network_critical(), mode="core")
    data, report = fixer.degraded_read("g0", 0)
    np.testing.assert_array_equal(data, objects[0])
    # 5 direct reads + 3 vertical sources
    assert report.blocks_fetched == 5 + 3
    # read is non-destructive: the block is still missing
    assert not store.available(("g0", 0, 2))


def test_degraded_read_falls_back_to_row_decode():
    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=60)
    objects, _ = make_group(code, store)
    # two failures in the same column -> vertical impossible for (0,2)
    store.fail_nodes([store.node_of(("g0", 0, 2)), store.node_of(("g0", 2, 2))])
    fixer = BlockFixer(store, code, ClusterProfile.network_critical(), mode="core")
    data, report = fixer.degraded_read("g0", 0)
    np.testing.assert_array_equal(data, objects[0])
    assert report.blocks_fetched == 6  # full row decode


def test_partial_recovery_across_clusters():
    """An unrecoverable cluster must not block repair of an independent
    recoverable cluster (§6.1 benefit ii)."""
    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=80)
    _, matrix = make_group(code, store)
    # unrecoverable cluster: two rows x (m+1) identical columns
    bad = [(0, c) for c in range(4)] + [(1, c) for c in range(4)]
    # recoverable singleton elsewhere
    good = [(3, 8)]
    store.fail_nodes([store.node_of(("g0", r, c)) for r, c in bad + good])
    fixer = BlockFixer(store, code, ClusterProfile.network_critical(), mode="core")
    report = fixer.fix_group("g0")
    assert not report.recovered  # overall group not fully recovered
    np.testing.assert_array_equal(store.blocks[("g0", 3, 8)], matrix[3, 8])


# -- rack-aware placement (failure domains) -----------------------------------


def test_rack_aware_placement_row_and_col_distinct():
    """With nodes_per_rack set, no two blocks of the same row OR column
    share a rack — a whole-rack failure (ToR/PDU) costs each stripe and
    each vertical repair group at most one block."""
    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=36, nodes_per_rack=3)  # 12 racks >= n=9
    for g in range(6):
        make_group(code, store, group_id=f"g{g}", seed=g)
    for g in range(6):
        racks = {
            (r, c): store.rack_of(store.node_of((f"g{g}", r, c)))
            for r in range(code.rows)
            for c in range(code.n)
        }
        for r in range(code.rows):
            assert len({racks[(r, c)] for c in range(code.n)}) == code.n
        for c in range(code.n):
            assert len({racks[(r, c)] for r in range(code.rows)}) == code.rows


def test_whole_rack_failure_costs_one_block_per_line():
    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=36, nodes_per_rack=3)
    for g in range(4):
        make_group(code, store, group_id=f"g{g}", seed=10 + g)
    for rack in range(12):
        lo = rack * 3
        store.fail_nodes([lo, lo + 1, lo + 2])
        for g in range(4):
            fm = store.failure_matrix(f"g{g}", code.rows, code.n)
            assert fm.sum(axis=1).max() <= 1  # <= 1 loss per row
            assert fm.sum(axis=0).max() <= 1  # <= 1 loss per column
        store.heal_node(lo), store.heal_node(lo + 1), store.heal_node(lo + 2)


def test_rack_aware_repair_writeback_keeps_invariant():
    """Repair write-back must re-place the healed block without putting
    it in a rack already hosting a live block of its row or column."""
    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=36, nodes_per_rack=3)
    _, matrix = make_group(code, store, seed=3)
    key = ("g0", 1, 4)
    store.fail_nodes([store.node_of(key)])
    fixer = BlockFixer(store, code, ClusterProfile.network_critical(), mode="core")
    assert fixer.fix_group("g0").recovered
    np.testing.assert_array_equal(store.blocks[key], matrix[1, 4])
    new_rack = store.rack_of(store.node_of(key))
    peer_racks = {
        store.rack_of(store.node_of(("g0", r, c)))
        for r in range(code.rows)
        for c in range(code.n)
        if (r, c) != (1, 4) and (r == 1 or c == 4)
        and store.available(("g0", r, c))
    }
    assert new_rack not in peer_racks


def test_rack_aware_placement_needs_enough_racks():
    from repro.storage.blockstore import PlacementError

    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=12, nodes_per_rack=3)  # 4 racks < n=9
    with pytest.raises(PlacementError):
        make_group(code, store)


def test_rackless_store_placement_unchanged():
    """nodes_per_rack=None must keep the classic layout byte-identical
    (the rack plane is strictly opt-in)."""
    code = CoreCode(9, 6, 3)
    a, b = BlockStore(num_nodes=40), BlockStore(num_nodes=40, nodes_per_rack=None)
    make_group(code, a, seed=5)
    make_group(code, b, seed=5)
    assert a.placement == b.placement


def test_gateway_wires_rack_aware_placement():
    from repro.gateway import GatewayConfig, ObjectGateway, WorkloadConfig, generate_requests

    code = CoreCode(9, 6, 3)
    cfg = GatewayConfig(batch_window=0.01, nodes_per_rack=3)
    gw = ObjectGateway(code, ClusterProfile.network_critical(), 36, cfg)
    rng = np.random.default_rng(9)
    gw.load_objects(rng.integers(0, 256, (6, code.k, 1024), dtype=np.uint8))
    assert gw.store.nodes_per_rack == 3
    wl = WorkloadConfig(num_objects=6, num_requests=40, arrival_rate=500.0, seed=9)
    rep = gw.serve(generate_requests(wl), [])
    assert len(rep.completed) == 40


# -- repair sources staged by reference ---------------------------------------

SRC_Q = 16384  # 16 KiB blocks


def _host_encoded_group(code: CoreCode, family: str, store: BlockStore):
    """Store one group and return it as the code's own host encode:
    ``gf256.np_matmul`` by the generator, CORE's XOR row over the t
    objects. ``family`` "core" is the product code, else the row family
    of that name; returns the family object (None for CORE) and the
    (rows, n, q) matrix."""
    from repro.coding import gf256
    from repro.gateway.planner import make_family

    rng = np.random.default_rng(11)
    if family == "core":
        fam, gen = None, code.horizontal.gen
        objects = rng.integers(0, 256, size=(code.t, code.k, SRC_Q), dtype=np.uint8)
    else:
        fam = make_family(code, family)
        gen = fam.code.gen
        objects = rng.integers(0, 256, size=(1, code.k, SRC_Q), dtype=np.uint8)
    rows = [gf256.np_matmul(gen, obj) for obj in objects]
    if family == "core":
        rows.append(np.bitwise_xor.reduce(np.stack(rows), axis=0))
    matrix = np.stack(rows)
    store.put_group("g0", matrix)
    return fam, matrix


# (name, (n, k, t), family, mode, scheduler, lost cells,
#  blocks fetched = bytes fetched in blocks, read_by_plan)
REPAIR_PATHS = [
    ("core_v", (9, 6, 3), "core", "core", "rgs", [(1, 3)], 3, {"local": 3}),
    ("core_h", (9, 6, 3), "core", "core", "row_first", [(1, 3)], 6, {"global": 6}),
    ("rs_col0", (9, 6, 3), "rs", "core", "rgs", [(0, 0)], 6, {"global": 6}),
    ("rs_two_in_a_step", (9, 6, 3), "rs", "core", "rgs", [(0, 0), (0, 7)], 6, {"global": 6}),
    ("xorbas_local", (16, 10, 5), "xorbas", "core", "rgs", [(0, 0)], 5, {"local": 5}),
    ("xorbas_global", (16, 10, 5), "xorbas", "core", "rgs",
     [(0, 0), (0, 1), (0, 5), (0, 6)], 10, {"global": 10}),
    ("hdfs_raid", (9, 6, 3), "core", "hdfs_raid", "rgs", [(1, 3), (1, 5)], 15, {"global": 15}),
    ("hdfs_raid_opt", (9, 6, 3), "core", "hdfs_raid_opt", "rgs", [(1, 3), (1, 5)], 6,
     {"global": 6}),
]


@pytest.mark.parametrize(
    "name,nkt,family,mode,scheduler,lost,fetched,read_by_plan",
    REPAIR_PATHS,
    ids=[p[0] for p in REPAIR_PATHS],
)
def test_repair_stages_sources_in_place_and_rebuilds_exactly(
    name, nkt, family, mode, scheduler, lost, fetched, read_by_plan
):
    """Every repair path hands the store's own source blocks to the
    device, copying none on the host, and rebuilds each lost block byte
    for byte as the host encode has it; the fetch counters read what
    they read when the sources were stacked on the host."""
    code = CoreCode(*nkt)
    store = BlockStore(num_nodes=120)
    fam, matrix = _host_encoded_group(code, family, store)
    for r, c in lost:
        store.drop_block(("g0", r, c))
    fixer = BlockFixer(
        store, code, ClusterProfile.network_critical(),
        mode=mode, scheduler=scheduler, family=fam,
    )
    rep = fixer.fix_group("g0")
    assert rep.recovered and rep.blocks_repaired == len(lost)
    assert (rep.blocks_fetched, rep.bytes_fetched) == (fetched, fetched * SRC_Q)
    assert rep.read_by_plan == read_by_plan
    assert rep.source_copied_bytes == 0
    for r, c in lost:
        np.testing.assert_array_equal(store.get(("g0", r, c)), matrix[r, c])


def test_non_contiguous_source_is_copied_counted_and_rebuilds_exactly():
    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=60)
    _, matrix = _host_encoded_group(code, "core", store)
    wide = np.zeros((SRC_Q, 2), dtype=np.uint8)
    wide[:, 0] = matrix[0, 3]
    store.blocks[("g0", 0, 3)] = wide[:, 0]  # same bytes, strided
    assert not store.blocks[("g0", 0, 3)].flags.c_contiguous
    store.drop_block(("g0", 1, 3))
    rep = BlockFixer(store, code, ClusterProfile.network_critical()).fix_group("g0")
    assert rep.recovered and rep.read_by_plan == {"local": 3}
    assert (rep.bytes_fetched, rep.source_copied_bytes) == (3 * SRC_Q, SRC_Q)
    np.testing.assert_array_equal(store.get(("g0", 1, 3)), matrix[1, 3])


def test_global_repair_takes_a_sparse_selection_by_reference():
    """Sources given with a dependent one among them (S2, the XOR of
    data columns 5-9, after those five): the repair matrix skips it, so
    the selection is not a prefix of the sources, and the rebuilt block
    is still exact."""
    code = CoreCode(16, 10, 5)
    store = BlockStore(num_nodes=120)
    fam, matrix = _host_encoded_group(code, "xorbas", store)
    order = np.asarray([5, 6, 7, 8, 9, 15, 1, 2, 3, 4, 14, 10])
    row_ids, _ = fam.code.repair_matrix(order, np.asarray([0]))
    assert 15 not in row_ids.tolist() and len(row_ids) == code.k
    fixer = BlockFixer(store, code, ClusterProfile.network_critical(), family=fam)
    blocks = [store.get(("g0", 0, int(c))) for c in order]
    got = fixer._family_global_repair(order, blocks, np.asarray([0]))
    np.testing.assert_array_equal(got[0], matrix[0, 0])


class _NumpyWithoutStack:
    """numpy, but ``stack`` raises: stands in for the repair module's
    ``np`` so that a host stack of repair sources fails the test."""

    def __getattr__(self, name):
        return getattr(np, name)

    @staticmethod
    def stack(*args, **kwargs):
        raise AssertionError("repair sources were stacked on the host")


@pytest.mark.parametrize("family,lost", [("core", [(1, 3), (2, 3)]), ("rs", [(0, 0), (0, 7)])])
def test_fix_group_makes_no_host_stack(monkeypatch, family, lost):
    from repro.storage import repair

    code = CoreCode(9, 6, 3)
    store = BlockStore(num_nodes=60)
    fam, matrix = _host_encoded_group(code, family, store)
    for r, c in lost:
        store.drop_block(("g0", r, c))
    monkeypatch.setattr(repair, "np", _NumpyWithoutStack())
    rep = BlockFixer(store, code, ClusterProfile.network_critical(), family=fam).fix_group("g0")
    assert rep.recovered and rep.blocks_repaired == len(lost)
    for r, c in lost:
        np.testing.assert_array_equal(store.get(("g0", r, c)), matrix[r, c])
