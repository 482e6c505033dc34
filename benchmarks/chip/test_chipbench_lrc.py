"""The HDFS-Xorbas configuration (lrc_16_10) against its plain reference,
reference_lrc.py, at 16 KiB blocks on the CPU: the stripes load_objects
stores, degraded GETs through a local group, BlockFixer's local repair of
an RS parity through the implied parity, a global repair past the local
groups, the faults its cell must catch, and its roofline reader."""

from __future__ import annotations

import hashlib
import json
import time
import types

import numpy as np
import pytest

import harness
import reference_lrc
import test_chipbench_faults as faults
import test_chipbench_kernel_names as kernel_names

BLOCK = 16384
SEED = 2**31 + 303
BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELL = "lrc.degraded_read"


def loaded(seed: int = SEED):
    config = json.loads((harness.HERE / "configs" / "lrc_16_10.json").read_text())
    config["block_bytes"] = BLOCK
    gw = harness.build_gateway(config, {}, control=False)
    objects = harness.make_objects(seed, config["objects"], config["k"], BLOCK)
    gw.load_objects(objects)
    return config, gw, objects


def stripe_of(gw, gid: str, n: int) -> dict:
    return {c: gw.store.get((gid, 0, c)) for c in range(n) if gw.store.available((gid, 0, c))}


def test_reference_generator_and_groups():
    assert reference_lrc.generator_poly(4) == [85, 120, 36, 8, 1]
    gen = reference_lrc.generator(16, 10)
    # the RS parities sum to S1 + S2: the implied parity
    np.testing.assert_array_equal(np.bitwise_xor.reduce(gen[10:14], axis=0), np.ones(10, np.uint8))
    for grp in reference_lrc.local_groups(16, 10):
        assert len(grp) == 6 and not np.bitwise_xor.reduce(gen[grp], axis=0).any()
    assert set().union(*reference_lrc.local_groups(16, 10)) == set(range(16))


def test_reference_matches_the_programs_generator():
    from repro.coding import lrc

    np.testing.assert_array_equal(reference_lrc.generator(16, 10), lrc.xorbas_generator(16, 10))


def test_reference_decodes_and_repairs_its_own_stripes():
    data = np.random.default_rng(5).integers(0, 256, (10, 64), dtype=np.uint8)
    stripe = reference_lrc.encode(16, 10, data)
    whole = dict(enumerate(stripe))
    for col in range(16):
        held = {c: b for c, b in whole.items() if c != col}
        np.testing.assert_array_equal(reference_lrc.local_repair(16, 10, col, held), stripe[col])
    for lost in ((0, 1, 2, 3), (0, 5, 10, 15), (10, 11, 12, 13), (4, 9, 14, 15)):
        held = {c: b for c, b in whole.items() if c not in lost}
        np.testing.assert_array_equal(reference_lrc.decode(16, 10, held), data)


def test_stored_stripes_equal_the_reference_encode():
    config, gw, objects = loaded()
    n, k = config["n"], config["k"]
    assert len(gw.meta.groups) == config["objects"]
    for gid, (oid,) in gw.meta.groups.items():
        want = reference_lrc.encode(n, k, objects[oid])
        for c in range(n):
            np.testing.assert_array_equal(gw.store.blocks[(gid, 0, c)], want[c])


@pytest.mark.parametrize("col", range(10))
def test_single_data_loss_reads_through_a_five_source_local_plan(col):
    from repro.gateway import Request

    config, gw, objects = loaded()
    oid = 0
    gid, row = gw.meta.objects[oid]
    gw.store.drop_block((gid, row, col))
    (op,) = gw.planner.plan(gid, row).decodes
    assert (op.kind, op.plan, op.targets, len(op.sources)) == ("V", "local", (col,), 5)
    (rec,) = gw.serve([Request(time=1.0, object_id=oid)], []).records
    assert rec.degraded
    assert rec.payload_digest == hashlib.sha256(objects[oid].tobytes()).hexdigest()
    st = gw.coalescer.stats
    assert (st.rebuilt_by_plan, st.read_by_plan) == ({"local": 1}, {"local": 5})


@pytest.mark.parametrize("col", [10, 11, 12, 13])
def test_fixer_rebuilds_an_rs_parity_from_its_implied_group(col):
    config, gw, objects = loaded()
    n, k = config["n"], config["k"]
    gid = gw.meta.objects[0][0]
    want = stripe_of(gw, gid, n)
    gw.store.drop_block((gid, 0, col))
    rep = gw.fixer.fix_group(gid)
    assert rep.recovered and rep.blocks_fetched == 5 and rep.blocks_repaired == 1
    assert (rep.rebuilt_by_plan, rep.read_by_plan) == ({"local": 1}, {"local": 5})
    rebuilt = gw.store.get((gid, 0, col))
    held = {c: b for c, b in want.items() if c != col}
    # the other RS parities and S1, S2: the implied parity's group
    assert sorted(set(range(10, 16)) - {col}) == sorted(
        c for c in reference_lrc.local_groups(n, k)[2] if c != col
    )
    np.testing.assert_array_equal(rebuilt, reference_lrc.local_repair(n, k, col, held))
    np.testing.assert_array_equal(rebuilt, reference_lrc.encode(n, k, objects[0])[col])


def test_four_losses_repair_globally_and_exactly():
    config, gw, objects = loaded()
    n, k = config["n"], config["k"]
    gid = gw.meta.objects[0][0]
    lost = (0, 1, 5, 6)  # two in each data group: no group holds one loss
    for c in lost:
        gw.store.drop_block((gid, 0, c))
    rep = gw.fixer.fix_group(gid)
    assert rep.recovered and rep.blocks_repaired == 4
    assert (rep.rebuilt_by_plan, rep.read_by_plan) == ({"global": 4}, {"global": k})
    want = reference_lrc.encode(n, k, objects[0])
    for c in range(n):
        np.testing.assert_array_equal(gw.store.get((gid, 0, c)), want[c])


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out", "state_unchanged"])
def test_fault_makes_the_cell_not_correct(monkeypatch, fault):
    faults.plant(monkeypatch, fault, "read")
    result = harness.run_cell(
        BENCH, CELL, SEED, 0.3, False, t0=time.perf_counter(),
        overrides={"block_bytes": BLOCK},
    )
    assert not result["correct"], result["check"]
    assert result["failed"] > 0


def test_roofline_reader_finds_its_kernel():
    name = kernel_names.module_name(kernel_names.lowered("ragged_xor_tiles").compile())
    mods = kernel_names.modules_of("lrc_local_decode_hbm_roofline")
    assert [f for f in mods if f in name] == ["ragged_xor_tiles"], name


def test_roofline_reader_counts_six_blocks_per_get():
    reading = harness.Reading(
        harness.cell_spec(BENCH, CELL)[0],
        "TPU v5 lite",
        types.SimpleNamespace(module_seconds=lambda mods: 0.5),
        {},
        {"payload_bytes": 20 * 10 * 2**26},  # 20 GETs of 10 blocks
        51.0,
    )
    got = harness.read_metric("lrc_local_decode_hbm_roofline", reading)
    assert got == pytest.approx(100.0 * 20 * 6 * 2**26 / (819e9 * 0.5))
    reading.trace = None
    assert harness.read_metric("lrc_local_decode_hbm_roofline", reading) is None
