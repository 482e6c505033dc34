"""The Facebook-warehouse configuration (rs_14_10) against the plain
reference, reference.py, at 16 KiB blocks on the CPU: the stripes
load_objects stores, every column the reference computes, BlockFixer's
10-source rebuild of each single lost column and of two losses of one
stripe, and the faults its cell must catch."""

from __future__ import annotations

import json
import time

import numpy as np
import pytest

import harness
import reference
import test_chipbench_faults as faults

BLOCK = 16384
SEED = 2**31 + 404
BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELL = "rs14.node_repair"


def loaded(seed: int = SEED):
    config = json.loads((harness.HERE / "configs" / "rs_14_10.json").read_text())
    config["block_bytes"] = BLOCK
    gw = harness.build_gateway(config, {}, control=False)
    objects = harness.make_objects(seed, config["objects"], config["k"], BLOCK)
    gw.load_objects(objects)
    return config, gw, objects


def test_the_cell_runs_the_configuration_as_published():
    cell, config, traffic = harness.cell_spec(BENCH, CELL)
    assert (config["n"], config["k"], config["t"]) == (14, 10, 1)
    assert config["block_bytes"] == 256 * 2**20
    assert config["gateway"]["code_family"] == "rs" and config["gateway"]["verify_checksums"]
    assert (cell["chips"], traffic["unit"]) == (1, "node_loss")


def test_stored_stripes_equal_the_reference_encode():
    config, gw, objects = loaded()
    n, k = config["n"], config["k"]
    assert len(gw.meta.groups) == config["objects"]
    for gid, (oid,) in gw.meta.groups.items():
        want = reference.rs_encode(n, k, objects[oid])
        for c in range(n):
            np.testing.assert_array_equal(gw.store.blocks[(gid, 0, c)], want[c])


def test_expected_block_gives_every_column():
    config, gw, objects = loaded()
    n, k, t = config["n"], config["k"], config["t"]
    for gid, (oid,) in gw.meta.groups.items():
        stripe = reference.rs_encode(n, k, objects[oid])
        for c in range(n):
            got = reference.expected_block("rs", n, k, t, [objects[oid]], 0, c)
            np.testing.assert_array_equal(got, stripe[c])


@pytest.mark.parametrize("col", range(14))
def test_fixer_rebuilds_a_single_lost_column_from_ten_sources(col):
    config, gw, objects = loaded()
    n, k = config["n"], config["k"]
    gid = gw.meta.objects[1][0]
    gw.store.drop_block((gid, 0, col))
    rep = gw.fixer.fix_group(gid)
    assert rep.recovered and rep.blocks_repaired == 1 and rep.blocks_fetched == k
    want = reference.expected_block("rs", n, k, 1, [objects[1]], 0, col)
    np.testing.assert_array_equal(gw.store.get((gid, 0, col)), want)


@pytest.mark.parametrize("lost", [(0, 9), (3, 12), (10, 13)])
def test_fixer_rebuilds_two_losses_of_one_stripe_in_one_step(lost):
    config, gw, objects = loaded()
    n, k = config["n"], config["k"]
    gid = gw.meta.objects[0][0]
    for c in lost:
        gw.store.drop_block((gid, 0, c))
    rep = gw.fixer.fix_group(gid)
    assert rep.recovered and rep.blocks_repaired == 2 and rep.blocks_fetched == k
    want = reference.rs_encode(n, k, objects[0])
    for c in range(n):
        np.testing.assert_array_equal(gw.store.get((gid, 0, c)), want[c])


def run_cell():
    return harness.run_cell(
        BENCH, CELL, SEED, 0.3, False, t0=time.perf_counter(),
        overrides={"block_bytes": BLOCK},
    )


@pytest.mark.parametrize("fault", ["answer_altered", "state_unchanged"])
def test_fault_makes_the_cell_not_correct(monkeypatch, fault):
    faults.plant(monkeypatch, fault, "repair")
    result = run_cell()
    assert not result["correct"], result["check"]
    assert result["failed"] > 0


def test_half_the_writebacks_left_out_fails_the_run(monkeypatch):
    """Every other rebuilt block never written back: the warm-up's
    second repair finds a block of its stripe gone, and the run stops
    there with no result."""
    faults.plant(monkeypatch, "half_left_out", "repair")
    with pytest.raises(RuntimeError, match="warm-up failed"):
        run_cell()
