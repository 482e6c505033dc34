"""The plain reference against the program at 16 KiB blocks on the CPU:
its GF(256) table, the RS and CORE encodes that load_objects stores, and
the bytes of degraded reads."""

from __future__ import annotations

import hashlib
import json

import numpy as np
import pytest

import codec_bytes
import harness
import reference
from traffic_gen import Mix

BLOCK = 16384
CONFIGS = {"core": "core_9_6_3", "rs": "rs_9_6"}


def loaded(family: str, seed: int, traffic: dict | None = None):
    config = json.loads((harness.HERE / "configs" / f"{CONFIGS[family]}.json").read_text())
    config["block_bytes"] = BLOCK
    gw = harness.build_gateway(config, traffic or {}, control=False)
    objects = harness.make_objects(seed, config["objects"], config["k"], BLOCK)
    gw.load_objects(objects)
    return config, gw, objects


def test_gf_table_matches_the_program():
    from repro.coding import gf256

    a = np.arange(256, dtype=np.uint8)[:, None]
    b = np.arange(256, dtype=np.uint8)[None, :]
    np.testing.assert_array_equal(reference.mul_table(), gf256.np_matmul(a, b))
    for x in range(1, 256):
        assert reference.mul_table()[x, reference.gf_inv(x)] == 1


def test_rs_generator_is_systematic_and_mds():
    gen = reference.rs_generator(9, 6)
    np.testing.assert_array_equal(gen[:6], np.eye(6, dtype=np.uint8))
    data = np.random.default_rng(3).integers(0, 256, (6, 64), dtype=np.uint8)
    stripe = reference.rs_encode(9, 6, data)
    for cols in ([0, 1, 2, 3, 4, 5], [3, 4, 5, 6, 7, 8], [0, 2, 4, 6, 7, 8]):
        np.testing.assert_array_equal(reference.rs_decode(9, 6, cols, stripe[cols]), data)


@pytest.mark.parametrize("family", ["core", "rs"])
def test_reference_encode_matches_load(family):
    config, gw, objects = loaded(family, 2**31 + 5)
    n, k, t = config["n"], config["k"], config["t"]
    for gid, members in gw.meta.groups.items():
        group = objects[members]
        want = (
            reference.core_encode(n, k, t, group)
            if family == "core"
            else reference.rs_encode(n, k, group[0])[None]
        )
        for r in range(want.shape[0]):
            for c in range(n):
                stored = gw.store.blocks[(gid, r, c)]
                np.testing.assert_array_equal(stored, want[r, c])
                np.testing.assert_array_equal(
                    reference.expected_block(family, n, k, t, group, r, c), stored
                )


@pytest.mark.parametrize("family", ["core", "rs"])
def test_reference_decodes_match_degraded_reads(family):
    """Every object misses a data block; each GET's payload equals what
    the reference rebuilds from the surviving blocks the store holds."""
    traffic = {
        "unit": "get",
        "setup_faults": {"crash_one_data_block_per_object": True},
        "gateway": {"repair_on_failure": False},
    }
    config, gw, objects = loaded(family, 2**31 + 6, traffic)
    n, k = config["n"], config["k"]
    mix = Mix(traffic, gw, 2**31 + 6)
    gw.serve([], mix.setup_events())
    store = gw.store
    for i in range(len(objects)):
        unit = mix.unit(i)
        (oid,) = unit.gets
        gid, row = gw.meta.objects[oid]
        (lost,) = mix.lost_cols[oid]
        if family == "core":
            rows = [r for r in range(gw.family.rows) if r != row]
            rebuilt = reference.xor_repair(np.stack([store.get((gid, r, lost)) for r in rows]))
            data = objects[oid].copy()
            data[lost] = rebuilt
        else:
            cols = [c for c in range(n) if store.available((gid, 0, c))][:k]
            data = reference.rs_decode(n, k, cols, np.stack([store.get((gid, 0, c)) for c in cols]))
        np.testing.assert_array_equal(data, objects[oid])
        (rec,) = gw.serve(unit.requests, unit.events).records
        assert rec.degraded
        assert rec.payload_digest == hashlib.sha256(data.tobytes()).hexdigest()


def test_get_units_go_round_robin_over_the_objects():
    """After warm-up each unit is one GET, in the mix's seeded order,
    corrupted objects first, each served with the object's bytes."""
    traffic = {
        "unit": "get",
        "setup_faults": {"crash_one_data_block_per_object": True, "corrupt_objects": 1},
        "gateway": {"repair_on_failure": False},
    }
    _config, gw, objects = loaded("core", 2**31 + 7, traffic)
    mix = Mix(traffic, gw, 2**31 + 7)
    gw.serve([], mix.setup_events())
    assert mix.order[0] == mix.corrupt_objects[0] and sorted(mix.order) == mix.objects
    warm = mix.warmup_count()
    seen = []
    for i in range(warm, warm + 8):
        unit = mix.unit(i)
        (rec,) = gw.serve(unit.requests, unit.events).records
        assert [rec.object_id] == unit.gets
        assert rec.payload_digest == hashlib.sha256(objects[rec.object_id].tobytes()).hexdigest()
        seen += unit.gets
    assert seen == [mix.order[j % len(mix.order)] for j in range(8)]


def test_work_of_the_decodes():
    assert codec_bytes.decode_ops("core", 6, 3, {0: {2}, 3: {5}}) == {
        0: [("xor", 3, 1)],
        3: [("xor", 3, 1)],
    }
    # two losses in one column: that column's rows decode horizontally
    assert codec_bytes.decode_ops("core", 6, 3, {0: {2}, 1: {2}}) == {
        0: [("gf256", 6, 1)],
        1: [("gf256", 6, 1)],
    }
    assert codec_bytes.decode_ops("rs", 6, 1, {0: {1, 4}}) == {0: [("gf256", 6, 2)]}
    work: dict = {}
    codec_bytes.add_bytes(work, "read", [("xor", 3, 1), ("gf256", 6, 2)], 10)
    assert work == {"read.xor": 40, "read.gf256": 80}
