"""The bulk load: the store keeps each object's data blocks as read-only
views of the buffers the writer handed in, and only the blocks the code
computes (parity columns, CORE's XOR row) are encoded on the device and
copied back. The stored groups equal the full encode, the load's
counters count those two kinds of bytes, and neither corruption nor
repair ever writes into the writer's buffers."""

from __future__ import annotations

import numpy as np
import pytest

from repro.core.product_code import CoreCode
from repro.gateway import GatewayConfig, ObjectGateway
from repro.storage.netmodel import ClusterProfile

Q = 512
# family -> (code, nodes, objects); core_short leaves its last group
# one object short, so one row of it is zero padding
CASES = {
    "core": ("core", CoreCode(9, 6, 3), 40, 6),
    "core_short": ("core", CoreCode(9, 6, 3), 40, 5),
    "rs": ("rs", CoreCode(14, 10, 1), 30, 3),
    "lrc": ("lrc", CoreCode(10, 6, 1), 30, 3),
    "xorbas": ("xorbas", CoreCode(16, 10, 1), 30, 3),
}


def loaded(case: str, seed: int = 3) -> tuple[ObjectGateway, np.ndarray]:
    family, code, nodes, num = CASES[case]
    gw = ObjectGateway(
        code,
        ClusterProfile.network_critical(),
        nodes,
        GatewayConfig(code_family=family, record_payloads=True),
    )
    objects = np.random.default_rng(seed).integers(0, 256, (num, code.k, Q), dtype=np.uint8)
    gw.load_objects(objects)
    return gw, objects


def group_matrix(gw: ObjectGateway, gid: str) -> np.ndarray:
    return np.stack(
        [
            np.stack([gw.store.blocks[(gid, r, c)] for c in range(gw.code.n)])
            for r in range(gw.family.rows)
        ]
    )


@pytest.mark.parametrize("case", sorted(CASES))
def test_data_blocks_are_read_only_views_of_the_writers_buffers(case):
    gw, objects = loaded(case)
    for oid, (gid, row) in gw.meta.objects.items():
        for c in range(gw.code.k):
            blk = gw.store.blocks[(gid, row, c)]
            assert not blk.flags.writeable
            assert np.shares_memory(blk, objects[oid, c])
            assert blk.__array_interface__["data"][0] == objects[oid, c].ctypes.data
    assert objects.flags.writeable  # the writer's own array is left as it was


@pytest.mark.parametrize("case", sorted(CASES))
def test_stored_groups_equal_the_full_encode(case):
    gw, objects = loaded(case)
    t = gw.family.objects_per_group
    k = gw.code.k
    for gid, members in gw.meta.groups.items():
        chunk = objects[members]
        pad = np.zeros((t - len(members), k, Q), dtype=np.uint8)
        want = np.asarray(gw.family.encode_group(np.concatenate([chunk, pad])))
        np.testing.assert_array_equal(group_matrix(gw, gid), want)


@pytest.mark.parametrize("case", sorted(CASES))
def test_load_counters_count_computed_and_aliased_bytes(case):
    gw, objects = loaded(case)
    n, k = gw.code.n, gw.code.k
    groups = len(gw.meta.groups)
    if gw.family.name == "core":
        t = gw.family.objects_per_group
        computed = groups * (t * (n - k) + n)  # parity columns, the XOR row
    else:
        computed = groups * (n - k)
    assert gw.load_d2h_bytes == computed * Q
    assert gw.load_aliased_bytes == objects.shape[0] * k * Q == objects.nbytes


@pytest.mark.parametrize("case", sorted(CASES))
def test_an_in_place_write_to_a_stored_block_raises(case):
    gw, objects = loaded(case)
    before = objects.copy()
    gid, row = gw.meta.objects[0]
    for col in (0, gw.code.k):  # a data block and a parity block
        blk = gw.store.blocks[(gid, row, col)]
        with pytest.raises(ValueError):
            blk[0] ^= 1
    np.testing.assert_array_equal(objects, before)


@pytest.mark.parametrize("case", sorted(CASES))
def test_corruption_and_repair_leave_the_writers_buffers_unchanged(case):
    gw, objects = loaded(case)
    before = objects.copy()
    gid, row = gw.meta.objects[0]
    corrupt, lost = (gid, row, 1), (gid, row, 2)
    assert gw.store.corrupt_block(corrupt)
    assert not gw.store.verify(corrupt)
    gw.store.quarantine(corrupt)  # what the integrity plane does on a mismatch
    gw.store.drop_block(lost)
    report = gw.fixer.fix_group(gid)
    assert report.recovered and report.blocks_repaired == 2
    np.testing.assert_array_equal(objects, before)
    for key in (corrupt, lost):
        np.testing.assert_array_equal(gw.store.get(key), before[0, key[2]])
        assert not np.shares_memory(gw.store.get(key), objects)


def test_load_spans_record_under_a_trace(tmp_path):
    """``gw.load`` holds one ``gw.load.encode`` and one ``gw.load.d2h``
    per group, in that order, on the loading thread."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    gw, _objects = loaded("core_short")
    jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    got = sorted(
        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name.split("#", 1)[0])
        for plane in data.planes
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith("gw.load")
    )
    names = [name for _s, _e, name in got]
    groups = len(gw.meta.groups)
    assert names == ["gw.load"] + ["gw.load.encode", "gw.load.d2h"] * groups
    load_start, load_end = got[0][:2]
    assert all(load_start <= s <= e <= load_end for s, e, _name in got[1:])
