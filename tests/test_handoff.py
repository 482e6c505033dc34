"""The payload hand-off: a GET hands off its k blocks in column order,
read where they lie, and its sha256 is streamed over them. The digest is
byte for byte the one of the stacked payload, whichever column was
decoded; only a block that is not contiguous is copied."""

from __future__ import annotations

import hashlib

import numpy as np
import pytest

from repro.core.product_code import CoreCode
from repro.gateway import GatewayConfig, ObjectGateway
from repro.gateway.gateway import _payload_digest
from repro.gateway.workload import Request
from repro.storage.netmodel import ClusterProfile

Q = 256
CODES = {
    "core": (CoreCode(9, 6, 3), 40),
    "rs": (CoreCode(9, 6, 3), 40),
    "xorbas": (CoreCode(16, 10, 1), 20),  # HDFS-Xorbas LRC(16,10)
}


def stacked_digest(blocks) -> str:
    return hashlib.sha256(np.stack(list(blocks)).tobytes()).hexdigest()


def make_gateway(family: str, seed: int = 5) -> tuple[ObjectGateway, np.ndarray]:
    code, nodes = CODES[family]
    gw = ObjectGateway(
        code,
        ClusterProfile.network_critical(),
        nodes,
        GatewayConfig(code_family=family, record_payloads=True),  # verify on
    )
    objects = np.random.default_rng(seed).integers(
        0, 256, (3, code.k, Q), dtype=np.uint8
    )
    gw.load_objects(objects)
    return gw, objects


def get(gw: ObjectGateway, oid: int = 0):
    report = gw.serve([Request(time=0.0, object_id=oid)], [])
    (rec,) = [r for r in report.records if r.kind == "get"]
    return rec


@pytest.mark.parametrize(
    "family,lost,plan",
    [
        ("core", 0, "local"),
        ("core", 3, "local"),
        ("core", 5, "local"),
        ("rs", 0, "global"),
        ("rs", 3, "global"),
        ("rs", 5, "global"),
        ("xorbas", 0, "local"),
        ("core", None, None),  # a clean GET
    ],
    ids=[
        "core-first", "core-middle", "core-last",
        "rs-first", "rs-middle", "rs-last",
        "xorbas-local", "core-clean",
    ],
)
def test_get_digest_is_the_stacked_payloads(family, lost, plan):
    gw, objects = make_gateway(family)
    if lost is not None:
        gw.store.drop_block((*gw._objects[0], lost))
    rec = get(gw)
    assert rec.degraded == (lost is not None)
    assert rec.payload_digest == stacked_digest(objects[0])
    rebuilt = gw.coalescer.stats.rebuilt_by_plan
    assert rebuilt == ({plan: 1} if plan else {})
    k = gw.code.k
    assert (gw.handoff_bytes, gw.handoff_copied_bytes) == (k * Q, 0)


def test_assembled_payload_is_the_store_blocks_themselves(monkeypatch):
    gw, objects = make_gateway("core")
    gid, row = gw._objects[0]
    lost = 2
    gw.store.drop_block((gid, row, lost))
    seen = []
    assemble = ObjectGateway._assemble_payload

    def keep(self, *a):
        payload = assemble(self, *a)
        seen.append(payload)
        return payload

    monkeypatch.setattr(ObjectGateway, "_assemble_payload", keep)
    rec = get(gw)
    (payload,) = seen
    assert isinstance(payload, list) and len(payload) == gw.code.k
    for c, blk in enumerate(payload):
        if c == lost:
            assert not any(blk is b for b in gw.store.blocks.values())
            np.testing.assert_array_equal(blk, objects[0][c])
        else:
            assert blk is gw.store.blocks[(gid, row, c)]
    assert rec.degraded and rec.payload_digest == stacked_digest(objects[0])
    assert gw.handoff_copied_bytes == 0


def _strided(blocks):
    wide = np.zeros((blocks[1].size * 2,), np.uint8)
    wide[::2] = blocks[1]
    return [blocks[0], wide[::2], *blocks[2:]], blocks[1].nbytes


@pytest.mark.parametrize(
    "shape",
    ["list", "list_with_strided_block", "stacked_2d", "fortran_2d", "half_of_the_blocks"],
)
def test_handoff_blocks_digest_any_layout(shape):
    gw, objects = make_gateway("core")
    blocks = list(objects[1])
    copied = 0
    if shape == "list":
        payload = blocks
    elif shape == "list_with_strided_block":
        payload, copied = _strided(blocks)
    elif shape == "stacked_2d":
        payload = np.stack(blocks)
    elif shape == "fortran_2d":
        payload = np.asfortranarray(np.stack(blocks))
        copied = payload.nbytes  # every row is strided
    else:
        payload = blocks[: len(blocks) // 2]
        blocks = payload
    handed = gw._handoff_blocks(payload)
    assert _payload_digest(handed) == stacked_digest(blocks)
    assert all(b.flags.c_contiguous for b in handed)
    assert gw.handoff_bytes == sum(b.nbytes for b in blocks)
    assert gw.handoff_copied_bytes == copied


@pytest.mark.parametrize("fault", ["first_byte", "last_byte", "half_left_out"])
def test_verify_raises_on_a_wrong_payload(monkeypatch, fault):
    gw, _objects = make_gateway("core")
    gw.store.drop_block((*gw._objects[0], 1))
    assemble = ObjectGateway._assemble_payload

    def wrong(self, *a):
        blocks = assemble(self, *a)
        if fault == "half_left_out":
            return blocks[: len(blocks) // 2]
        i = 0 if fault == "first_byte" else -1
        bad = blocks[i].copy()
        bad[i] ^= 1
        blocks[i] = bad
        return blocks

    monkeypatch.setattr(ObjectGateway, "_assemble_payload", wrong)
    with pytest.raises(AssertionError, match="GET integrity failure"):
        get(gw)
