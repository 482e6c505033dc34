"""Ragged decode megakernel (kernels/ragged_decode.py + the coalescer's
``mode="ragged"`` dataplane): kernel-level correctness against the jnp
oracles, the byte-identity property against the bucketed baseline over
randomized mixed-shape windows (H+V, ragged lengths, top-rung-overflow
batch sizes), the O(1)-per-kind jit-signature bound, and the LaunchUnit
accounting contract the gateway's engine dispatch relies on."""

from collections import defaultdict

import jax.numpy as jnp
import numpy as np
import pytest

from repro.gateway.coalescer import (
    PAD_LADDER,
    BUCKETED,
    RAGGED,
    DecodeCoalescer,
)
from repro.coding.gf256 import np_matmul
from repro.gateway.planner import DecodeOp
from repro.kernels import ops, ref
from repro.kernels.gf256_matmul import expand_coeff_bitplanes
from repro.kernels.ragged_decode import CHUNK_BIG, CHUNK_SMALL, chunk_sizes


# ---------------------------------------------------------------------------
# kernel level
# ---------------------------------------------------------------------------

def test_ragged_gf256_matches_reference_per_tile():
    """Each tile applies ITS OWN coefficient row: C tiles with C distinct
    rows must match C independent reference products."""
    rng = np.random.default_rng(7)
    c, kk, tn = 8, 6, 256
    coef_rows = rng.integers(0, 256, (c, kk), dtype=np.uint8)
    mc = np.stack(
        [expand_coeff_bitplanes(coef_rows[i][None, :])[0] for i in range(c)]
    )
    data = rng.integers(0, 256, (c, kk, tn), dtype=np.uint8)
    out = np.asarray(
        ops.gf256_ragged(
            mc.transpose(1, 0, 2), data.transpose(1, 0, 2), interpret=True
        )
    )
    for i in range(c):
        want = np.asarray(
            ref.gf256_matmul(jnp.asarray(coef_rows[i][None, :]), jnp.asarray(data[i]))
        )[0]
        np.testing.assert_array_equal(out[i], want)


def test_ragged_xor_matches_reduce():
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, (12, 5, 512), dtype=np.uint8)
    out = np.asarray(ops.xor_ragged(data.transpose(1, 0, 2), interpret=True))
    np.testing.assert_array_equal(out, np.bitwise_xor.reduce(data, axis=1))


@pytest.mark.parametrize("tile_block", [8, 16, CHUNK_BIG])
def test_ragged_tile_blocks_split_a_chunk(tile_block):
    """A compiled launch may walk a big chunk in several tile blocks:
    every block height the TPU accepts gives the same bytes."""
    rng = np.random.default_rng(tile_block)
    c, kk, tn = CHUNK_BIG, 5, 512
    coef_rows = rng.integers(0, 256, (c, kk), dtype=np.uint8)
    mc = np.stack([expand_coeff_bitplanes(coef_rows[i][None, :])[0] for i in range(c)])
    data = rng.integers(0, 256, (kk, c, tn), dtype=np.uint8)
    out = ops.gf256_ragged(mc.transpose(1, 0, 2), data, interpret=True, tile_block=tile_block)
    for i in range(c):
        np.testing.assert_array_equal(out[i], np_matmul(coef_rows[i][None, :], data[:, i])[0])
    xor = ops.xor_ragged(data, interpret=True, tile_block=tile_block)
    np.testing.assert_array_equal(xor, np.bitwise_xor.reduce(data, axis=0))


def test_ragged_zero_padding_is_identity():
    """Zero K rows and zero tail bytes contribute nothing — the staging
    contract the coalescer's gather relies on instead of masking."""
    rng = np.random.default_rng(13)
    c, kk, tn = 4, 6, 128
    coef_rows = rng.integers(0, 256, (c, 3), dtype=np.uint8)  # 3 live rows
    mc = np.zeros((c, kk, 8), dtype=np.uint8)
    for i in range(c):
        mc[i, :3] = expand_coeff_bitplanes(coef_rows[i][None, :])[0]
    data = np.zeros((c, kk, tn), dtype=np.uint8)
    live = rng.integers(0, 256, (c, 3, 100), dtype=np.uint8)  # ragged tail
    data[:, :3, :100] = live
    out = np.asarray(
        ops.gf256_ragged(mc.transpose(1, 0, 2), data.transpose(1, 0, 2), interpret=True)
    )
    for i in range(c):
        want = np.asarray(
            ref.gf256_matmul(jnp.asarray(coef_rows[i][None, :]), jnp.asarray(live[i]))
        )[0]
        np.testing.assert_array_equal(out[i, :100], want)
        assert not out[i, 100:].any()  # zero tail stays zero


def test_chunk_sizes_two_rungs_bound_padding():
    for t in (1, 3, CHUNK_SMALL, CHUNK_SMALL + 1, CHUNK_BIG - 1, CHUNK_BIG,
              CHUNK_BIG + 1, 3 * CHUNK_BIG + 5, 517):
        chunks = chunk_sizes(t)
        assert set(chunks) <= {CHUNK_SMALL, CHUNK_BIG}
        total = sum(chunks)
        assert 0 <= total - t < CHUNK_SMALL  # padding < one small chunk
        # big chunks first, so signatures and padding are deterministic
        assert chunks == sorted(chunks, reverse=True)


# ---------------------------------------------------------------------------
# coalescer property: ragged vs bucketed vs reference, randomized windows
# ---------------------------------------------------------------------------

def _random_window(rng, n_ops, lengths=(100, 512, 1000, 4096)):
    """Synthetic mixed-shape window: V ops (t sources), H ops with 1-3
    targets over k sources, ragged per-op byte lengths."""
    ops_, store = [], {}
    for i in range(n_ops):
        kind = ["V", "H"][int(rng.integers(0, 2))]
        length = int(rng.choice(lengths))
        if kind == "V":
            kk = int(rng.choice([3, 5]))
            sources = tuple((f"g{i}", r, 0) for r in range(kk))
            op = DecodeOp("V", f"g{i}", kk, (0,), sources, None)
        else:
            kk = 6
            m = int(rng.integers(1, 4))
            sources = tuple((f"g{i}", 0, c) for c in range(kk))
            coeffs = rng.integers(0, 256, (m, kk), dtype=np.uint8)
            op = DecodeOp("H", f"g{i}", 0, tuple(range(m)), sources, coeffs)
        for s in sources:
            store[s] = rng.integers(0, 256, length, dtype=np.uint8)
        ops_.append(op)
    return ops_, store


def _reference(op, store):
    srcs = np.stack([store[s] for s in op.sources])
    if op.kind == "V":
        return {op.targets[0]: np.bitwise_xor.reduce(srcs, axis=0)}
    out = np.asarray(
        ref.gf256_matmul(jnp.asarray(op.coeffs), jnp.asarray(srcs))
    )
    return {col: out[m] for m, col in enumerate(op.targets)}


@pytest.mark.parametrize("seed", range(5))
def test_ragged_matches_bucketed_and_reference_on_mixed_windows(seed):
    """The megakernel changes HOW a window decodes, never WHAT: over
    randomized mixed-shape windows the ragged path must be byte-identical
    to the bucketed baseline and to the jnp oracle, with zero filler
    stripes (padded_ops) by construction."""
    rng = np.random.default_rng(seed)
    window, store = _random_window(rng, n_ops=int(rng.integers(1, 16)))
    fetch = lambda key: store[key]
    rag = DecodeCoalescer(interpret=True, mode=RAGGED)
    buck = DecodeCoalescer(interpret=True, mode=BUCKETED)
    res_r, units_r = rag.execute(window, fetch)
    res_b, _units_b = buck.execute(window, fetch)
    assert len(res_r) == len(res_b) == len(window)
    for op, a, b in zip(window, res_r, res_b):
        want = _reference(op, store)
        assert set(a) == set(b) == set(want)
        for col in want:
            np.testing.assert_array_equal(a[col], b[col])
            np.testing.assert_array_equal(a[col], want[col])
    assert rag.stats.padded_ops == 0
    assert rag.stats.decode_ops == buck.stats.decode_ops == len(window)
    # unit fractions of each physical launch sum to 1 (modeled-cost
    # billing depends on it), and every op got at least one unit
    frac = defaultdict(float)
    owned = set()
    for u in units_r:
        frac[u.launch_id] += u.fraction
        owned.update(u.op_indices)
    assert all(abs(v - 1.0) < 1e-9 for v in frac.values())
    assert owned == set(range(len(window)))


def test_ragged_top_rung_overflow_window():
    """A window far beyond the bucketed top rung (PAD_LADDER[-1]) — the
    bucketed path splits into top-rung chunks, the ragged path into
    big/small tile chunks; bytes must agree either way."""
    rng = np.random.default_rng(99)
    n_ops = PAD_LADDER[-1] + 10
    ops_, store = [], {}
    for i in range(n_ops):
        sources = tuple((f"g{i}", r, 0) for r in range(3))
        for s in sources:
            store[s] = rng.integers(0, 256, 64, dtype=np.uint8)
        ops_.append(DecodeOp("V", f"g{i}", 3, (0,), sources, None))
    fetch = lambda key: store[key]
    rag = DecodeCoalescer(interpret=True, mode=RAGGED)
    buck = DecodeCoalescer(interpret=True, mode=BUCKETED)
    res_r, _ = rag.execute(ops_, fetch)
    res_b, _ = buck.execute(ops_, fetch)
    for a, b in zip(res_r, res_b):
        np.testing.assert_array_equal(a[0], b[0])
    assert buck.stats.decode_calls == 2  # 256 + 10-padded-to-16
    # ragged: 266 tiles -> 8 big + 3 small chunks, all one signature set
    assert rag.stats.decode_calls == len(chunk_sizes(n_ops))
    assert rag.stats.launches_by_kind == {"V": rag.stats.decode_calls}
    assert buck.stats.launches_by_kind == {"V": 2}
    assert rag.stats.max_batch >= CHUNK_BIG


def test_ragged_multi_tile_rows_roundtrip():
    """Rows longer than the tile width span several tiles; the scatter
    must reassemble them exactly (including a ragged tail tile)."""
    rng = np.random.default_rng(5)
    length = 10_000  # > 2 tiles at the minimum 128-wide tile, ragged tail
    sources = tuple(("g0", r, 0) for r in range(3))
    store = {s: rng.integers(0, 256, length, dtype=np.uint8) for s in sources}
    op = DecodeOp("V", "g0", 3, (0,), sources, None)
    co = DecodeCoalescer(interpret=True, mode=RAGGED, autotune_kernels=False)
    res, _units = co.execute([op], lambda k: store[k])
    want = np.bitwise_xor.reduce(np.stack([store[s] for s in sources]), axis=0)
    np.testing.assert_array_equal(res[0][0], want)


def test_pinned_tile_width_cuts_and_bills_at_that_width():
    """A pinned tile width replaces the autotuned one: rows are cut and
    billed in tiles of that width, and no autotune lookup runs."""
    from repro.kernels import autotune

    rng = np.random.default_rng(6)
    length = 65536
    sources = tuple(("g0", r, 0) for r in range(3))
    store = {s: rng.integers(0, 256, length, dtype=np.uint8) for s in sources}
    op = DecodeOp("V", "g0", 3, (0,), sources, None)
    before = autotune.cache_stats()
    co = DecodeCoalescer(interpret=True, mode=RAGGED, tile_n=16384)
    res, units = co.execute([op], lambda k: store[k])
    want = np.bitwise_xor.reduce(np.stack([store[s] for s in sources]), axis=0)
    np.testing.assert_array_equal(res[0][0], want)
    assert sum(u.tiles for u in units) == 4
    assert co.tiles_for(length, "V") == 4
    assert autotune.cache_stats() == before


# ---------------------------------------------------------------------------
# jit-signature bound
# ---------------------------------------------------------------------------

def test_ragged_jit_entries_bounded_at_two_per_kind():
    """Arbitrary traffic — window sizes from 1 op to far beyond the big
    chunk, every (M, K) mix, multiple windows — must settle at <= 2
    traced signatures per kind (the two chunk rungs). This is the
    megakernel's core promise: shape diversity costs zero retraces."""
    rng = np.random.default_rng(3)
    co = DecodeCoalescer(interpret=True, mode=RAGGED)
    for n_ops in (1, 3, 9, 40, 130):
        window, store = _random_window(
            rng, n_ops, lengths=(512, 1000, 4096)
        )
        co.execute(window, lambda key: store[key])
    by_kind = co.jit_entries_by_kind()
    assert by_kind, "no launches traced"
    assert all(n <= 2 for n in by_kind.values()), by_kind
    assert co.stats.jit_entries <= 2 * len(by_kind)
    assert co.stats.decode_calls > 10  # plenty of launches, few traces


def test_gateway_ragged_jit_entries_bounded_end_to_end():
    """Through the full gateway (default coalesce="ragged"): a degraded
    500-request run with organically varying window sizes stays within
    2 signatures per kind."""
    from repro.core.product_code import CoreCode
    from repro.gateway import (
        GatewayConfig,
        ObjectGateway,
        WorkloadConfig,
        generate_requests,
    )
    from repro.gateway.workload import FailureEvent
    from repro.storage.netmodel import ClusterProfile

    code = CoreCode(9, 6, 3)
    gw = ObjectGateway(
        code, ClusterProfile.network_critical(), 60,
        GatewayConfig(batch_window=0.01),
    )
    rng = np.random.default_rng(9)
    gw.load_objects(rng.integers(0, 256, (12, code.k, 512), dtype=np.uint8))
    victim = gw.store.node_of(("g0", 0, 0))
    reqs = generate_requests(
        WorkloadConfig(num_objects=12, num_requests=500, arrival_rate=4000.0,
                       seed=13)
    )
    report = gw.serve(reqs, [FailureEvent(time=0.005, node=victim)])
    assert len(report.completed) == 500
    by_kind = gw.coalescer.jit_entries_by_kind()
    assert by_kind and all(n <= 2 for n in by_kind.values()), by_kind
    assert report.decode_launches == gw.coalescer.stats.decode_calls
    assert report.launches_per_window > 0


# ---------------------------------------------------------------------------
# stats contract
# ---------------------------------------------------------------------------

def test_batch_histogram_is_bounded_and_consistent():
    """The per-launch batch-size list was unbounded (one int per launch
    forever); the histogram keys by batch size, so a long run's memory
    stays O(distinct sizes) while max_batch / coalescing_ratio hold."""
    rng = np.random.default_rng(21)
    co = DecodeCoalescer(interpret=True, mode=RAGGED)
    for _ in range(6):
        window, store = _random_window(rng, 6, lengths=(256,))
        co.execute(window, lambda key: store[key])
    st = co.stats
    assert sum(st.batch_hist.values()) == st.decode_calls
    assert max(st.batch_hist) == st.max_batch
    assert all(
        isinstance(k, int) and v > 0 for k, v in st.batch_hist.items()
    )
    assert st.coalescing_ratio == st.decode_ops / st.decode_calls
    assert 0.0 <= st.padded_byte_ratio < 1.0
    assert st.windows == 6
    assert st.launches_per_window == st.decode_calls / 6


def test_gateway_ragged_and_bucketed_serve_identical_bytes():
    """End to end through the gateway: coalesce="ragged" vs "bucketed"
    changes WHEN decodes are billed, never WHAT is served — per-request
    payload digests must match on a degraded trace."""
    from repro.core.product_code import CoreCode
    from repro.gateway import (
        GatewayConfig,
        ObjectGateway,
        WorkloadConfig,
        generate_requests,
    )
    from repro.gateway.workload import FailureEvent
    from repro.storage.netmodel import ClusterProfile

    code = CoreCode(9, 6, 3)
    reports = {}
    for coalesce in ("ragged", "bucketed"):
        gw = ObjectGateway(
            code, ClusterProfile.network_critical(), 60,
            GatewayConfig(batch_window=0.01, coalesce=coalesce,
                          record_payloads=True),
        )
        rng = np.random.default_rng(9)
        gw.load_objects(rng.integers(0, 256, (12, code.k, 2048), dtype=np.uint8))
        reqs = generate_requests(
            WorkloadConfig(num_objects=12, num_requests=150,
                           arrival_rate=3000.0, seed=4)
        )
        # fail nodes that provably hold data blocks of live objects
        # (placement is process-stable, so both runs fail the same nodes)
        victims = [gw.store.node_of(("g0", 0, 0)), gw.store.node_of(("g1", 0, 2))]
        failures = [
            FailureEvent(time=0.005 + 0.01 * i, node=n)
            for i, n in enumerate(victims)
        ]
        reports[coalesce] = gw.serve(reqs, failures)
    rag, buck = reports["ragged"].records, reports["bucketed"].records
    assert len(rag) == len(buck) == 150
    for a, b in zip(rag, buck):
        assert (a.time, a.object_id, a.kind, a.degraded) == (
            b.time, b.object_id, b.kind, b.degraded,
        )
        assert a.payload_digest == b.payload_digest
    assert any(r.degraded for r in rag)


def test_gateway_rejects_unknown_coalesce_mode():
    from repro.core.product_code import CoreCode
    from repro.gateway import GatewayConfig, ObjectGateway
    from repro.storage.netmodel import ClusterProfile

    with pytest.raises(ValueError):
        ObjectGateway(
            CoreCode(9, 6, 3), ClusterProfile.network_critical(), 60,
            GatewayConfig(coalesce="mega"),
        )
    with pytest.raises(ValueError):
        DecodeCoalescer(mode="mega")
