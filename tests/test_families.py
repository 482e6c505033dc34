"""Code-family semantics (PR 8): RS / CORE / LRC behind one planner.

Covers the bake-off's correctness surface:

- family geometry, tolerance, and the Table-1 repair cost model per
  column (CORE verticals at t, RS at k, LRC local groups at k/2);
- LRC local-group repair fetches STRICTLY fewer blocks than the RS
  k-block re-decode — measured through the real BlockFixer, not the
  cost model;
- decode byte-identity through degraded paths: all three families
  serve sha256-identical payloads for the same stripe data with a
  data block missing;
- the Weibull / trace-driven failure inter-arrival laws (1309.0186):
  mean preservation (crash_rate stays 1/mean under every law),
  determinism, and the admission bound under bursty churn.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core.product_code import CoreCode
from repro.gateway import (
    GatewayConfig,
    ObjectGateway,
    WorkloadConfig,
    generate_requests,
)
from repro.gateway.planner import FAMILY_NAMES, make_family
from repro.scenario.trace import ScenarioConfig, _crash_gap, generate_scenario
from repro.storage.netmodel import ClusterProfile

CODE = CoreCode(9, 6, 3)  # even k, n >= k+2: valid for all three families
NUM_OBJECTS = 6
Q = 256


def _mk_gateway(fam: str, seed: int = 3, **cfg_kw) -> ObjectGateway:
    cfg = GatewayConfig(code_family=fam, record_payloads=True, **cfg_kw)
    gw = ObjectGateway(CODE, ClusterProfile.network_critical(), 40, cfg)
    rng = np.random.default_rng(seed)
    gw.load_objects(
        rng.integers(0, 256, (NUM_OBJECTS, CODE.k, Q), dtype=np.uint8)
    )
    return gw


# -- family geometry + cost model ------------------------------------------


def test_family_geometry():
    core = make_family(CODE, "core")
    rs = make_family(CODE, "rs")
    lrc = make_family(CODE, "lrc")
    xorbas = make_family(CODE, "xorbas")
    assert (core.rows, core.n, core.k) == (CODE.t + 1, CODE.n, CODE.k)
    for fam in (rs, lrc, xorbas):
        assert (fam.rows, fam.n, fam.k) == (1, CODE.n, CODE.k)
        assert fam.objects_per_group == 1
    assert core.objects_per_group == CODE.t
    assert set(FAMILY_NAMES) == {"core", "rs", "lrc", "xorbas"}
    with pytest.raises(ValueError):
        make_family(CODE, "raptor")


def test_family_tolerance_and_overhead():
    core = make_family(CODE, "core")
    rs = make_family(CODE, "rs")
    lrc = make_family(CODE, "lrc")
    m = CODE.n - CODE.k
    assert core.tolerance == m
    assert rs.tolerance == m
    # LRC trades one guaranteed erasure for cheap local repair
    assert lrc.tolerance == m - 1
    assert rs.storage_overhead == lrc.storage_overhead == CODE.n / CODE.k
    # CORE's vertical parity row costs extra stretch
    assert core.storage_overhead == pytest.approx(CODE.stretch)
    assert core.storage_overhead > rs.storage_overhead


def test_single_repair_cost_model():
    core = make_family(CODE, "core")
    rs = make_family(CODE, "rs")
    lrc = make_family(CODE, "lrc")
    k = CODE.k
    for col in range(CODE.n):
        assert core.single_repair_cost(col) == CODE.t
        assert rs.single_repair_cost(col) == k
        expected = k // 2 if lrc.code.local_group(col) is not None else k
        assert lrc.single_repair_cost(col) == expected
    # every local repair beats the RS re-decode; globals tie it
    assert lrc.avg_repair_cost < rs.avg_repair_cost
    assert core.avg_repair_cost < rs.avg_repair_cost


def test_lrc_repair_plan_is_local_first():
    lrc = make_family(CODE, "lrc")
    # a single lost data column repairs from its k/2-member local group
    plan = lrc.repair_plan([0])
    assert plan is not None and len(plan) == 1
    kind, sources, repaired = plan[0]
    assert kind == "local"
    assert len(sources) == CODE.k // 2
    assert tuple(repaired) == (0,)
    # RS always re-decodes from k sources
    rs_plan = make_family(CODE, "rs").repair_plan([0])
    assert rs_plan is not None
    _, rs_sources, _ = rs_plan[0]
    assert len(rs_sources) == CODE.k


# -- repair through the real BlockFixer ------------------------------------


def _repair_one_block(fam: str):
    gw = _mk_gateway(fam, seed=7)
    gid, row = gw._objects[0]
    key = (gid, row, 0)  # a data column: LRC repairs it locally
    gw.store.drop_block(key)
    rep = gw.fixer.fix_group(gid)
    assert rep.recovered
    assert gw.store.available(key)
    return rep


def test_local_group_repair_fetches_fewer_than_rs():
    reports = {fam: _repair_one_block(fam) for fam in ("rs", "lrc", "core")}
    assert reports["rs"].blocks_fetched == CODE.k
    assert reports["lrc"].blocks_fetched == CODE.k // 2
    assert reports["core"].blocks_fetched == CODE.t
    # the bake-off's structural claim, as an inequality
    assert reports["lrc"].blocks_fetched < reports["rs"].blocks_fetched
    assert reports["core"].blocks_fetched < reports["rs"].blocks_fetched


def test_lrc_global_parity_repair_falls_back_to_k():
    gw = _mk_gateway("lrc", seed=7)
    gid, row = gw._objects[0]
    # the last column is a global parity: no local group, k-block decode
    assert gw.family.code.local_group(CODE.n - 1) is None
    key = (gid, row, CODE.n - 1)
    gw.store.drop_block(key)
    rep = gw.fixer.fix_group(gid)
    assert rep.recovered and gw.store.available(key)
    assert rep.blocks_fetched == CODE.k


# -- byte identity through degraded paths ----------------------------------


def _serve_degraded(fam: str) -> dict[int, str]:
    gw = _mk_gateway(fam, seed=11, batch_window=0.005)
    # lose one data block of objects 0 and 1 — every GET for them goes
    # through the family's degraded path (no repair: raw reconstruction)
    for obj, col in ((0, 0), (1, 2)):
        gw.store.drop_block((*gw._objects[obj], col))
    wl = WorkloadConfig(
        num_objects=NUM_OBJECTS, num_requests=60, arrival_rate=300.0, seed=11
    )
    rep = gw.serve(generate_requests(wl), [])
    assert len(rep.completed) == len(rep.records)
    assert len(rep.degraded_gets) > 0, fam
    digests: dict[int, str] = {}
    for r in rep.completed:
        if r.kind == "get" and r.payload_digest:
            assert digests.setdefault(r.object_id, r.payload_digest) == (
                r.payload_digest
            )
    assert {0, 1} <= set(digests)  # the degraded objects were read
    return digests


def test_degraded_byte_identity_across_families():
    digests = {fam: _serve_degraded(fam) for fam in FAMILY_NAMES}
    assert digests["core"] == digests["rs"] == digests["lrc"] == digests["xorbas"]


# -- failure inter-arrival laws (1309.0186) --------------------------------


def _gaps(law: str, n: int = 4000, **kw) -> np.ndarray:
    cfg = ScenarioConfig(
        duration=1.0, num_nodes=30, crash_rate=5.0, interarrival=law, **kw
    )
    rng = np.random.default_rng(0)
    return np.asarray([_crash_gap(rng, cfg) for _ in range(n)])


def test_interarrival_laws_preserve_mean():
    mean = 1.0 / 5.0
    for law, kw in (
        ("exponential", {}),
        ("weibull", {"interarrival_shape": 0.7}),
        ("trace", {"interarrival_samples": (0.3, 1.0, 2.5, 7.0)}),
    ):
        gaps = _gaps(law, **kw)
        assert np.all(gaps > 0)
        assert gaps.mean() == pytest.approx(mean, rel=0.1), law


def test_weibull_shape_below_one_is_burstier_than_exponential():
    # shape < 1: heavier tail AND more near-zero gaps than exponential
    # at the same mean — the warehouse-cluster churn signature
    exp, wei = _gaps("exponential"), _gaps("weibull", interarrival_shape=0.7)
    assert wei.std() > exp.std()
    assert np.median(wei) < np.median(exp)


def test_trace_law_resamples_rescaled_empirical_gaps():
    samples = (0.5, 1.0, 4.0)
    gaps = _gaps("trace", interarrival_samples=samples)
    scaled = set(
        np.round(np.asarray(samples) * (0.2 / np.mean(samples)), 12)
    )
    assert set(np.round(gaps, 12)) <= scaled


def test_interarrival_validation():
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError):
        _crash_gap(
            rng, ScenarioConfig(1.0, 30, interarrival="pareto")
        )
    with pytest.raises(ValueError):
        _crash_gap(
            rng,
            ScenarioConfig(1.0, 30, interarrival="weibull", interarrival_shape=0.0),
        )
    with pytest.raises(ValueError):
        _crash_gap(rng, ScenarioConfig(1.0, 30, interarrival="trace"))


def test_weibull_scenario_deterministic_and_bounded():
    cfg = ScenarioConfig(
        duration=2.0,
        num_nodes=30,
        nodes_per_rack=3,
        max_concurrent_failures=2,
        crash_rate=8.0,
        mean_downtime=0.1,
        transient_fraction=0.8,
        interarrival="weibull",
        interarrival_shape=0.7,
        seed=13,
    )
    t1, t2 = generate_scenario(cfg), generate_scenario(cfg)
    assert t1.events == t2.events  # seeded: bit-for-bit reproducible
    crashes = [
        e for e in t1.events
        if type(e).__name__ in ("FailureEvent", "CapacityLossEvent")
    ]
    assert crashes, "trace produced no failures"
    # the admission bound holds under the bursty law: never more than
    # max_concurrent_failures nodes down at once
    down: set[int] = set()
    peak = 0
    for ev in t1.events:
        name = type(ev).__name__
        if name in ("FailureEvent", "CapacityLossEvent"):
            down.add(ev.node)
        elif name == "NodeRecoverEvent":
            down.discard(ev.node)
        peak = max(peak, len(down))
    assert 0 < peak <= cfg.max_concurrent_failures
