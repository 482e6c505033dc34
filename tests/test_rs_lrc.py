"""Reed-Solomon + LRC codec tests: MDS property, erasure decode, repair."""

import itertools

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.coding import gf256, lrc, rs
from repro.coding.linear import rank_gf256


@pytest.mark.parametrize("n,k", [(5, 3), (9, 6), (14, 12), (10, 6)])
def test_rs_systematic_and_mds(n, k):
    code = rs.make_rs(n, k)
    assert np.array_equal(code.gen[:k], np.eye(k, dtype=np.uint8))
    # MDS: every k-subset of rows has rank k (exhaustive for small n)
    for subset in itertools.combinations(range(n), k):
        assert rank_gf256(code.gen[list(subset)]) == k, subset


@pytest.mark.parametrize("n,k", [(9, 6), (14, 12)])
def test_rs_encode_decode_roundtrip(n, k):
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=(k, 64), dtype=np.uint8)
    code = rs.make_rs(n, k)
    cw = np.asarray(code.encode(jnp.asarray(data)))
    assert cw.shape == (n, 64)
    np.testing.assert_array_equal(cw[:k], data)  # systematic
    # erase m arbitrary blocks, decode from the rest
    for _ in range(10):
        erased = rng.choice(n, size=n - k, replace=False)
        avail = np.setdiff1d(np.arange(n), erased)
        dec = np.asarray(code.decode(avail, jnp.asarray(cw[avail])))
        np.testing.assert_array_equal(dec, data)


def test_rs_repair_specific_blocks():
    n, k = 9, 6
    rng = np.random.default_rng(8)
    data = rng.integers(0, 256, size=(k, 32), dtype=np.uint8)
    code = rs.make_rs(n, k)
    cw = np.asarray(code.encode(jnp.asarray(data)))
    missing = np.asarray([2, 7])
    avail = np.setdiff1d(np.arange(n), missing)
    rep = np.asarray(code.repair(avail, jnp.asarray(cw[avail]), missing))
    np.testing.assert_array_equal(rep, cw[missing])


@given(st.integers(min_value=2, max_value=12), st.data())
@settings(max_examples=25, deadline=None)
def test_rs_any_k_of_n_property(k, data_st):
    n = data_st.draw(st.integers(min_value=k, max_value=min(k + 6, 18)))
    rng = np.random.default_rng(k * 31 + n)
    data = rng.integers(0, 256, size=(k, 8), dtype=np.uint8)
    code = rs.make_rs(n, k)
    cw = np.asarray(code.encode(jnp.asarray(data)))
    avail = np.sort(rng.choice(n, size=k, replace=False))
    dec = np.asarray(code.decode(avail, jnp.asarray(cw[avail])))
    np.testing.assert_array_equal(dec, data)


# ---------------------------------------------------------------------------
# LRC
# ---------------------------------------------------------------------------


def test_lrc_layout_and_parities():
    code = lrc.make_lrc(10, 6)
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, size=(6, 16), dtype=np.uint8)
    cw = np.asarray(code.encode(jnp.asarray(data)))
    assert cw.shape == (10, 16)
    np.testing.assert_array_equal(cw[:6], data)
    # p_1 / p_2 are XORs of the halves (paper Fig. 2)
    np.testing.assert_array_equal(cw[6], np.bitwise_xor.reduce(data[:3], axis=0))
    np.testing.assert_array_equal(cw[7], np.bitwise_xor.reduce(data[3:], axis=0))


def test_lrc_local_repair_paper_example():
    # paper: o_{1,2} = o_{1,1} + o_{1,3} + p_{1,1} — 3 transfers for (10,6)
    code = lrc.make_lrc(10, 6)
    plan = code.repair_plan({1})
    assert plan is not None and len(plan) == 1
    kind, sources, repaired = plan[0]
    assert kind == "local" and repaired == [1]
    assert sorted(sources) == [0, 2, 6]


def test_lrc_global_parity_needs_k():
    code = lrc.make_lrc(10, 6)
    plan = code.repair_plan({8})  # a global parity
    assert plan is not None and len(plan) == 1
    kind, sources, _ = plan[0]
    assert kind == "global" and len(sources) == 6


def test_lrc_tolerates_m_minus_2_always():
    # any n-k-2 failures decodable via global code
    code = lrc.make_lrc(10, 6)
    for erased in itertools.combinations(range(10), 2):
        avail = np.setdiff1d(np.arange(10), erased)
        assert code.decodable(avail), erased


def test_lrc_avg_single_repair_cost_formula():
    # (k+2)/n * k/2 + (n-k-2)/n * k == (2kn - k^2 - 2k)/2n
    n, k = 10, 6
    direct = (k + 2) / n * (k / 2) + (n - k - 2) / n * k
    assert abs(lrc.avg_single_repair_cost(n, k) - direct) < 1e-12


def test_lrc_repair_plan_executes_correctly():
    code = lrc.make_lrc(10, 6)
    rng = np.random.default_rng(10)
    data = rng.integers(0, 256, size=(6, 16), dtype=np.uint8)
    cw = np.asarray(code.encode(jnp.asarray(data)))
    failed = {1, 4, 8}
    plan = code.repair_plan(set(failed))
    assert plan is not None
    store = {i: cw[i] for i in range(10) if i not in failed}
    for kind, sources, repaired in plan:
        assert all(s in store for s in sources)
        if kind == "local":
            (tgt,) = repaired
            store[tgt] = np.bitwise_xor.reduce(
                np.stack([store[s] for s in sources]), axis=0
            )
        else:
            dec = np.asarray(
                code.decode(
                    np.asarray(sources),
                    jnp.asarray(np.stack([store[s] for s in sources])),
                )
            )
            full = np.asarray(code.encode(jnp.asarray(dec)))
            for t in repaired:
                store[t] = full[t]
    for i in range(10):
        np.testing.assert_array_equal(store[i], cw[i])


# ---------------------------------------------------------------------------
# HDFS-Xorbas LRC(16, 10) and the computed tolerance of every LRC
# ---------------------------------------------------------------------------


def test_xorbas_rs_parities_sum_to_the_implied_parity():
    assert lrc.cyclic_generator_poly(4) == (85, 120, 36, 8, 1)
    gen = lrc.xorbas_generator(16, 10)
    np.testing.assert_array_equal(gen[:10], np.eye(10, dtype=np.uint8))
    # g(1) = 0, so P1 + .. + P4 = X1 + .. + X10 = S1 + S2
    np.testing.assert_array_equal(
        np.bitwise_xor.reduce(gen[10:14], axis=0), np.ones(10, dtype=np.uint8)
    )
    np.testing.assert_array_equal(gen[14], [1] * 5 + [0] * 5)
    np.testing.assert_array_equal(gen[15], [0] * 5 + [1] * 5)


def test_xorbas_rs_part_is_mds():
    gen = lrc.xorbas_generator(16, 10)[:14]
    for subset in itertools.combinations(range(14), 10):
        assert rank_gf256(gen[list(subset)]) == 10, subset


def test_xorbas_every_four_losses_decode_and_tolerance_is_four():
    code = lrc.make_xorbas(16, 10)
    for lost in itertools.combinations(range(16), 4):
        assert code.decodable(np.setdiff1d(np.arange(16), lost)), lost
    assert code.tolerance == 4
    # distance 5: some five losses do not decode
    assert not code.decodable(np.setdiff1d(np.arange(16), [0, 1, 2, 3, 4]))


def test_xorbas_every_block_has_a_five_source_local_group():
    code = lrc.make_xorbas(16, 10)
    for i in range(16):
        assert code.local_cost(i) == 5, i
        plan = code.repair_plan({i})
        assert [(kind, len(src), rep) for kind, src, rep in plan] == [("local", 5, [i])]
    # an RS parity's group is the implied parity's: the other P's, S1, S2
    ((_kind, sources, _rep),) = code.repair_plan({12})
    assert sorted(sources) == [10, 11, 13, 14, 15]


def test_azure_lrc_16_10_tolerates_four_not_n_minus_k_minus_one():
    code = lrc.make_lrc(16, 10)
    assert code.tolerance == 4
    undecodable = [
        lost
        for lost in itertools.combinations(range(16), 5)
        if not code.decodable(np.setdiff1d(np.arange(16), lost))
    ]
    assert len(undecodable) == 1, undecodable


@pytest.mark.parametrize(
    "make,n,k",
    [
        (lrc.make_lrc, 9, 6),
        (lrc.make_lrc, 10, 6),
        (lrc.make_lrc, 16, 10),
        (lrc.make_xorbas, 9, 6),
        (lrc.make_xorbas, 16, 10),
    ],
)
def test_every_pattern_up_to_tolerance_decodes(make, n, k):
    """Decode real data from every erasure pattern up to the code's
    tolerance, through the code's own solver."""
    code = make(n, k)
    data = np.random.default_rng(n * k).integers(0, 256, size=(k, 8), dtype=np.uint8)
    stripe = np.asarray(code.encode(jnp.asarray(data)))
    for e in range(1, code.tolerance + 1):
        for lost in itertools.combinations(range(n), e):
            avail = np.setdiff1d(np.arange(n), lost)
            rows, inv = code.decode_matrix(avail)
            np.testing.assert_array_equal(gf256.np_matmul(inv, stripe[rows]), data)
