"""Validate the trip-count-aware HLO cost analyzer against analytic
counts on known programs (scan-of-matmul, psum'd shard_map) — this is
the oracle behind every §Roofline number."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.hlo_cost import analyze_hlo_text, parse_module


def _cost(fn, *args):
    co = jax.jit(fn).lower(*args).compile()
    return analyze_hlo_text(co.as_text()), co


def test_scan_matmul_flops_trip_scaled():
    L, B, D = 5, 8, 64

    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), ()

        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()

    cost, co = _cost(
        f,
        jax.ShapeDtypeStruct((B, D), jnp.float32),
        jax.ShapeDtypeStruct((L, D, D), jnp.float32),
    )
    expect = 2 * B * D * D * L
    assert cost.flops == pytest.approx(expect, rel=0.02), (cost.flops, expect)
    # builtin cost_analysis counts the body once -> must be ~L x smaller
    builtin = co.cost_analysis().get("flops", 0.0)
    assert builtin < expect / 2


def test_nested_scan_flops():
    L, M, B, D = 4, 3, 2, 16

    def f(x, ws):
        def outer(c, w):
            def inner(ci, _):
                return jnp.tanh(ci @ w), ()

            c2, _ = jax.lax.scan(inner, c, None, length=M)
            return c2, ()

        y, _ = jax.lax.scan(outer, x, ws)
        return y.sum()

    cost, _ = _cost(
        f,
        jax.ShapeDtypeStruct((B, D), jnp.float32),
        jax.ShapeDtypeStruct((L, D, D), jnp.float32),
    )
    expect = 2 * B * D * D * L * M
    assert cost.flops == pytest.approx(expect, rel=0.05)


def test_dot_general_batched_flops():
    B, H, S, D = 2, 4, 32, 16

    def f(q, k):
        return jnp.einsum("bhsd,bhtd->bhst", q, k)

    cost, _ = _cost(
        f,
        jax.ShapeDtypeStruct((B, H, S, D), jnp.float32),
        jax.ShapeDtypeStruct((B, H, S, D), jnp.float32),
    )
    expect = 2 * B * H * S * S * D
    assert cost.flops == pytest.approx(expect, rel=0.02)


def test_bytes_reasonable_for_elementwise():
    N = 1 << 20

    def f(x):
        return x * 2.0 + 1.0

    cost, _ = _cost(f, jax.ShapeDtypeStruct((N,), jnp.float32))
    # one read + one write = 8 MiB; allow fusion-boundary slack
    assert 0.5 * 8e6 < cost.hbm_bytes < 3 * 8e6


def test_parse_module_roundtrip_smoke():
    def f(x):
        return jnp.sin(x).sum()

    co = jax.jit(f).lower(jax.ShapeDtypeStruct((128,), jnp.float32)).compile()
    comps, entry = parse_module(co.as_text())
    assert entry is not None and entry in comps
    assert comps[entry].instrs
