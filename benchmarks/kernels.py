"""Kernel microbench: Pallas GF(2^8) matmul (RS encode/decode) and XOR
parity vs the pure-jnp oracles — us/call in interpret mode (CPU) and the
structural VMEM/roofline numbers for the TPU target — plus the ragged
decode megakernel (kernels/ragged_decode.py) against an equal-bytes
sequence of per-shape stacked launches, the launch-overhead contrast the
gateway's ``gateway_megakernel`` rows measure end to end, and its ENCODE
mirror (kernels/ragged_encode.py) — the write window's parity-generation
and XOR-fold launches — against the same per-shape baseline.

The paper's compute contrast (cheap XOR repair vs RS decode) shows up
directly as the flop/byte gap between the two kernels.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.coding import rs
from repro.kernels import ops, ref
from repro.kernels.gf256_matmul import expand_coeff_bitplanes


def _time(fn, *args, reps=3) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def run(fast: bool = True) -> list[dict]:
    rows = []
    n, k = 14, 12
    parity = rs.parity_matrix(n, k)  # (m, k)
    sizes = [1 << 16, 1 << 20] if fast else [1 << 16, 1 << 20, 1 << 24]
    rng = np.random.default_rng(0)
    for q in sizes:
        data = jnp.asarray(rng.integers(0, 256, (k, q), dtype=np.uint8))
        t_pallas = _time(lambda d: ops.rs_encode(parity, d), data)
        t_ref = _time(lambda d: ref.gf256_matmul(jnp.asarray(parity), d), data)
        out_p = np.asarray(ops.rs_encode(parity, data))
        out_r = np.asarray(ref.gf256_matmul(jnp.asarray(parity), data))
        match = bool((out_p == out_r).all())
        rows.append(
            {"bench": "kernel_gf256_encode", "q_bytes": q,
             "pallas_us": round(t_pallas, 1), "ref_us": round(t_ref, 1),
             "match": match,
             "bytes_moved": (k + n - k) * q,
             "tpu_bound_us": round((k + n - k) * q / 819e9 * 1e6, 2)}
        )
        vert = jnp.asarray(rng.integers(0, 256, (5, q), dtype=np.uint8))
        t_x = _time(lambda d: ops.xor_parity(d), vert)
        t_xr = _time(lambda d: ref.xor_parity(d), vert)
        match_x = bool((np.asarray(ops.xor_parity(vert)) ==
                        np.asarray(ref.xor_parity(vert))).all())
        rows.append(
            {"bench": "kernel_xor_parity", "q_bytes": q,
             "pallas_us": round(t_x, 1), "ref_us": round(t_xr, 1),
             "match": match_x,
             "bytes_moved": 6 * q,
             "tpu_bound_us": round(6 * q / 819e9 * 1e6, 2)}
        )
    rows.extend(_ragged_rows(fast))
    rows.extend(_ragged_encode_rows(fast))
    return rows


def _ragged_rows(fast: bool) -> list[dict]:
    """Megakernel microbench: one descriptor-driven launch over C mixed
    tiles vs C single-shape stacked launches of the same bytes (the
    per-launch overhead the gateway's window pays C times without it).
    Correctness is checked against the jnp oracle per tile."""
    rng = np.random.default_rng(4)
    kk, c = 6, 32
    tn = 16384 if fast else 65536
    coef_rows = rng.integers(0, 256, (c, kk), dtype=np.uint8)
    mc = np.stack(
        [expand_coeff_bitplanes(coef_rows[i][None, :])[0] for i in range(c)]
    )
    data = rng.integers(0, 256, (c, kk, tn), dtype=np.uint8)
    # the megakernel takes source-major (K, C, ...) staging buffers
    mc_k, data_k = mc.transpose(1, 0, 2), data.transpose(1, 0, 2)
    jdata = jnp.asarray(data)
    t_mega = _time(
        lambda _d: ops.gf256_ragged(mc_k, data_k, interpret=True), jdata
    )
    per_tile = [jnp.asarray(data[i]) for i in range(c)]

    def _stacked(_d):
        # return every output so the timer blocks on ALL c launches,
        # not just the last dispatch of an async queue
        return [
            ops.gf256_matmul(coef_rows[i][None, :], per_tile[i],
                             block_n=tn, interpret=True)
            for i in range(c)
        ]

    t_split = _time(_stacked, jdata)
    out = np.asarray(ops.gf256_ragged(mc_k, data_k, interpret=True))
    match = all(
        (out[i] == np.asarray(
            ref.gf256_matmul(jnp.asarray(coef_rows[i][None, :]), per_tile[i])
        )[0]).all()
        for i in range(c)
    )
    return [
        {"bench": "kernel_ragged_decode", "tiles": c, "tile_bytes": tn,
         "megakernel_us": round(t_mega, 1),
         "per_shape_launches_us": round(t_split, 1),
         "launch_amortization": round(t_split / max(t_mega, 1e-9), 2),
         "match": bool(match)}
    ]


def _ragged_encode_rows(fast: bool) -> list[dict]:
    """Encode mirror of the ragged microbench: one descriptor-driven
    ENCODE launch over C mixed parity-generation tiles vs C per-shape
    stacked launches of the same bytes (the write window's launch
    overhead), plus the XOR fold entry — both checked against the host
    oracles the gateway's consistency audits use."""
    rng = np.random.default_rng(6)
    n, k = 9, 6
    c = 32
    tn = 16384 if fast else 65536
    pmat = rs.parity_matrix(n, k)  # (n - k, k)
    coef_rows = np.stack([pmat[i % (n - k)] for i in range(c)])
    mc = np.stack(
        [expand_coeff_bitplanes(coef_rows[i][None, :])[0] for i in range(c)]
    )
    data = rng.integers(0, 256, (c, k, tn), dtype=np.uint8)
    mc_k, data_k = mc.transpose(1, 0, 2), data.transpose(1, 0, 2)
    jdata = jnp.asarray(data)
    t_mega = _time(
        lambda _d: ops.gf256_ragged_encode(mc_k, data_k, interpret=True), jdata
    )
    per_tile = [jnp.asarray(data[i]) for i in range(c)]

    def _stacked(_d):
        return [
            ops.gf256_matmul(coef_rows[i][None, :], per_tile[i],
                             block_n=tn, interpret=True)
            for i in range(c)
        ]

    t_split = _time(_stacked, jdata)
    out = np.asarray(ops.gf256_ragged_encode(mc_k, data_k, interpret=True))
    match = all(
        (out[i] == np.asarray(
            ref.gf256_matmul(jnp.asarray(coef_rows[i][None, :]), per_tile[i])
        )[0]).all()
        for i in range(c)
    )
    # the EV fold entry: stored parity + (old, new) delta pairs
    fold = rng.integers(0, 256, (c, 5, tn), dtype=np.uint8)
    out_x = np.asarray(ops.xor_ragged_encode(fold.transpose(1, 0, 2), interpret=True))
    match_x = all(
        (out_x[i] == np.asarray(ref.xor_parity(jnp.asarray(fold[i])))).all()
        for i in range(c)
    )
    return [
        {"bench": "kernel_ragged_encode", "tiles": c, "tile_bytes": tn,
         "megakernel_us": round(t_mega, 1),
         "per_shape_launches_us": round(t_split, 1),
         "launch_amortization": round(t_split / max(t_mega, 1e-9), 2),
         "match": bool(match and match_x)}
    ]


def check(rows: list[dict]) -> list[str]:
    ok = all(r["match"] for r in rows)
    return [f"kernels: pallas(interpret) == jnp oracle on all sizes: "
            f"{'PASS' if ok else 'FAIL'}"]


if __name__ == "__main__":
    import sys

    from benchmarks.run import describe_device

    print(f"device: {describe_device()}")
    rows = run()
    for r in rows:
        print(r)
    msgs = check(rows)
    print("\n".join(msgs))
    sys.exit(1 if any("FAIL" in m for m in msgs) else 0)
