"""Benchmark driver: one module per paper figure/table (+ kernels and the
serving gateway).

``PYTHONPATH=src python -m benchmarks.run [--full|--fast] [--only fig12,...]``

Prints every row as CSV-ish dicts, then the paper-claim validation
summary (PASS/FAIL per headline claim). --full uses paper-scale sample
counts (slow on 1 CPU); --fast runs only the quick smoke set
(gateway_load + kernels) for the perf trajectory.
"""

from __future__ import annotations

import argparse
import importlib
import sys
import time

MODULES = [
    "resilience",        # Fig 4
    "repair_traffic",    # Fig 5 + 6
    "degraded_read",     # Fig 7 + 8
    "clusters",          # Fig 9
    "recoverability",    # Fig 10
    "scheduling",        # Fig 11 + Table 1
    "repair_e2e",        # Fig 12
    "scheduling_e2e",    # Fig 13
    "kernels",           # Pallas kernels
    "gateway_load",      # serving gateway (throughput / latency / coalescing)
]

FAST_MODULES = ["gateway_load", "kernels"]


def describe_device() -> str:
    """The device every row below ran on, as JAX reports it. No fallback:
    the run uses JAX's default backend, and the Pallas kernels interpret
    only on the CPU (kernels/backend.resolve_interpret)."""
    import jax

    d = jax.devices()[0]
    return f"platform={d.platform} kind={d.device_kind} count={len(jax.devices())}"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sample counts")
    ap.add_argument("--fast", action="store_true",
                    help="quick smoke set only (gateway_load + kernels)")
    ap.add_argument("--only", default=None, help="comma-separated module list")
    args = ap.parse_args()

    if args.only:
        mods = args.only.split(",")
    elif args.fast:
        mods = FAST_MODULES
    else:
        mods = MODULES
    from repro.kernels.backend import enable_compile_cache

    print(f"device: {describe_device()}")
    print(f"compile cache: {enable_compile_cache()}")
    all_checks: list[str] = []
    failed = False
    for name in mods:
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.perf_counter()
        rows = mod.run(fast=not args.full)
        dt = time.perf_counter() - t0
        print(f"\n=== benchmarks.{name} ({dt:.1f}s) " + "=" * 40)
        for r in rows:
            print(",".join(f"{k}={v}" for k, v in r.items()))
        if hasattr(mod, "write_bench"):
            # machine-readable perf snapshot (BENCH_<name>.json) so the
            # trajectory is tracked across PRs
            mod.write_bench(rows)
            print(f"wrote {mod.BENCH_PATH}")
        if hasattr(mod, "check"):
            msgs = mod.check(rows)
            all_checks.extend(msgs)

    print("\n" + "=" * 70)
    print("PAPER-CLAIM VALIDATION SUMMARY")
    print("=" * 70)
    for m in all_checks:
        print(" ", m)
        if "FAIL" in m:
            failed = True
    print("=" * 70)
    print("OVERALL:", "FAIL" if failed else "PASS")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
