"""Chip benchmark: one run of one cell of BENCHMARK.json.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs only on a TPU with as many chips as the cell asks for; anywhere
else it exits with code 2 and prints no result. With ``--trace 0`` the
result's metrics are the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics, read from a profiler trace of the window. The
last line of standard output is the result as one JSON object; the last
lines of standard error give each compared number beside its limit.

``--control`` runs the comparison's control instead of the program as
configured (the integrity check switched off); the benchmark's own runs
never pass it.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"run.py: no workload {args.workload!r}", file=sys.stderr)
        return 2
    # JAX's persistent compilation cache lives in the checkout, at a fixed
    # path, so every later run of a cell there skips its compiles
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".cache" / "jax")
    # the TPU runtime otherwise logs to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    chips = cells[args.workload]["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(
            f"run.py: the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)",
            file=sys.stderr,
        )
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    from repro.kernels.backend import enable_compile_cache

    import harness

    enable_compile_cache()
    result = harness.run_cell(
        bench,
        args.workload,
        args.seed,
        args.seconds,
        bool(args.trace),
        t0=T0,
        control=args.control,
    )
    for name, c in result["check"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # Leave without the interpreter's teardown: freeing the store's host
    # blocks and shutting the TPU runtime down cost seconds after the
    # result is out. The run starts no other process.
    os._exit(rc)
