"""Chip smoke: the CORE gateway's main path, once, on one TPU.

    python chip_smoke.py [--seed N]

Drives the path a user drives, through its entry points:
``ObjectGateway.load_objects`` then ``ObjectGateway.serve``, at the
deployment the paper ran:

  * code: CoreCode(9, 6, 3), the geometry the repo pins for all three
    families (docs/REPRODUCTION.md);
  * block size: 64 MiB, the HDFS block size of the paper's cluster;
  * cluster: 60 nodes, ClusterProfile.network_critical();
  * config: GatewayConfig(verify=True, repair_on_failure=True), every
    other field at its default (ragged decode and encode megakernels,
    autotuned tile widths, measured kernel billing).

Reduced: the object count. A cluster holds far more than 6 objects
(2 CORE groups, 2.25 GiB of user data, 72 coded blocks). The block
width and the code geometry are not reduced.

The trace serves GETs through two node crashes chosen so that both
degraded reads run: the first crash takes one block of a column (a
vertical XOR read, kind "V"), the second takes a second block of the
same column (a horizontal GF(256) read, kind "H"). A few full-row PUTs
run the encode megakernels ("EH", "EV"), and the crashes trigger a
BlockFixer repair at the end of the trace.

The script fails unless every GET's bytes equal the ground truth (the
gateway's own verify), all four kinds launched, and the durability and
parity audits report 0 lost and 0 stale blocks. It refuses to run
without a TPU. Its last line is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

MIB = 1 << 20
BLOCK_BYTES = 64 * MIB
NUM_OBJECTS = 6
NUM_NODES = 60
NUM_GETS = 30
GET_SPACING = 0.0125  # seconds between GET arrivals (simulated clock)
FIRST_CRASH, SECOND_CRASH = 0.1, 0.2
PUTS = ((0.05, 3), (0.25, 4), (0.32, 2))  # (arrival, object id)


def build_trace(gw, seed: int):
    """GETs over every object, full-row PUTs, and two crashes: the nodes
    holding column 0 of group g0's rows 0 and 1. Object 0 (g0, row 0)
    reads vertically after the first crash and horizontally after the
    second."""
    import numpy as np

    from repro.gateway import FailureEvent, Request

    rng = np.random.default_rng(seed + 1)
    gets = [
        Request(time=i * GET_SPACING, object_id=int(oid))
        for i, oid in enumerate(rng.permutation(np.arange(NUM_GETS) % NUM_OBJECTS))
    ]
    # object 0 right after each crash, so both read kinds are certain
    gets += [
        Request(time=FIRST_CRASH + 0.001, object_id=0),
        Request(time=SECOND_CRASH + 0.001, object_id=0),
    ]
    puts = [Request(time=t, object_id=oid, kind="put") for t, oid in PUTS]
    crashes = [
        FailureEvent(time=FIRST_CRASH, node=gw.store.node_of(("g0", 0, 0))),
        FailureEvent(time=SECOND_CRASH, node=gw.store.node_of(("g0", 1, 0))),
    ]
    return sorted(gets + puts, key=lambda r: r.time), crashes


def say(msg: str) -> None:
    print(msg, flush=True)


def run(seed: int, block_bytes: int = BLOCK_BYTES) -> dict:
    """Every phase, in order, printing each one's readings as it ends;
    raises on the first check that fails. Returns the readings."""
    import jax
    import numpy as np

    from repro.core.product_code import CoreCode
    from repro.gateway import GatewayConfig, ObjectGateway
    from repro.kernels import autotune
    from repro.storage.netmodel import ClusterProfile

    code = CoreCode(9, 6, 3)
    out: dict = {}

    t0 = time.perf_counter()
    gw = ObjectGateway(
        code,
        ClusterProfile.network_critical(),
        NUM_NODES,
        GatewayConfig(verify=True, repair_on_failure=True),
    )
    rng = np.random.default_rng(seed)
    objects = rng.integers(0, 256, (NUM_OBJECTS, code.k, block_bytes), dtype=np.uint8)
    gw.load_objects(objects)
    out["load_s"] = time.perf_counter() - t0
    out["blocks_stored"] = len(gw.store.blocks)
    say(
        f"load: {NUM_OBJECTS} objects of {code.k} x {block_bytes / MIB} MiB blocks, "
        f"{out['blocks_stored']} coded blocks stored, {out['load_s']} s"
    )

    # the autotune sweeps the coalescer runs at its first launch: every
    # candidate tile width compiled and timed once, memoized for serve
    t0 = time.perf_counter()
    autotune.tuned_ragged_gf256()
    autotune.tuned_ragged_xor()
    out["warmup_s"] = time.perf_counter() - t0
    tiles = {k: v["block_n"] for k, v in autotune.report().items()}
    say(f"warm-up/compile: autotune sweeps {out['warmup_s']} s, tile widths {tiles}")

    requests, crashes = build_trace(gw, seed)
    t0 = time.perf_counter()
    report = gw.serve(requests, crashes)
    out["serve_s"] = time.perf_counter() - t0

    st = gw.coalescer.stats
    gets = [r for r in report.completed if r.kind == "get"]
    puts = [r for r in report.completed if r.kind == "put"]
    out.update(
        gets=len(gets),
        gets_verified=int(report.metrics.counter_total("verified_gets")),
        puts=len(puts),
        ops_by_kind=dict(st.ops_by_kind),
        launches_by_kind=dict(st.launches_by_kind),
        blocks_repaired=sum(r.blocks_repaired for r in report.repair_reports),
        serve_warmup_s=st.warmup_seconds,
    )
    say(
        f"serve: {len(requests)} requests, {len(crashes)} node crashes, {out['serve_s']} s, "
        f"of which first-sight kernel launches (trace, compile, run) "
        f"{out['serve_warmup_s']} s; "
        f"GETs {out['gets']} ({len(report.degraded_gets)} degraded), "
        f"verified {out['gets_verified']}; PUTs {out['puts']}"
    )
    say(
        f"launches by kind {out['launches_by_kind']}; ops by kind {out['ops_by_kind']}; "
        f"live jit signatures by kind {gw.coalescer.jit_entries_by_kind()}"
    )
    out["durability"] = gw.audit_durability()
    out["parity"] = gw.audit_parity()
    say(
        f"repair: {out['blocks_repaired']} blocks repaired; durability audit "
        f"{out['durability']}; parity audit {out['parity']}"
    )
    stats = jax.devices()[0].memory_stats() or {}
    say(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")

    failures = []
    if out["gets"] != NUM_GETS + 2 or out["gets_verified"] != out["gets"]:
        failures.append(f"{out['gets_verified']} of {out['gets']} GETs verified")
    if out["puts"] != len(PUTS):
        failures.append(f"{out['puts']} of {len(PUTS)} PUTs completed")
    for kind in ("V", "H", "EH", "EV"):
        if not (st.ops_by_kind.get(kind) and st.launches_by_kind.get(kind)):
            failures.append(f"no {kind} launch")
    if out["blocks_repaired"] == 0:
        failures.append("no block repaired")
    if out["durability"]["blocks_lost"] or out["durability"]["missing_blocks"]:
        failures.append(f"durability audit: {out['durability']}")
    if out["parity"]["stale_blocks"] or out["parity"]["corrupt_blocks"]:
        failures.append(f"parity audit: {out['parity']}")
    if failures:
        raise RuntimeError("chip smoke failed: " + "; ".join(failures))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="seed of the object data")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    say(f"device: {device}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this smoke runs only on a TPU", file=sys.stderr)
        return 1

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.kernels.backend import enable_compile_cache

    say(f"compile cache: {enable_compile_cache()}")
    run(args.seed)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
