"""One run of one cell: set up, warm up, drive the window, check, report.

``run_cell`` is the whole run after the command's look for a chip; the
CPU tests call it directly at small block sizes. Everything that belongs
to one configuration, traffic mix or per-layer metric is found by name:

  * configurations: the file BENCHMARK.json names for the config;
  * traffic mixes: ``traffic/<mix>.json``, read by traffic_gen.Mix;
  * per-layer metrics: ``metrics/<metric>.py``, each with ``read(r)``
    returning a number or None (nothing to read).

The window is closed-loop: whole units (``ObjectGateway.serve`` calls)
that start inside ``seconds``; a rate is the bytes of those units over
the wall time from the window's start to the end of its last unit.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import codec_bytes
import reference
import trace_reduce
from traffic_gen import Mix

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
GIB = float(1 << 30)
# threads for the comparison after the window (hashlib and numpy's
# element-wise loops run without the interpreter lock)
CHECK_THREADS = 6

# Every compared number is a count that a sound run holds at 0: an
# exact comparison, so each limit is 0.
LIMITS = {
    "gets_wrong": 0,
    "gets_missing": 0,
    "blocks_wrong": 0,
    "blocks_not_restored": 0,
    "blocks_missing": 0,
    "blocks_lost": 0,
    "unreadable_objects": 0,
    "unit_errors": 0,
}


def load_json(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def cell_spec(bench: dict, name: str) -> tuple[dict, dict, dict]:
    """(cell, configuration, traffic mix) of the cell called ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT / cfg_entry["file"])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return cell, config, traffic


def metrics_for(bench: dict, cell: dict, section: str) -> list[dict]:
    """The ``section`` metrics this cell reports: those listing it, and
    those with no list (for a per-layer metric: whose ``moves`` metric
    the cell reports)."""
    e2e = [m for m in bench["end_to_end"] if cell["name"] in m.get("workloads", [cell["name"]])]
    if section == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [
        m
        for m in bench[section]
        if (cell["name"] in m["workloads"] if "workloads" in m else m["moves"] in moved)
    ]


def make_objects(seed: int, count: int, k: int, block_bytes: int) -> np.ndarray:
    """(count, k, block_bytes) uint8 object data from the seed, drawn as
    64-bit words in bulk (PCG64DXSM: twice PCG64's rate on this loop)."""
    if block_bytes % 8:
        raise ValueError("block_bytes must be a multiple of 8")
    gen = np.random.PCG64DXSM(np.random.SeedSequence([seed, 0]))
    words = gen.random_raw(count * k * block_bytes // 8)
    return words.view(np.uint8).reshape(count, k, block_bytes)


def build_gateway(config: dict, traffic: dict, control: bool):
    from repro.core.product_code import CoreCode
    from repro.gateway import GatewayConfig, ObjectGateway
    from repro.storage.netmodel import ClusterProfile

    code = CoreCode(config["n"], config["k"], config["t"])
    settings = dict(config["gateway"])
    settings.update(traffic.get("gateway", {}))
    if control:
        # the control breaks the configuration's integrity guarantee:
        # no crc32 check on read, on decode output, or before a rebuild
        settings["verify_checksums"] = False
    profile = getattr(ClusterProfile, config["cluster_profile"])()
    return ObjectGateway(code, profile, config["nodes"], GatewayConfig(**settings))


@dataclasses.dataclass
class UnitResult:
    gets: list  # (object id, payload digest or None)
    restored: list  # (block key, array held after the unit, or None)
    blocks_fetched: int = 0
    blocks_repaired: int = 0
    error: str | None = None


def execute(gw, mix: Mix, i: int) -> UnitResult:
    """Generate unit ``i`` and serve it."""
    from jax.profiler import TraceAnnotation

    try:
        unit = mix.unit(i)
        with TraceAnnotation("bench.serve"):
            report = gw.serve(unit.requests, unit.events)
    except Exception as exc:  # a unit that raises is a failed unit, reported
        return UnitResult([], [], error=f"unit {i}: {exc!r}")
    with TraceAnnotation("bench.record"):
        gets = [
            (r.object_id, r.payload_digest if r.latency is not None else None)
            for r in report.records
            if r.kind == "get"
        ]
        missing = collections.Counter(unit.gets) - collections.Counter(g[0] for g in gets)
        gets += [(oid, None) for oid in missing.elements()]
        store = gw.store
        restored = [
            (key, store.blocks.get(key) if store.available(key) else None)
            for key in unit.damaged
        ]
        reps = report.repair_reports
        return UnitResult(
            gets,
            restored,
            sum(r.blocks_fetched for r in reps),
            sum(r.blocks_repaired for r in reps),
        )


class Reading:
    """What a per-layer metric reader may read."""

    def __init__(self, cell, device_kind, trace, work, counters, window_s):
        self.cell = cell
        self.device_kind = device_kind
        self.trace = trace  # trace_reduce.Reduced, or None
        self.work = work  # algorithm bytes by codec, codec_bytes.py
        self.counters = counters  # program counters over the window
        self.window_s = window_s

    @property
    def peaks(self) -> dict:
        import peaks

        return peaks.peaks_for(self.device_kind)


def read_metric(name: str, reading: Reading):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(reading)


def run_cell(
    bench: dict,
    name: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t0: float,
    control: bool = False,
    overrides: dict | None = None,
    log=sys.stderr,
) -> dict:
    """The whole run after the look for a chip; returns the result line."""
    import jax
    from jax.profiler import TraceAnnotation

    cell, config, traffic = cell_spec(bench, name)
    config = {**config, **(overrides or {})}
    block = int(config["block_bytes"])
    n, k, t = config["n"], config["k"], config["t"]
    family = config["gateway"]["code_family"]

    def say(msg):
        print(f"[{name} seed={seed}] {msg}", file=log, flush=True)

    gw = build_gateway(config, traffic, control)
    t_gen = time.perf_counter()
    objects = make_objects(seed, config["objects"], k, block)
    t_load = time.perf_counter()
    gw.load_objects(objects)
    mix = Mix(traffic, gw, seed)
    gw.serve([], mix.setup_events())
    t_warm = time.perf_counter()
    warm = mix.warmup_count()
    for i in range(warm):
        res = execute(gw, mix, i)
        if res.error:
            raise RuntimeError(f"warm-up failed: {res.error}")
    say(
        f"set-up: data {t_load - t_gen:.3f} s, load and faults {t_warm - t_load:.3f} s, "
        f"warm-up ({warm} units) "
        f"{time.perf_counter() - t_warm:.3f} s"
    )

    st = gw.coalescer.stats
    launches0 = sum(v for kk, v in st.launches_by_kind.items() if not kk.startswith("E"))
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    devs = jax.local_devices()[: cell["chips"]]

    def in_use() -> int:
        """Device bytes held now, on the fullest chip."""
        return max((d.memory_stats() or {}).get("bytes_in_use", 0) for d in devs)

    in_use_start = in_use()
    results: list[UnitResult] = []
    unit_s: list[float] = []
    i = warm
    t_start = t_end = time.perf_counter()
    with TraceAnnotation(trace_reduce.WINDOW_SPAN):
        while t_end - t_start < seconds:
            with TraceAnnotation("bench.unit"):
                res = execute(gw, mix, i)
            results.append(res)
            now = time.perf_counter()
            unit_s.append(now - t_end)
            t_end = now
            i += 1
            if res.error:
                break
    in_use_end = in_use()
    t_closed = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    t_traced = time.perf_counter()
    elapsed = t_end - t_start
    setup_s = t_start - t0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    launches = (
        sum(v for kk, v in st.launches_by_kind.items() if not kk.startswith("E"))
        - launches0
    )
    audit = gw.audit_durability()
    groups = {gid: list(m) for gid, m in gw.meta.groups.items()}
    placed = dict(gw.meta.objects)
    # blocks of the store not readable once the window's repairs are done
    missing_end = sum(
        not gw.store.available((gid, r, c))
        for gid in groups
        for r in range(gw.family.rows)
        for c in range(n)
    )
    lost_by_group = mix.lost_by_group()
    del gw, mix

    # -- the comparison, against the plain reference -----------------------
    t_ref = time.perf_counter()
    check = {"unit_errors": sum(1 for r in results if r.error)}
    gets = [g for r in results for g in r.gets]
    restored = [b for r in results for b in r.restored]
    if traffic["unit"] == "get":
        oids = sorted({oid for oid, _d in gets})
        with ThreadPoolExecutor(CHECK_THREADS) as pool:
            digests = pool.map(
                lambda oid: hashlib.sha256(objects[oid].reshape(-1)).hexdigest(), oids
            )
            want = dict(zip(oids, digests))
        missing = sum(d is None for _oid, d in gets)
        wrong = sum(d is not None and d != want[oid] for oid, d in gets)
        check.update(gets_wrong=wrong, gets_missing=missing)
        attempted, failed = len(gets), wrong + missing
    else:
        held: dict[tuple, list] = {}
        for key, arr in restored:
            if arr is not None:
                held.setdefault(key, []).append(arr)
        reference.rs_generator(n, k)

        def wrong_copies(key) -> int:
            gid, row, col = key
            members = [objects[o] for o in groups[gid]]
            want = reference.expected_block(
                family, n, k, t, members, row if family == "core" else 0, col
            )
            return sum(not np.array_equal(arr, want) for arr in held[key])

        with ThreadPoolExecutor(CHECK_THREADS) as pool:
            wrong = sum(pool.map(wrong_copies, held))
        not_restored = sum(arr is None for _key, arr in restored)
        check.update(
            blocks_wrong=wrong, blocks_not_restored=not_restored, blocks_missing=missing_end
        )
        attempted, failed = len(restored), wrong + max(not_restored, missing_end)
    t_checked = time.perf_counter()
    check.update(
        blocks_lost=audit["blocks_lost"], unreadable_objects=audit["unreadable_objects"]
    )
    correct = all(check[c] <= LIMITS[c] for c in check)
    failed += check["unit_errors"]
    for r in results:
        if r.error:
            say(f"error: {r.error}")

    # -- metrics ----------------------------------------------------------------
    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devs),
        "memory_peak_bytes": int(peak),
        # the peak is the process's, set by the load's encode; what the
        # window itself holds on the chip is read at its two ends
        "window_bytes_in_use": [int(in_use_start), int(in_use_end)],
    }
    payload_bytes = sum(k * block for _oid, d in gets if d is not None)
    restored_bytes = sum(block for _key, arr in restored if arr is not None)
    e2e = {
        "read_gibps": payload_bytes / GIB / elapsed,
        "repair_gibps": restored_bytes / GIB / elapsed,
        "setup_s": setup_s,
    }
    result = {"correct": bool(correct), "attempted": attempted, "failed": failed}
    if not trace:
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in metrics_for(bench, cell, "end_to_end")
        }
    else:
        events = trace_reduce.load_events(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        reduced = trace_reduce.reduce_events(events)
        work: dict[str, int] = {}
        for oid, digest in gets:
            if digest is not None:
                gid, row = placed[oid]
                ops = codec_bytes.decode_ops(family, k, t, lost_by_group[gid])
                codec_bytes.add_bytes(work, "read", ops.get(row, []), block)
        for r in results:
            by_group: dict[str, dict] = {}
            for (gid, row, col), _arr in r.restored:
                by_group.setdefault(gid, {}).setdefault(row, set()).add(col)
            for lost in by_group.values():
                for ops in codec_bytes.decode_ops(family, k, t, lost).values():
                    codec_bytes.add_bytes(work, "repair", ops, block)
        counters = {
            "decode_launches": launches,
            "payload_bytes": payload_bytes,
            "repair_blocks_fetched": sum(r.blocks_fetched for r in results),
            "repair_blocks_repaired": sum(r.blocks_repaired for r in results),
        }
        reading = Reading(cell, dev.device_kind, reduced, work, counters, elapsed)
        result["metrics"] = {}
        for m in metrics_for(bench, cell, "per_layer"):
            value = read_metric(m["name"], reading)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        result["breakdown"] = {
            "device_ops": reduced.top_ops,
            "idle_gaps": reduced.idle_gaps,
        }
    result["device"] = device
    result["check"] = {c: {"value": v, "limit": LIMITS[c]} for c, v in check.items()}
    say(f"window: {len(results)} units in {elapsed:.3f} s; setup_s {setup_s:.3f}")
    say("unit seconds: " + " ".join(f"{u:.3f}" for u in unit_s))
    say(
        f"after the window: trace stop {t_traced - t_closed:.3f} s, audit "
        f"{t_ref - t_traced:.3f} s, reference {t_checked - t_ref:.3f} s, "
        f"metrics {time.perf_counter() - t_checked:.3f} s; device bytes in use "
        f"{in_use_start} at the window's start, {in_use_end} at its end"
    )
    return result
