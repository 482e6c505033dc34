"""The program's wall-clock spans and crc32 byte counter over a traced
window, beside the harness's own traced run.

The harness reads its device numbers from the profiler trace of the
window (trace_reduce.py) and throws the trace away. This file runs the
same traced run with two readers added, and changes nothing the run
does or reports:

  * the trace's events are kept, and ``span_ns`` gives each span name
    (a harness ``bench.`` span, or a program span of
    ``repro.obs.host.SPANS``, known by its prefix) the window time in
    which it is the innermost open span on the thread that ran the
    window. Runtime events (``np.asarray(jax.Array)``, ``PjitFunction``)
    are not spans: they count toward the span around them, so the
    values sum to the window;
  * each window unit's crc32 bytes (``BlockStore.crc32_bytes``, read
    before and after the unit) and the payload or restored bytes it
    delivered.

``READINGS`` are the shares and ratios these give, named as per-layer
metrics would be; ``breakdown`` computes them.

    python3 benchmarks/chip/span_breakdown.py --workload <cell> \\
        --seed <n> --seconds <s> [--control]

runs the cell's traced run through run.py (a TPU is needed, as there),
prints run.py's result line, then one JSON line with the breakdown.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import statistics
import sys

import harness
import trace_reduce

# the harness's spans, then the program's layers (repro.obs.host)
PREFIXES = ("bench.", "gw.", "fabric.", "store.", "stage.", "kernel.", "repair.")
HARNESS = ("bench.window", "bench.unit", "bench.serve")
STAGING = ("stage.gather", "stage.h2d", "stage.d2h", "stage.scatter")
GATEWAY = ("gw.serve", "gw.plan", "gw.fetch", "gw.decode", "fabric.transfer")
REPAIR_HOST = (
    "repair.sweep", "repair.gather", "repair.h2d", "repair.d2h", "repair.writeback",
)
# share of the window (%) in which one of the spans is the innermost
READINGS = {
    "integrity_share.read": ("store.crc32",),
    "integrity_share.repair": ("store.crc32",),
    "staging_share.read": STAGING,
    "handoff_share.read": ("gw.handoff",),
    "gateway_share.read": GATEWAY,
    "gateway_share.repair": GATEWAY,
    "repair_host_share.repair": REPAIR_HOST,
}
# besides: crc32_bytes_per_byte.read (crc32 bytes over GET payload bytes
# delivered) and crc32_bytes_per_byte.repair (over bytes restored)


def span_name(name: str) -> str:
    """An event's name without TraceMe's ``#key=value#`` metadata."""
    return name.split("#", 1)[0]


def thread_spans(events) -> tuple[int, int, list]:
    """(window start, window end, spans) of the thread that ran the
    window: spans as (start, end, name), outer before inner."""
    windows = [
        (s, s + d, (plane, line))
        for plane, line, name, s, d in events
        if name == trace_reduce.WINDOW_SPAN and not trace_reduce.is_device_plane(plane)
    ]
    if not windows:
        raise ValueError(f"no {trace_reduce.WINDOW_SPAN!r} span in the trace")
    w0, w1, thread = windows[0]
    spans = sorted(
        (
            (s, s + d, span_name(name))
            for plane, line, name, s, d in events
            if (plane, line) == thread and d > 0 and span_name(name).startswith(PREFIXES)
        ),
        key=lambda ev: (ev[0], -ev[1]),
    )
    return w0, w1, spans


def self_ns(spans: list, a: int, b: int) -> dict:
    """Time of [a, b) in which each span name is the innermost open span
    (spans nest; a child is cut at its parent's end). Time under no span
    goes to ``None``."""
    out: dict = {}
    stack: list[list] = []  # [end, name], innermost last
    at = a

    def upto(t):
        nonlocal at
        if t > at:
            name = stack[-1][1] if stack else None
            out[name] = out.get(name, 0) + t - at
            at = t

    for s, e, name in spans:
        s, e = max(s, a), min(e, b)
        if e <= s:
            continue
        while stack and stack[-1][0] <= s:
            upto(stack[-1][0])
            stack.pop()
        upto(s)
        stack.append([min(e, stack[-1][0]) if stack else e, name])
    while stack:
        upto(stack[-1][0])
        stack.pop()
    upto(b)
    return out


def span_ns(events) -> dict:
    """Window ns per span name (innermost); sums to the window."""
    w0, w1, spans = thread_spans(events)
    return self_ns(spans, w0, w1)


def share(ns: dict, names) -> float | None:
    """100 x the window time of ``names`` over the window, or None where
    none of them appears."""
    if not any(n in ns for n in names):
        return None
    return 100.0 * sum(ns.get(n, 0) for n in names) / sum(ns.values())


@dataclasses.dataclass
class UnitCount:
    index: int
    crc32_bytes: int
    payload_bytes: int
    restored_bytes: int
    damaged: list  # block keys the unit destroyed or corrupted


@dataclasses.dataclass
class Kept:
    events: list | None = None
    units: list = dataclasses.field(default_factory=list)  # window units


@contextlib.contextmanager
def kept():
    """Within: harness.run_cell keeps its trace's events and counts each
    window unit's crc32 and delivered bytes into the yielded ``Kept``."""
    k = Kept()
    execute, load_events = harness.execute, trace_reduce.load_events

    def counted(gw, mix, i):
        before = gw.store.crc32_bytes
        res = execute(gw, mix, i)
        if i >= mix.warmup_count():
            k.units.append(
                UnitCount(
                    i,
                    gw.store.crc32_bytes - before,
                    sum(gw.code.k * gw.meta.block_bytes for _o, d in res.gets if d is not None),
                    sum(arr.nbytes for _key, arr in res.restored if arr is not None),
                    [key for key, _arr in res.restored],
                )
            )
        return res

    def keep_events(trace_dir):
        k.events = load_events(trace_dir)
        return k.events

    harness.execute, trace_reduce.load_events = counted, keep_events
    try:
        yield k
    finally:
        harness.execute, trace_reduce.load_events = execute, load_events


def breakdown(k: Kept, unit: str) -> dict:
    """The span breakdown of a kept run; ``unit`` is ``read`` for a GET
    cell, ``repair`` for a node-repair one."""
    w0, w1, spans = thread_spans(k.events)
    ns = self_ns(spans, w0, w1)
    readings = {
        name: share(ns, names)
        for name, names in READINGS.items()
        if name.endswith("." + unit)
    }
    crc = sum(u.crc32_bytes for u in k.units)
    done = sum(u.payload_bytes if unit == "read" else u.restored_bytes for u in k.units)
    readings[f"crc32_bytes_per_byte.{unit}"] = crc / done if done else None
    units = [(e - s, s, e) for s, e, name in spans if name == "bench.unit" and s >= w0]

    def top(a, b):
        return sorted(
            ((name, 1e-9 * v) for name, v in self_ns(spans, a, b).items()),
            key=lambda kv: -kv[1],
        )

    slowest = max(units, default=None)
    return {
        "window_s": 1e-9 * (w1 - w0),
        "spans_s": top(w0, w1),
        "readings": readings,
        "harness_share": share(ns, HARNESS),
        "crc32_bytes": crc,
        "delivered_bytes": done,
        "unit_s_median": 1e-9 * statistics.median(u[0] for u in units) if units else None,
        "slowest_unit": (
            {"unit_s": 1e-9 * slowest[0], "spans_s": top(slowest[1], slowest[2])}
            if slowest
            else None
        ),
    }


def main(argv=None) -> int:
    import run

    argv = sys.argv[1:] if argv is None else argv
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    traffic = harness.cell_spec(bench, run.parse(argv).workload)[2]
    with kept() as k:
        rc = run.main([*argv, "--trace", "1"])
    if rc:
        return rc
    unit = "read" if traffic["unit"] == "get" else "repair"
    print(json.dumps(breakdown(k, unit)), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)  # as run.py: no teardown of the store and the TPU runtime
