"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is reduced from a flat list of events, ``(plane, line, name,
start_ns, duration_ns)``, so the arithmetic can be checked on a small
recorded list (testdata/) without a chip:

  * the window is the host span ``bench.window`` the harness opens;
  * busy time is the union of the op intervals on each TPU plane's
    ``XLA Ops`` line, clipped to the window and averaged over the chips
    used; the idle share is 1 - busy / window;
  * a codec's device time is the summed duration of its jitted modules
    on the ``XLA Modules`` line, matched by name fragments that each
    metric's own file lists;
  * the breakdown's device ops are named by their HLO instruction; its
    idle gaps are summed by the innermost host event open at each gap's
    midpoint on the thread that ran the window (the harness's ``bench.``
    spans and whatever the runtime traces inside them).
"""

from __future__ import annotations

import dataclasses
import pathlib

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def load_events(trace_dir) -> list[tuple]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax

    paths = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(str(paths[-1]))
    return [
        (plane.name, line.name, ev.name, int(ev.start_ns), int(ev.duration_ns))
        for plane in data.planes
        for line in plane.lines
        for ev in line.events
    ]


def is_device_plane(plane: str) -> bool:
    return plane.startswith("/device:TPU:")


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # averaged over the device planes
    devices: int
    module_ns: dict  # module name -> summed device ns (all planes)
    top_ops: list  # [[name, seconds], ...] largest first
    idle_gaps: list  # [[host span, seconds], ...] largest first

    def idle_percent(self) -> float | None:
        if self.devices == 0 or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def module_seconds(self, fragments) -> float:
        """Device seconds of the modules whose names hold any fragment."""
        return 1e-9 * sum(
            ns
            for name, ns in self.module_ns.items()
            if any(f in name for f in fragments)
        )


def op_name(hlo: str) -> str:
    """``%fusion.75 = u8[...] fusion(...)`` -> ``fusion.75``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def reduce_events(events: list[tuple]) -> Reduced:
    windows = [
        (s, s + d, (plane, line))
        for plane, line, name, s, d in events
        if name == WINDOW_SPAN and not is_device_plane(plane)
    ]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0, w1, thread = windows[0]
    host = sorted(
        (
            (s, s + d, name)
            for plane, line, name, s, d in events
            if (plane, line) == thread and d > 0
        ),
        key=lambda ev: (ev[0], -ev[1]),  # an outer event before the inner
    )
    per_plane: dict[str, list[tuple[int, int]]] = {}
    module_ns: dict[str, int] = {}
    op_ns: dict[str, int] = {}
    for plane, line, name, s, d in events:
        if not is_device_plane(plane):
            continue
        lo, hi = max(s, w0), min(s + d, w1)
        if hi <= lo:
            continue
        if line == OPS_LINE:
            per_plane.setdefault(plane, []).append((lo, hi))
            op = op_name(name)
            op_ns[op] = op_ns.get(op, 0) + (hi - lo)
        elif line == MODULES_LINE:
            module_ns[name] = module_ns.get(name, 0) + (hi - lo)
    busy = {p: _union(iv) for p, iv in per_plane.items()}
    n_dev = len(busy)
    busy_ns = sum(e - s for iv in busy.values() for s, e in iv)
    gaps: dict[str, int] = {}
    for iv in busy.values() if busy else [[]]:
        edges = [w0] + [x for s, e in iv for x in (s, e)] + [w1]
        pairs = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        labels = _labels([(a + b) // 2 for a, b in pairs], host)
        for (a, b), label in zip(pairs, labels):
            gaps[label] = gaps.get(label, 0) + (b - a)
    return Reduced(
        window_s=1e-9 * (w1 - w0),
        busy_s=1e-9 * busy_ns / n_dev if n_dev else 0.0,
        devices=n_dev,
        module_ns=module_ns,
        top_ops=_top(op_ns),
        idle_gaps=_top({k: v / max(n_dev, 1) for k, v in gaps.items()}),
    )


def _labels(points: list[int], host: list[tuple]) -> list[str]:
    """Innermost host event open at each of the ascending ``points``;
    ``host`` is one thread's events sorted by start, which nest."""
    out, stack, j = [], [], 0
    for p in points:
        while j < len(host) and host[j][0] <= p:
            while stack and stack[-1][1] <= host[j][0]:
                stack.pop()
            stack.append(host[j])
            j += 1
        while stack and stack[-1][1] <= p:
            stack.pop()
        out.append(stack[-1][2] if stack else "outside host events")
    return out


def _top(ns_by_name: dict) -> list:
    ranked = sorted(ns_by_name.items(), key=lambda kv: -kv[1])[:TOP]
    return [[name, 1e-9 * ns] for name, ns in ranked]
