"""Wall-clock spans of the host path, on the profiler's clock.

Each span is a ``jax.profiler.TraceAnnotation``: it records only while a
profiler trace is active (``jax.profiler.start_trace``), on the calling
thread's host line and on the same clock as the device's ops, so a
trace reduction can put every idle gap of the device down to the host
step that was running. With no trace active a span costs one inactive
``TraceMe`` (about a microsecond) and encodes none of its attrs. There
is no switch: tracing the process is what turns the spans on.

Spans sit at block, launch or phase granularity, never inside a
per-tile loop and never inside a jitted function. They nest on their
thread, which gives each span its parent. Attrs (``object_id``,
``group``, ``key``, ``kind``, ``tiles``, ``bytes``) ride as the
annotation's keyword arguments.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

# Every span name, with the step it times. A name's prefix names its
# layer: gw. (gateway), fabric. (simulated network), store. (block
# store), stage. (host staging of the ragged kernels), kernel. (device
# dispatch and wait), repair. (BlockFixer and the repair sweep).
SPANS: dict[str, str] = {
    "gw.serve": "ObjectGateway.serve, the whole event loop; self time is the loop's own Python",
    "gw.plan": "a GET window's planning and SLO admission",
    "gw.fetch": "a GET window's store reads, replans and hedges",
    "gw.decode": "a GET window's decode through the coalescer, as a whole; attr plan: its ops' plan kinds",
    "gw.handoff": "the payload hand-off: its k blocks, read in place, to verify and its sha256",
    "gw.load": "ObjectGateway.load_objects, the whole bulk load of the set-up",
    "gw.load.encode": "one group's upload and its encode of the blocks the code computes, to the end of the device's work",
    "gw.load.d2h": "device-to-host copy of one group's computed blocks (its data blocks stay the caller's)",
    "fabric.transfer": "one simulated-fabric transfer's bookkeeping (host cost of the simulation)",
    "store.crc32": "one crc32 digest of a block, with its chunks' pool work (the integrity plane)",
    "stage.gather": "zero-fill and gather of one ragged launch's staging buffers",
    "stage.h2d": "host-to-device copy of one ragged launch's operands",
    "kernel.run": "dispatch of one kernel launch and the wait for its result",
    "kernel.warmup": "a first-sight (unbilled) launch: trace, compile and run",
    "stage.d2h": "device-to-host copy of one ragged launch's result",
    "stage.scatter": "scatter of one ragged launch's result tiles into the output rows",
    "repair.sweep": "crc32 check of a group's surviving blocks before its rebuild",
    "repair.gather": "one repair step's source blocks, collected from the store by reference",
    "repair.h2d": "host-to-device copy of one repair step's sources, block by block, never stacked",
    "repair.d2h": "device-to-host copy of one repair step's rebuilt blocks",
    "repair.writeback": "put_block of one rebuilt block, with its new digest",
}


def span(name: str, **attrs) -> TraceAnnotation:
    """Context manager timing the host step ``name`` (a key of SPANS)."""
    return TraceAnnotation(name, **attrs)
