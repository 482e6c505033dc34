"""Observability plane: sim-time spans, streaming metrics, Perfetto
export and critical-path accounting for the gateway stack, and the
wall-clock spans of its host path (``repro.obs.host``).

Everything but ``repro.obs.host`` runs over the SIMULATED clock — spans
measure simulated seconds, not wall time — and is observation-only by
contract: enabling tracing never changes event ordering, simulated
timestamps, or payload bytes (tests/test_obs.py pins traced ≡ untraced
fingerprints).

Span taxonomy
=============

Request traces (one per completed GET/PUT; ``trace_id`` doubles as the
root span id so children parent on it before the root is finalized):

  ``request``        root span [arrival, completion]; attrs: object_id,
                     kind, tenant, degraded, bytes, cache_hits, fetch_at
  ``plan``           instant at plan time; attrs: degraded, sources,
                     decodes (instants for admission estimate too)
  ``fetch``          one per fabric-fetched source block
                     [fetch start, block landed]; attrs: key, src, bytes
  ``cache.hit``      instant per cache-served source block
  ``decode``         one per (request, decode op): the launch interval
                     that completed the op [engine start, engine end];
                     attrs: kind, launch_id, fraction, tiles, op (window
                     op index), shared (co-owning requests),
                     op_ready (own sources landed),
                     ready (launch-wide source barrier)
  ``verify``         instant at delivery (ground-truth check, 0 sim cost)
  ``hedge``          one per speculative alternate-path fetch racing a
                     slow direct fetch [hedge launch, last hedge source
                     landed]; attrs: key, kind (V|H), won, attempt
  ``corrupt``        instant at digest-mismatch detection (corruption
                     reclassified as an erasure); attrs: key, source
                     (read | scrub | write | repair)

Infrastructure tracks (emitted into whichever request/repair trace
caused the work):

  ``xfer``           fabric transfer [first byte, last byte] on the
                     SOURCE port's track; attrs: src, dst, bytes,
                     tenant, wait (queueing before the first quantum)
  ``engine.launch``  engine occupancy [start, end] on the engine's track

Repair traces (one per background-repair run):

  ``repair.run``     root span over the run; attrs: groups, healed
  ``pacing``         instant per closed-loop share decision; attrs:
                     share, observed_p99, pressure
  ``repair.group``   one group's fix [detection, fabric makespan];
                     attrs: group, mode, blocks_repaired, recovered
  ``repair.fetch``   one repair step's source gathering; attrs: kind,
                     blocks
  ``repair.decode``  the repair's decode billing on the engine pool
  ``repair.heal``    instant when a block becomes readable again

Scrub traces (one per background scrub tick, on the repair track):

  ``scrub.run``      root span over the tick; attrs: scanned, found
                     (``corrupt`` instants for blocks it catches parent
                     on it)

Track layout (Perfetto: one process per group, one thread per member):

  ``("tenant", <tenant>)``   request roots + per-request stages
  ``("engine", engine<i>)``  decode-engine occupancy
  ``("fabric", port<n>)``    per-send-port transfers
  ``("repair", repair)``     background repair activity

Sampling: ``Tracer(sample=...)`` takes ``"always"``, ``"head:N"``,
``"tail:SECONDS"`` or comma-combinations (keep if ANY matches), so
tail-latency traces are never dropped while steady-state traffic can be
heavily sampled. Spans land in a bounded ring buffer (``capacity``).

Metrics: ``MetricsRegistry`` (labeled counters / gauges / log-binned
histograms), ``P2Quantile``, ``StreamHist``, and the list-compatible
``BoundedSamples`` / ``BoundedLog`` that replaced ``GatewayReport``'s
unbounded per-request lists — resident memory stays O(1) in trace
length.

Analysis: ``critical_path`` cuts one request's latency into additive
stages (admission / fetch / batch_wait / engine_wait / decode /
deliver); ``stage_shares`` aggregates a run into shares summing to 1.0;
``launch_amortization`` reports how ops shared physical launches.
Export: ``write_chrome_trace`` / ``validate_chrome_trace`` produce and
check Perfetto-loadable JSON (see examples/gateway_serving.py --trace).

Wall-clock spans
================

``repro.obs.host.span(name, **attrs)`` is a ``jax.profiler.TraceAnnotation``:
WALL-clock time on the profiler's host line, the clock of the device's
ops, recorded only while a profiler trace is active (no switch of its
own). Nesting on the thread gives each span its parent; a reduction
gives each name the window time in which it is the innermost span.
``repro.obs.host.SPANS`` holds every name:

  ``gw.serve``          ObjectGateway.serve, whole body (self time: the
                        event loop's own Python)
  ``gw.plan``           a GET window's planning and admission loop
  ``gw.fetch``          a GET window's fetch/replan loop (store reads,
                        replans, hedging)
  ``gw.decode``         the window's decode, around coalescer.execute
  ``gw.handoff``        the payload's k blocks, in place, to verify
                        and its sha256
  ``fabric.transfer``   NetSimulator.transfer (host cost of the
                        simulated fabric)
  ``store.crc32``       BlockStore.digest, the one crc32 site, with the
                        wait for its chunks' crc32s on the digest pool
  ``stage.gather``      a ragged launch's zero-fill and gather
  ``stage.h2d``         a ragged launch's host-to-device uploads
  ``kernel.run``        a launch's dispatch up to block_until_ready
                        (ragged entries and BlockFixer's jits)
  ``kernel.warmup``     the coalescer's unbilled first-sight launch (a
                        compile inside a traced window shows here)
  ``stage.d2h``         a ragged launch's device-to-host copy
  ``stage.scatter``     a ragged launch's scatter into output rows
  ``repair.sweep``      the crc32 check of a group's survivors before
                        its rebuild (children: ``store.crc32``)
  ``repair.gather``     BlockFixer's collection of a step's sources
                        from the store, by reference
  ``repair.h2d``        BlockFixer's host-to-device copy, block by
                        block, and the stack on the device
  ``repair.d2h``        BlockFixer's device-to-host copy
  ``repair.writeback``  put_block of a rebuilt block (children:
                        ``store.crc32``)
"""

from repro.obs.critical_path import (
    PathBreakdown,
    STAGES,
    critical_path,
    launch_amortization,
    stage_shares,
)
from repro.obs.export import (
    to_chrome_trace,
    validate_chrome_trace,
    write_chrome_trace,
)
from repro.obs.metrics import (
    BoundedLog,
    BoundedSamples,
    Counter,
    Gauge,
    MetricsRegistry,
    P2Quantile,
    StreamHist,
)
from repro.obs.tracer import NULL_TRACER, Span, Tracer

__all__ = [
    "NULL_TRACER",
    "STAGES",
    "BoundedLog",
    "BoundedSamples",
    "Counter",
    "Gauge",
    "MetricsRegistry",
    "P2Quantile",
    "PathBreakdown",
    "Span",
    "StreamHist",
    "Tracer",
    "critical_path",
    "launch_amortization",
    "stage_shares",
    "to_chrome_trace",
    "validate_chrome_trace",
    "write_chrome_trace",
]
