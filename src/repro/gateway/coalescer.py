"""Request coalescer: execute a window's degraded-read decodes in as few
Pallas launches as the shape mix allows.

**Ragged megakernel.** A realistic mixed-tenant window holds decodes of
MIXED shapes — horizontal RS ops with varying target counts, vertical
XOR repairs, ragged byte lengths. The coalescer stages the WHOLE window
per kind: every decode row (one output row of one op) is cut into fixed-
width tiles (width autotuned, capped to the longest row), gathered into
a preallocated staging buffer ``(K, C, TN)`` with a per-tile descriptor
(op id, coefficient bit-planes, byte offset, valid length), and decoded
by ONE descriptor-driven kernel launch per chunk of tiles whose grid
walks tiles (kernels/ragged_decode.py). Flattening to ROWS is what
removes the target count M from the traced shape; its price is that an
op with M targets stages its K source slabs once per target row —
accepted because M > 1 is the rare case (multi-loss rows) and the
alternative (per-tile source indirection in the kernel) needs scalar-
prefetch support (ROADMAP follow-on). The launch tile count C comes from
exactly two rungs (small/big chunk), so the LIVE traced signatures per
kind stay <= 2 no matter how diverse the traffic — ``jit_entries`` is
O(1) per kind. The only filler is tail tiles and the final chunk's null
tiles, reported as ``stats.padded_byte_ratio``. The K axis and tile
width are grow-only caps: a window exceeding a cap retraces once and
retires the outgrown signatures (they can never be launched again);
cumulative compile churn stays visible as ``stats.jit_retraces``.

Staging-buffer contract: buffers are preallocated once per (kind, C)
and reused across windows; the gather writes each source's bytes
straight into its tile slab (no intermediate ``np.stack`` pyramids),
zero-filling K-axis padding and tile tails — zero bytes are the
identity for both GF(256) products and XOR, so the kernel needs no
masking and the host slices each row's valid prefix back out.

Engine-pool integration: ``execute`` returns a list of ``LaunchUnit``s
— the simulated-compute quanta the gateway dispatches onto its parallel
decode engines. A megakernel launch is SPLIT by tile ranges into one
unit per op, each billed its tile share of the measured launch time, so
one physical launch can still spread across engines. The gateway gates
every unit of a launch on the launch-wide source barrier (the staging
buffer holds all its ops' tiles), keyed by ``launch_id``.

Compute time is measured on the real jitted kernels (block_until_ready)
and scaled by the cluster profile, mirroring BlockFixer's convention.
Each traced signature is billed at its BEST-observed execution time:
the kernel's intrinsic cost is its fastest run, and transient host
stalls (a noisy neighbour during one launch) are not properties of the
simulated hardware — without the floor, one slow wall-clock sample
would skew a whole simulated-latency distribution.
"""

from __future__ import annotations

import logging
import time
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from typing import Callable

import jax
import numpy as np

from repro.gateway.planner import DecodeOp
from repro.kernels import autotune, ops
from repro.kernels import ragged_decode as _rdk
from repro.kernels.gf256_matmul import expand_coeff_bitplanes
from repro.kernels.ops import _next_pow2
from repro.obs.host import span
from repro.storage.blockstore import BlockKey

_log = logging.getLogger(__name__)

@dataclass(frozen=True)
class LaunchUnit:
    """One simulated-compute quantum the gateway schedules on its decode
    engine pool. ``op_indices`` are positions in the ``execute`` op
    list; ``fraction`` is this unit's share of its physical launch's
    wall time (a megakernel launch splits by tile ranges, one unit per
    op), so modeled-cost billing can charge ``decode_cost x fraction``
    and still sum to one launch."""

    op_indices: tuple[int, ...]
    compute: float  # scaled seconds
    kind: str
    launch_id: int
    fraction: float = 1.0
    tiles: int = 0  # descriptor tiles this unit covers


@dataclass
class CoalescerStats:
    decode_ops: int = 0  # logical reconstructions requested
    decode_calls: int = 0  # actual kernel launches issued
    max_batch: int = 0  # most ops sharing one launch
    compute_time: float = 0.0  # scaled seconds, cumulative
    windows: int = 0  # execute() calls that had work
    staged_bytes: int = 0  # useful source bytes staged for kernels
    padded_bytes: int = 0  # filler staged alongside (tile tails, null tiles)
    # ops-per-launch histogram. Bounded: at most one key per distinct
    # ops-per-launch count (<= the big chunk rung's tiles), so a
    # week-long scenario run does not accrete one int per launch.
    batch_hist: dict[int, int] = field(default_factory=dict)
    ops_by_kind: dict[str, int] = field(default_factory=dict)
    launches_by_kind: dict[str, int] = field(default_factory=dict)
    sources_by_kind: dict[str, int] = field(default_factory=dict)
    # decode ops by plan kind (DecodeOp.plan: "local" XOR, "global"
    # GF(256) row decode): blocks they rebuilt, source blocks they read
    rebuilt_by_plan: dict[str, int] = field(default_factory=dict)
    read_by_plan: dict[str, int] = field(default_factory=dict)
    jit_entries: int = 0  # LIVE traced kernel signatures (see below)
    jit_retraces: int = 0  # every trace ever taken (compile churn)
    decode_shapes: int = 0  # distinct decode shape_keys ever executed
    # write-dataplane counters (kinds "EH"/"EV"): kept separate so a
    # read-only run's decode stats stay bit-identical with or without
    # the encode path compiled in
    encode_ops: int = 0  # logical encode ops requested
    encode_calls: int = 0  # encode kernel launches issued
    encode_compute_time: float = 0.0  # scaled seconds, cumulative
    encode_windows: int = 0  # execute_encode() calls that had work

    @property
    def coalescing_ratio(self) -> float:
        """ops per launch; > 1 means batching is happening."""
        return self.decode_ops / self.decode_calls if self.decode_calls else 0.0

    @property
    def launches_per_window(self) -> float:
        return self.decode_calls / self.windows if self.windows else 0.0

    @property
    def padded_byte_ratio(self) -> float:
        """Filler fraction of all bytes staged for decode kernels."""
        total = self.staged_bytes + self.padded_bytes
        return self.padded_bytes / total if total else 0.0

    def record_launch(self, kind: str) -> None:
        self.launches_by_kind[kind] = self.launches_by_kind.get(kind, 0) + 1

    def record_ops(self, kind: str, ops: list[DecodeOp]) -> None:
        """Count one launch set's ops of ``kind``; decode ops also by plan."""
        sources = sum(len(op.sources) for op in ops)
        self.ops_by_kind[kind] = self.ops_by_kind.get(kind, 0) + len(ops)
        self.sources_by_kind[kind] = self.sources_by_kind.get(kind, 0) + sources
        if ops and not kind.startswith("E"):
            plan = ops[0].plan
            rebuilt = sum(len(op.targets) for op in ops)
            self.rebuilt_by_plan[plan] = self.rebuilt_by_plan.get(plan, 0) + rebuilt
            self.read_by_plan[plan] = self.read_by_plan.get(plan, 0) + sources

    def record_batch(self, n_ops: int) -> None:
        self.batch_hist[n_ops] = self.batch_hist.get(n_ops, 0) + 1
        self.max_batch = max(self.max_batch, n_ops)

    def sources_per_op(self, kind: str) -> float:
        """Mean source blocks per reconstruction of this kind — the
        paper's Table 1 costs: exactly t for a CORE "V" (a local group's
        other members for an LRC one), exactly k for "H"."""
        n = self.ops_by_kind.get(kind, 0)
        return self.sources_by_kind.get(kind, 0) / n if n else 0.0


class DecodeCoalescer:
    def __init__(
        self,
        compute_scale: float = 1.0,
        interpret: bool | None = None,
        autotune_kernels: bool = True,
        tile_n: int | None = None,
    ):
        self.compute_scale = compute_scale
        self.interpret = interpret
        self.autotune_kernels = autotune_kernels
        # pinned ragged tile width in bytes (None: autotuned)
        self.tile_n = tile_n
        self.stats = CoalescerStats()
        self._warm: set[tuple] = set()  # traced kernel signatures
        self._best: dict[tuple, float] = {}  # per-signature fastest run
        self._tuned: dict[str, autotune.TunedKernel] = {}
        self._shapes: set[tuple] = set()  # distinct op shape_keys seen
        # grow-only caps (retracing only on growth keeps the signature
        # set at the two chunk rungs for steady traffic) and the
        # reusable staging buffers, keyed (kind, C).
        self._k_cap: dict[str, int] = {}
        self._tile_n: dict[str, int] = {}
        self._staging: dict[tuple, np.ndarray] = {}

    def tiles_for(self, length: int, kind: str = "H") -> int:
        """Descriptor tiles one ``length``-byte output row costs at the
        current tile width (the ratcheted width once seen, else the
        same fit formula ``_execute_ragged_kind`` would pick). Used by
        per-tile modeled billing to price decode work that does not go
        through ``execute`` (background repair's codec)."""
        tn = self._tile_n.get(kind)
        if tn is None:
            tn = min(
                self.tile_n or _rdk.DEFAULT_TILE_N, _next_pow2(max(1, int(length)))
            )
        return -(-int(length) // tn)

    def jit_entries_by_kind(self) -> dict[str, int]:
        """Distinct traced signatures per decode kind — the megakernel's
        O(1)-per-kind guarantee, observable (tests/test_ragged_decode)."""
        out: dict[str, int] = {}
        for kind, *_shape in self._warm:
            out[kind] = out.get(kind, 0) + 1
        return out

    def _tuned_for(self, kind: str) -> autotune.TunedKernel | None:
        if not self.autotune_kernels:
            return None
        tuned = self._tuned.get(kind)
        if tuned is None:
            tune = (
                autotune.tuned_ragged_xor
                if kind in ("V", "EV")
                else autotune.tuned_ragged_gf256
            )
            tuned = tune(self.interpret)
            self._tuned[kind] = tuned
        return tuned

    def execute(
        self,
        decode_ops: list[DecodeOp],
        fetch: Callable[[BlockKey], np.ndarray],
    ) -> tuple[list[dict[int, np.ndarray]], list[LaunchUnit]]:
        """Run all ``decode_ops``; returns (results, units).

        ``results[i]`` maps target column -> reconstructed block for
        ``decode_ops[i]``. ``units`` are the simulated-compute quanta of
        the launches actually issued (see LaunchUnit): the gateway
        dispatches each unit onto its engine pool once the unit's ops'
        sources have landed, so one window's decode work can overlap
        other windows' fabric transfers and spread over engines."""
        results: list[dict[int, np.ndarray]] = [dict() for _ in decode_ops]
        units: list[LaunchUnit] = []
        if not decode_ops:
            return results, units
        self.stats.windows += 1
        by_kind: dict[str, list[int]] = defaultdict(list)
        for j, op in enumerate(decode_ops):
            self._shapes.add(op.shape_key)
            by_kind[op.kind].append(j)
        for kind in sorted(by_kind):
            self._execute_ragged(
                kind, by_kind[kind], decode_ops, fetch, results, units
            )
        self.stats.decode_shapes = len(self._shapes)
        return results, units

    def execute_encode(
        self,
        encode_ops: list[DecodeOp],
        fetch: Callable[[BlockKey], np.ndarray],
    ) -> tuple[list[dict[int, np.ndarray]], list[LaunchUnit]]:
        """Run a PUT window's encode work in chunked megakernel launches:
        GF(256) parity-row generation ("EH" ops, coefficient rows from
        coding/rs.py's ``parity_matrix``) and XOR-delta parity folds
        ("EV" ops — stored parity plus any number of old^new row
        contributions, one op per touched parity block per window).

        Same interface and staging contract as ``execute``, but via the
        separate kernels/ragged_encode.py jit entries, so encode signature growth
        is observable per kind and never retraces the decode kernels.
        Source keys are whatever hashables ``fetch`` resolves — the
        gateway feeds host-staged old/new row arrays under synthetic
        tokens. Emitted LaunchUnits are billed on the engine pool by the
        gateway exactly like decode launches (best-observed kernel time,
        modeled-cost override, launch-wide readiness barrier)."""
        results: list[dict[int, np.ndarray]] = [dict() for _ in encode_ops]
        units: list[LaunchUnit] = []
        if not encode_ops:
            return results, units
        self.stats.encode_windows += 1
        by_kind: dict[str, list[int]] = defaultdict(list)
        for j, op in enumerate(encode_ops):
            assert op.kind.startswith("E"), f"not an encode kind: {op.kind!r}"
            by_kind[op.kind].append(j)
        for kind in sorted(by_kind):
            self._execute_ragged(
                kind, by_kind[kind], encode_ops, fetch, results, units
            )
        return results, units

    def _execute_ragged(
        self, kind, idxs, decode_ops, fetch, results, units
    ) -> None:
        """Stage every op of ``kind`` as descriptor tiles and decode the
        whole set in chunked megakernel launches (see module docstring
        for the staging contract)."""
        tuned = None if self.tile_n is not None else self._tuned_for(kind)
        # fetch each distinct source once, straight into the gather below
        src: dict[BlockKey, np.ndarray] = {}
        # one descriptor row per OUTPUT row: (op_idx, target column,
        # coefficient bit-planes (K, 8) or None for XOR, sources, length)
        rows: list[tuple] = []
        for j in idxs:
            op = decode_ops[j]
            for s in op.sources:
                if s not in src:
                    src[s] = np.asarray(fetch(s))
            length = int(src[op.sources[0]].shape[-1])
            for s in op.sources[1:]:
                assert src[s].shape[-1] == length, (
                    f"ragged decode op sources must share a length: "
                    f"{src[s].shape[-1]} != {length}"
                )
            if kind in ("V", "EV"):
                rows.append((j, op.targets[0], None, op.sources, length))
            else:
                planes = expand_coeff_bitplanes(np.asarray(op.coeffs))
                for m, col in enumerate(op.targets):
                    rows.append((j, col, planes[m], op.sources, length))
        k_max = max(len(r[3]) for r in rows)
        self._k_cap[kind] = max(self._k_cap.get(kind, 0), k_max)
        k_cap = self._k_cap[kind]
        max_len = max(r[4] for r in rows)
        tn_fit = (
            tuned.block_n_for(max_len)
            if tuned is not None
            else min(self.tile_n or _rdk.DEFAULT_TILE_N, _next_pow2(max_len))
        )
        self._tile_n[kind] = max(self._tile_n.get(kind, 0), tn_fit)
        tn = self._tile_n[kind]
        # cut rows into fixed-width tiles
        tiles: list[tuple[int, int, int]] = []  # (row index, offset, valid)
        out_rows = [np.empty(r[4], dtype=np.uint8) for r in rows]
        for ri, (_j, _col, _planes, _sources, length) in enumerate(rows):
            off = 0
            while off < length:
                valid = min(tn, length - off)
                tiles.append((ri, off, valid))
                off += valid
        pos = 0
        for c in _rdk.chunk_sizes(len(tiles)):
            self._launch_ragged_chunk(
                kind, c, tiles[pos : pos + c], rows, src, out_rows,
                tn, k_cap, tuned, units,
            )
            pos += c
        for ri, (j, col, _planes, _sources, _length) in enumerate(rows):
            results[j][col] = out_rows[ri]
        if kind.startswith("E"):
            self.stats.encode_ops += len(idxs)
        else:
            self.stats.decode_ops += len(idxs)
        self.stats.record_ops(kind, [decode_ops[j] for j in idxs])

    def _buffer(self, key: tuple, shape: tuple) -> np.ndarray:
        """Preallocated staging buffer, reused across windows; replaced
        only when a grow-only cap (K, TN) ratchets."""
        buf = self._staging.get(key)
        if buf is None or buf.shape != shape:
            buf = np.zeros(shape, dtype=np.uint8)
            self._staging[key] = buf
        return buf

    def _launch_ragged_chunk(
        self, kind, c, chunk_tiles, rows, src, out_rows, tn, k_cap, tuned, units
    ) -> None:
        """Gather one chunk of tiles into the staging buffers, run ONE
        megakernel launch, scatter outputs, and emit per-op LaunchUnits
        billed by tile share."""
        # source-major staging (K, C, TN): the kernels' layout, so the
        # launch views it as words with no host copy
        with span("stage.gather", kind=kind, tiles=c):
            data = self._buffer((kind, "data", c), (k_cap, c, tn))
            data.fill(0)
            mc = None
            if kind not in ("V", "EV"):
                mc = self._buffer((kind, "mc", c), (k_cap, c, 8))
                mc.fill(0)
            useful = 0
            for slot, (ri, off, valid) in enumerate(chunk_tiles):
                _j, _col, planes, sources, _length = rows[ri]
                for k, s in enumerate(sources):
                    data[k, slot, :valid] = src[s][off : off + valid]
                if mc is not None:
                    mc[: planes.shape[0], slot, :] = planes
                useful += valid * len(sources)
        interpret = self.interpret
        # encode kinds route to the separate ragged_encode jit entries,
        # keeping the encode/decode signature pools independently
        # countable (jit_entries_by_kind) and independently retraced
        if kind == "V":
            launch = lambda: ops.xor_ragged(data, interpret=interpret)
        elif kind == "EV":
            launch = lambda: ops.xor_ragged_encode(data, interpret=interpret)
        elif kind == "EH":
            launch = lambda: ops.gf256_ragged_encode(mc, data, interpret=interpret)
        else:
            launch = lambda: ops.gf256_ragged(mc, data, interpret=interpret)
        # Unbilled warm-up on first sight of a traced signature: chunk
        # rung, K cap and tile width are the only jit shape keys, and
        # the one-off trace/compile cost must not be billed to the
        # window's simulated decode latency.
        sig = (kind, c, k_cap, tn)
        if sig not in self._warm:
            # a grow-only cap ratchet obsoletes this kind's previous
            # signatures — they can never be launched again, so the LIVE
            # set stays at the two chunk rungs per kind; jit_retraces
            # keeps the cumulative trace count for churn visibility
            stale = {
                s for s in self._warm if s[0] == kind and s[2:] != (k_cap, tn)
            }
            self._warm -= stale
            for s in stale:
                self._best.pop(s, None)
            if stale:
                _log.warning(
                    "coalescer: kind %r cap ratchet to (K=%d, TN=%d) "
                    "retired %d traced signature(s)",
                    kind, k_cap, tn, len(stale),
                )
            with span("kernel.warmup", kind=kind):
                jax.block_until_ready(launch())
            self._warm.add(sig)
            self.stats.jit_entries = len(self._warm)
            self.stats.jit_retraces += 1
        t0 = time.perf_counter()
        out = launch()
        jax.block_until_ready(out)
        out = np.asarray(out)
        dt = (time.perf_counter() - t0) * self.compute_scale
        best = self._best.get(sig)
        dt = dt if best is None or dt < best else best
        self._best[sig] = dt
        with span("stage.scatter", kind=kind, tiles=c):
            for slot, (ri, off, valid) in enumerate(chunk_tiles):
                out_rows[ri][off : off + valid] = out[slot, :valid]
        # one unit per op, billed its tile share of the launch, so the
        # engine pool can spread this single launch across engines
        # (the gateway still gates all of them on the launch-wide
        # source barrier)
        encode = kind.startswith("E")
        launch_id = self.stats.encode_calls if encode else self.stats.decode_calls
        tiles_per_op = Counter(rows[ri][0] for ri, _off, _valid in chunk_tiles)
        n_valid = len(chunk_tiles)
        for j in sorted(tiles_per_op):
            frac = tiles_per_op[j] / n_valid
            units.append(
                LaunchUnit(
                    (j,), dt * frac, kind, launch_id, frac, tiles_per_op[j]
                )
            )
        if encode:
            self.stats.encode_calls += 1
            self.stats.encode_compute_time += dt
        else:
            self.stats.decode_calls += 1
            self.stats.compute_time += dt
        self.stats.record_launch(kind)
        self.stats.record_batch(len(tiles_per_op))
        self.stats.staged_bytes += useful
        self.stats.padded_bytes += c * k_cap * tn - useful
