"""Request coalescer: execute a window's degraded-read decodes in as few
Pallas launches as the shape mix allows.

Two dataplanes share one interface (``DecodeCoalescer(mode=...)``):

**Ragged megakernel (default, ``mode="ragged"``).** A realistic mixed-
tenant window holds decodes of MIXED shapes — horizontal RS ops with
varying target counts, vertical XOR repairs, ragged byte lengths — and
per-shape launches pay per-launch overhead once per bucket plus up to 2x
batch-ladder filler. The ragged path instead stages the WHOLE window per
kind: every decode row (one output row of one op) is cut into fixed-
width tiles (width autotuned, capped to the longest row), gathered into
a preallocated staging buffer ``(K, C, TN)`` with a per-tile descriptor
(op id, coefficient bit-planes, byte offset, valid length), and decoded
by ONE descriptor-driven kernel launch whose grid walks tiles
(kernels/ragged_decode.py). Flattening to ROWS is what removes the
target count M from the traced shape; its price is that an op with M
targets stages its K source slabs once per target row — accepted
because M > 1 is the rare case (multi-loss rows) and the alternative
(per-tile source indirection in the kernel) needs scalar-prefetch
support (ROADMAP follow-on). The launch tile count C comes from exactly
two rungs (small/big chunk), so the LIVE traced signatures per kind
stay <= 2 no matter how diverse the traffic — ``jit_entries`` is O(1)
per kind — and ``padded_ops`` is 0 by construction: the only filler is
tail tiles and the final chunk's null tiles, reported as
``stats.padded_byte_ratio``. The K axis and tile width are grow-only
caps: a window exceeding a cap retraces once and retires the outgrown
signatures (they can never be launched again); cumulative compile churn
stays visible as ``stats.jit_retraces``.

Staging-buffer contract: buffers are preallocated once per (kind, C)
and reused across windows; the gather writes each source's bytes
straight into its tile slab (no intermediate ``np.stack`` pyramids),
zero-filling K-axis padding and tile tails — zero bytes are the
identity for both GF(256) products and XOR, so the kernel needs no
masking and the host slices each row's valid prefix back out.

**Shape buckets (``mode="bucketed"``, the pre-megakernel baseline).**
One stacked launch per (kind, M, K, blocklen) bucket, batch sizes
padded up a fixed power-of-two ladder (PAD_LADDER) by replicating the
first stripe, buckets beyond the top rung split into top-rung chunks.
Kept as the measured comparison baseline (benchmarks/gateway_load.py
``gateway_megakernel`` rows) and the property-test oracle.

Engine-pool integration: ``execute`` returns a list of ``LaunchUnit``s
— the simulated-compute quanta the gateway dispatches onto its parallel
decode engines. A bucketed launch is one unit owning its batch; a
megakernel launch is SPLIT by tile ranges into one unit per op, each
billed its tile share of the measured launch time, so one physical
launch can still spread across engines. The gateway gates every unit
of a launch on the launch-wide source barrier (the staging buffer
holds all its ops' tiles), keyed by ``launch_id``.

Compute time is measured on the real jitted kernels (block_until_ready)
and scaled by the cluster profile, mirroring BlockFixer's convention.
Each traced signature is billed at its BEST-observed execution time:
the kernel's intrinsic cost is its fastest run, and transient host
stalls (a noisy neighbour during one launch) are not properties of the
simulated hardware — without the floor, one slow wall-clock sample
would skew a whole simulated-latency distribution.
"""

from __future__ import annotations

import bisect
import logging
import time
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.gateway.planner import DecodeOp
from repro.kernels import autotune, ops
from repro.kernels import ragged_decode as _rdk
from repro.kernels.gf256_matmul import expand_coeff_bitplanes
from repro.kernels.ops import _next_pow2
from repro.storage.blockstore import BlockKey

_log = logging.getLogger(__name__)

RAGGED = "ragged"
BUCKETED = "bucketed"

# Batch-size rungs for the bucketed baseline: B pads up to the next rung
# (powers of two). Buckets larger than the top rung are SPLIT into
# top-rung launches, so the distinct traced signatures per decode shape
# are truly <= len(PAD_LADDER).
PAD_LADDER = (1, 2, 4, 8, 16, 32, 64, 128, 256)


def ladder_rung(b: int) -> int:
    """Smallest ladder rung >= b. Callers cap b at PAD_LADDER[-1] first
    (the coalescer splits oversized buckets into top-rung chunks)."""
    assert 0 < b <= PAD_LADDER[-1], b
    return PAD_LADDER[bisect.bisect_left(PAD_LADDER, b)]


@dataclass(frozen=True)
class LaunchUnit:
    """One simulated-compute quantum the gateway schedules on its decode
    engine pool. ``op_indices`` are positions in the ``execute`` op
    list; ``fraction`` is this unit's share of its physical launch's
    wall time (1.0 for a bucketed launch; a megakernel launch splits by
    tile ranges, one unit per op), so modeled-cost billing can charge
    ``decode_cost x fraction`` and still sum to one launch."""

    op_indices: tuple[int, ...]
    compute: float  # scaled seconds
    kind: str
    launch_id: int
    fraction: float = 1.0
    tiles: int = 0  # descriptor tiles this unit covers (0 = bucketed)


@dataclass
class CoalescerStats:
    decode_ops: int = 0  # logical reconstructions requested
    decode_calls: int = 0  # actual kernel launches issued
    padded_ops: int = 0  # ladder filler stripes launched (bucketed only)
    max_batch: int = 0  # most ops sharing one launch
    compute_time: float = 0.0  # scaled seconds, cumulative
    windows: int = 0  # execute() calls that had work
    staged_bytes: int = 0  # useful source bytes staged for kernels
    padded_bytes: int = 0  # filler staged alongside (tails, rungs)
    # ops-per-launch histogram. Bounded: at most one key per distinct
    # batch size (<= PAD_LADDER[-1] of them), unlike the unbounded
    # per-launch list it replaced — a week-long scenario run no longer
    # accretes one int per launch.
    batch_hist: dict[int, int] = field(default_factory=dict)
    ops_by_kind: dict[str, int] = field(default_factory=dict)
    launches_by_kind: dict[str, int] = field(default_factory=dict)
    sources_by_kind: dict[str, int] = field(default_factory=dict)
    jit_entries: int = 0  # LIVE traced kernel signatures (see below)
    jit_retraces: int = 0  # every trace ever taken (compile churn)
    warmup_seconds: float = 0.0  # wall time of those first-sight launches
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

    def record_batch(self, n_ops: int) -> None:
        self.batch_hist[n_ops] = self.batch_hist.get(n_ops, 0) + 1
        self.max_batch = max(self.max_batch, n_ops)

    def sources_per_op(self, kind: str) -> float:
        """Mean source blocks per reconstruction of this kind — the
        paper's Table 1 costs: exactly t for "V", exactly k for "H"."""
        n = self.ops_by_kind.get(kind, 0)
        return self.sources_by_kind.get(kind, 0) / n if n else 0.0


class DecodeCoalescer:
    def __init__(
        self,
        compute_scale: float = 1.0,
        interpret: bool | None = None,
        autotune_kernels: bool = True,
        mode: str = RAGGED,
        tile_n: int | None = None,
    ):
        if mode not in (RAGGED, BUCKETED):
            raise ValueError(
                f"mode must be 'ragged' or 'bucketed', got {mode!r}"
            )
        self.compute_scale = compute_scale
        self.interpret = interpret
        self.autotune_kernels = autotune_kernels
        self.mode = mode
        # pinned ragged tile width in bytes (None: autotuned)
        self.tile_n = tile_n
        self.stats = CoalescerStats()
        self._warm: set[tuple] = set()  # traced kernel signatures
        self._best: dict[tuple, float] = {}  # per-signature fastest run
        self._tuned: dict[str, autotune.TunedKernel] = {}
        self._shapes: set[tuple] = set()  # distinct op shape_keys seen
        # ragged-path state: grow-only caps (retracing only on growth
        # keeps the signature set at the two chunk rungs for steady
        # traffic) and the reusable staging buffers, keyed (kind, C).
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
        for sig in self._warm:
            kind = sig[1][0] if sig[0] == BUCKETED else sig[1]
            out[kind] = out.get(kind, 0) + 1
        return out

    def _tuned_for(self, kind: str) -> autotune.TunedKernel | None:
        if not self.autotune_kernels:
            return None
        # encode kinds ("E*") only ever run ragged — there is no
        # bucketed encode baseline (the write-path comparison point is
        # the gateway's per-PUT synchronous billing, not a shape-bucket
        # dataplane) — so they always take the ragged tuners
        mode = RAGGED if kind.startswith("E") else self.mode
        key = f"{mode}:{kind}"
        tuned = self._tuned.get(key)
        if tuned is None:
            if mode == RAGGED:
                tune = (
                    autotune.tuned_ragged_xor
                    if kind in ("V", "EV")
                    else autotune.tuned_ragged_gf256
                )
            else:
                tune = autotune.tuned_xor if kind == "V" else autotune.tuned_gf256
            tuned = tune(self.interpret)
            self._tuned[key] = tuned
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
        for op in decode_ops:
            self._shapes.add(op.shape_key)
        if self.mode == RAGGED:
            by_kind: dict[str, list[int]] = defaultdict(list)
            for j, op in enumerate(decode_ops):
                by_kind[op.kind].append(j)
            for kind in sorted(by_kind):
                self._execute_ragged(
                    kind, by_kind[kind], decode_ops, fetch, results, units
                )
        else:
            # buckets split by byte length too (it is a jit shape key
            # anyway), so ragged-length windows stack cleanly
            buckets: dict[tuple, list[int]] = defaultdict(list)
            for i, op in enumerate(decode_ops):
                n = int(np.asarray(fetch(op.sources[0])).shape[-1])
                buckets[(op.shape_key, n)].append(i)
            for (key, _n), all_idxs in buckets.items():
                kind = key[0]
                tuned = self._tuned_for(kind)
                # buckets beyond the top rung split into top-rung launches
                cap = PAD_LADDER[-1]
                chunks = [
                    all_idxs[c : c + cap] for c in range(0, len(all_idxs), cap)
                ]
                for idxs in chunks:
                    self._launch_bucket(
                        key, kind, idxs, tuned, decode_ops, fetch, results, units
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

        Same interface and staging contract as ``execute``, but always
        via the ragged path (see ``_tuned_for``) and the separate
        kernels/ragged_encode.py jit entries, so encode signature growth
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

    # -- ragged megakernel path -------------------------------------------------
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
        self.stats.ops_by_kind[kind] = (
            self.stats.ops_by_kind.get(kind, 0) + len(idxs)
        )
        self.stats.sources_by_kind[kind] = self.stats.sources_by_kind.get(
            kind, 0
        ) + sum(len(decode_ops[j].sources) for j in idxs)

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
        sig = (RAGGED, kind, c, k_cap, tn)
        if sig not in self._warm:
            # a grow-only cap ratchet obsoletes this kind's previous
            # signatures — they can never be launched again, so the LIVE
            # set stays at the two chunk rungs per kind; jit_retraces
            # keeps the cumulative trace count for churn visibility
            stale = {
                s
                for s in self._warm
                if s[0] == RAGGED
                and s[1] == kind
                and (s[3], s[4]) != (k_cap, tn)
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
            t_warm = time.perf_counter()
            jax.block_until_ready(launch())
            self.stats.warmup_seconds += time.perf_counter() - t_warm
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

    # -- bucketed baseline path -------------------------------------------------
    def _launch_bucket(
        self, key, kind, idxs, tuned, decode_ops, fetch, results, units
    ) -> None:
        """One stacked launch for ``idxs`` (all sharing shape ``key``),
        padded up the ladder; emits one LaunchUnit owning the whole
        batch and writes per-op ``results``."""
        b_pad = ladder_rung(len(idxs))
        # ladder padding: replicate the first stripe — same shape,
        # same coefficients, output rows sliced away below
        pad_idxs = idxs + [idxs[0]] * (b_pad - len(idxs))
        kw = {"interpret": self.interpret}
        if kind == "V":
            data = np.stack(
                [np.stack([fetch(s) for s in decode_ops[i].sources]) for i in pad_idxs]
            )  # (B, T, q)
            if tuned is not None:
                kw["block_n"] = tuned.block_n_for(data.shape[-1])
            launch = lambda: ops.xor_parity_batched(jnp.asarray(data), **kw)
        else:
            coefs = np.stack([decode_ops[i].coeffs for i in pad_idxs])  # (B, M, K)
            data = np.stack(
                [np.stack([fetch(s) for s in decode_ops[i].sources]) for i in pad_idxs]
            )  # (B, K, q)
            if tuned is not None:
                kw["block_n"] = tuned.block_n_for(data.shape[-1])
            launch = lambda: ops.gf256_matmul_batched(coefs, data, **kw)
        # Unbilled warm-up on first sight of a traced signature: the
        # padded batch size B and byte length are jit shape keys, and
        # the one-off trace/compile cost must not be billed to the
        # window's simulated decode latency.
        sig = (BUCKETED, key, b_pad, data.shape[-1])
        if sig not in self._warm:
            t_warm = time.perf_counter()
            jax.block_until_ready(launch())
            self.stats.warmup_seconds += time.perf_counter() - t_warm
            self._warm.add(sig)
            self.stats.jit_entries = len(self._warm)
            self.stats.jit_retraces += 1
        t0 = time.perf_counter()
        out = launch()
        jax.block_until_ready(out)
        out = np.asarray(out)
        if kind == "V":
            for b, i in enumerate(idxs):  # out: (B, q)
                results[i][decode_ops[i].targets[0]] = out[b]
        else:
            for b, i in enumerate(idxs):  # out: (B, M, q)
                for m, col in enumerate(decode_ops[i].targets):
                    results[i][col] = out[b, m]
        dt = (time.perf_counter() - t0) * self.compute_scale
        # bill at the signature's best-observed time (module docstring)
        best = self._best.get(sig)
        dt = dt if best is None or dt < best else best
        self._best[sig] = dt
        units.append(
            LaunchUnit(tuple(idxs), dt, kind, self.stats.decode_calls)
        )
        stripe = int(np.prod(data.shape[1:]))  # bytes per staged stripe
        self.stats.staged_bytes += len(idxs) * stripe
        self.stats.padded_bytes += (b_pad - len(idxs)) * stripe
        self.stats.compute_time += dt
        self.stats.decode_calls += 1
        self.stats.record_launch(kind)
        self.stats.decode_ops += len(idxs)
        self.stats.padded_ops += b_pad - len(idxs)
        self.stats.record_batch(len(idxs))
        self.stats.ops_by_kind[kind] = (
            self.stats.ops_by_kind.get(kind, 0) + len(idxs)
        )
        self.stats.sources_by_kind[kind] = self.stats.sources_by_kind.get(
            kind, 0
        ) + sum(len(decode_ops[i].sources) for i in idxs)
