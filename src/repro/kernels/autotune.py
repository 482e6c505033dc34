"""Measured autotuning for the ragged GF(256) / XOR Pallas entry points.

The ragged megakernels' TILE WIDTH (kernels/ragged_decode.py) is a knob
whose best setting is backend-dependent: fat tiles mean fewer grid
steps and launches, narrow tiles mean less tail-tile filler on short
rows. On TPU, fatter tiles amortize per-step grid/DMA overhead on these
bandwidth-bound kernels; under the CPU interpreter, each grid step is a
Python execution of the kernel body, so the trade-off shifts.

Instead of hard-coding per-backend defaults, this module *measures* the
candidates once per (kernel, backend) at first use — including the
interpret path, so the sweep itself is exercised by the CPU test suite —
and caches the winner for the process lifetime. The gateway's decode
coalescer asks for tuned parameters before its first launch; everything
stays off the request path because results are cached.

Winners also persist ACROSS processes (ROADMAP: run the sweep on real
hardware once, keep it): an atomic JSON cache keyed by
``device_kind/kernel/variant`` lives at ``default_cache_path()``, inside
the checkout (kernels/backend.CACHE_DIR) — override
with ``set_cache_path()`` or the ``REPRO_AUTOTUNE_CACHE`` env var (set
it to ``off`` to disable persistence) — and is consulted before any
sweep runs. Entries whose ``block_n`` no longer matches the current
candidate set are ignored (a stale cache must not pin a retired
configuration), and ``clear_cache()`` drops the disk file along with the
in-process winners. A candidate that fails to compile raises: the sweep
never skips one.

The probe window is deliberately tiny (a few 64 KiB rows): the point is
ranking the candidates, not absolute numbers. Callers cap the tile
width to their actual row length (``TunedKernel.block_n_for``), so a
tuned 64 KiB tile applied to 4 KiB rows does not 16x the work.
"""

from __future__ import annotations

import json
import os
import pathlib
import tempfile
import time
from dataclasses import dataclass

import jax
import numpy as np

from repro.kernels.backend import CACHE_DIR, resolve_interpret

# Ragged megakernel tile widths (bytes per descriptor tile).
RAGGED_GF_TILE_CANDIDATES = (1024, 4096, 16384, 65536)
RAGGED_XOR_TILE_CANDIDATES = (4096, 65536)
_PROBE_REPEATS = 3

_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"


@dataclass(frozen=True)
class TunedKernel:
    block_n: int
    elapsed: float  # best measured seconds for the winning config

    def block_n_for(self, n: int) -> int:
        """Tuned tile capped to the actual byte length (ops' next-power-
        of-two rounding), so padding never multiplies the work."""
        # deferred, like the probe imports: the kernels package inits
        # autotune before ops, so a module-level import would cycle
        from repro.kernels.ops import _next_pow2

        return min(self.block_n, _next_pow2(n))


_CACHE: dict[tuple[str, bool], TunedKernel] = {}
_cache_path_override: pathlib.Path | None = None
_cache_path_set = False

# Where tuned parameters came from, for first-class observability:
# process-cache hits, disk-cache hits, and fresh sweeps run.
_STATS = {"memory_hits": 0, "disk_hits": 0, "sweeps": 0}


def cache_stats() -> dict[str, int]:
    """Cumulative autotune cache accounting for this process: how many
    ``_tuned`` lookups were served from the in-process cache, how many
    from the persisted disk cache, and how many ran a fresh sweep."""
    return dict(_STATS)


def default_cache_path() -> pathlib.Path:
    return CACHE_DIR / "autotune.json"


def cache_path() -> pathlib.Path | None:
    """Active disk-cache location: explicit set_cache_path() wins, then
    the REPRO_AUTOTUNE_CACHE env var (value "off"/"0"/"" disables), then
    the checkout's default."""
    if _cache_path_set:
        return _cache_path_override
    env = os.environ.get(_CACHE_ENV)
    if env is not None:
        if env.strip().lower() in ("", "0", "off", "none"):
            return None
        return pathlib.Path(env)
    return default_cache_path()


def set_cache_path(path: str | os.PathLike | None) -> None:
    """Pin the disk cache to ``path`` (None disables persistence)."""
    global _cache_path_override, _cache_path_set
    _cache_path_override = pathlib.Path(path) if path is not None else None
    _cache_path_set = True


def _disk_key(kind: str, interpret: bool) -> str:
    variant = "interpret" if interpret else "compiled"
    return f"{jax.devices()[0].device_kind}/{kind}/{variant}"


def _load_disk() -> dict[str, dict]:
    path = cache_path()
    if path is None:
        return {}
    try:
        with open(path) as f:
            doc = json.load(f)
        entries = doc.get("entries", {})
        return entries if isinstance(entries, dict) else {}
    except (OSError, ValueError):
        return {}


def _save_disk(kind: str, interpret: bool, tuned: TunedKernel) -> None:
    """Atomic read-merge-write (tmp file + os.replace) so concurrent
    sweeps never tear the JSON; persistence failures are non-fatal."""
    path = cache_path()
    if path is None:
        return
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        entries = _load_disk()
        entries[_disk_key(kind, interpret)] = {
            "block_n": tuned.block_n,
            "elapsed": tuned.elapsed,
        }
        fd, tmp = tempfile.mkstemp(
            dir=path.parent, prefix=path.name, suffix=".tmp"
        )
        try:
            with os.fdopen(fd, "w") as f:
                json.dump({"schema": 1, "entries": entries}, f, indent=2,
                          sort_keys=True)
                f.write("\n")
            os.replace(tmp, path)
        except BaseException:
            # never leave a stray .tmp next to the cache
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
    except OSError:
        pass


def _load_persisted(
    kind: str, interpret: bool, candidates: tuple[int, ...]
) -> TunedKernel | None:
    entry = _load_disk().get(_disk_key(kind, interpret))
    if not isinstance(entry, dict):
        return None
    try:
        block_n = int(entry["block_n"])
        elapsed = float(entry.get("elapsed", 0.0))
    except (KeyError, TypeError, ValueError):
        return None
    if block_n not in candidates:
        return None  # stale entry from a retired candidate set
    return TunedKernel(block_n=block_n, elapsed=elapsed)


def clear_cache() -> None:
    """Drop the in-process winners AND the persisted disk cache."""
    _CACHE.clear()
    path = cache_path()
    if path is not None:
        try:
            path.unlink()
        except FileNotFoundError:
            pass
        except OSError:
            pass


def report() -> dict[str, dict]:
    """Tuned winners so far, keyed 'kind/backend' (for benchmark rows)."""
    return {
        f"{kind}/{'interpret' if interp else 'compiled'}": {
            "block_n": t.block_n,
            "elapsed": t.elapsed,
        }
        for (kind, interp), t in _CACHE.items()
    }


def _best(candidates: list[tuple[int, "callable"]]) -> tuple[int, float]:
    best_key, best_dt = None, float("inf")
    for key, launch in candidates:
        jax.block_until_ready(launch())  # untimed warm-up: trace + compile
        dt = float("inf")
        for _ in range(_PROBE_REPEATS):
            t0 = time.perf_counter()
            jax.block_until_ready(launch())
            dt = min(dt, time.perf_counter() - t0)
        if dt < best_dt:
            best_key, best_dt = key, dt
    return best_key, best_dt


def _tuned(
    kind: str,
    interpret: bool,
    candidates: tuple[int, ...],
    sweep,  # () -> list of (block_n, launch) probe candidates
) -> TunedKernel:
    """Shared memoization spine: process cache -> disk cache -> sweep."""
    cached = _CACHE.get((kind, interpret))
    if cached is not None:
        _STATS["memory_hits"] += 1
        return cached
    tuned = _load_persisted(kind, interpret, candidates)
    if tuned is None:
        bn, dt = _best(sweep())
        tuned = TunedKernel(block_n=bn, elapsed=dt)
        _save_disk(kind, interpret, tuned)
        _STATS["sweeps"] += 1
    else:
        _STATS["disk_hits"] += 1
    _CACHE[(kind, interpret)] = tuned
    return tuned


# The ragged tile-width probe stages a fixed WINDOW — a few rows of a
# fixed byte length — exactly as the coalescer would: rows cut into
# ceil(L / tn) tiles (tail padding included), tiles covered by the
# small/big chunk rungs, ONE launch per chunk. Ranking any other way is
# blind to the knob's real trade-off: fat tiles mean fewer launches and
# grid steps, narrow tiles less tail filler — per-launch bytes alone
# are constant across candidates.
_RAGGED_PROBE_ROWS = 4
_RAGGED_PROBE_ROW_BYTES = 65536


def _ragged_probe_chunks(kk: int, tn: int, rng) -> tuple[list[int], dict]:
    from repro.kernels.ragged_decode import chunk_sizes

    tiles_per_row = -(-_RAGGED_PROBE_ROW_BYTES // tn)
    chunks = chunk_sizes(_RAGGED_PROBE_ROWS * tiles_per_row)
    # host-staged (K, C, ...) buffers, as the coalescer hands them over
    bufs = {
        c: (
            rng.integers(0, 256, size=(kk, c, 8), dtype=np.uint8),
            rng.integers(0, 256, size=(kk, c, tn), dtype=np.uint8),
        )
        for c in set(chunks)
    }
    return chunks, bufs


def tuned_ragged_gf256(interpret: bool | None = None) -> TunedKernel:
    """Winning tile width for the ragged GF(256) megakernel
    (``block_n`` is the descriptor tile width TN)."""
    interpret = resolve_interpret(interpret)
    from repro.kernels import ops

    def sweep():
        rng = np.random.default_rng(2)
        kk = 6
        out = []
        for tn in RAGGED_GF_TILE_CANDIDATES:
            chunks, bufs = _ragged_probe_chunks(kk, tn, rng)

            def launch(chunks=chunks, bufs=bufs):
                return [
                    jax.block_until_ready(
                        ops.gf256_ragged(
                            bufs[c][0], bufs[c][1], interpret=interpret
                        )
                    )
                    for c in chunks
                ]

            out.append((tn, launch))
        return out

    return _tuned("ragged_gf256", interpret, RAGGED_GF_TILE_CANDIDATES, sweep)


def tuned_ragged_xor(interpret: bool | None = None) -> TunedKernel:
    """Winning tile width for the ragged XOR megakernel."""
    interpret = resolve_interpret(interpret)
    from repro.kernels import ops

    def sweep():
        rng = np.random.default_rng(3)
        kk = 3
        out = []
        for tn in RAGGED_XOR_TILE_CANDIDATES:
            chunks, bufs = _ragged_probe_chunks(kk, tn, rng)

            def launch(chunks=chunks, bufs=bufs):
                return [
                    jax.block_until_ready(
                        ops.xor_ragged(bufs[c][1], interpret=interpret)
                    )
                    for c in chunks
                ]

            out.append((tn, launch))
        return out

    return _tuned("ragged_xor", interpret, RAGGED_XOR_TILE_CANDIDATES, sweep)
