"""Measured kernel autotuning (kernels/autotune.py): the sweep must pick
a real candidate, cache it per backend — in process AND on disk, so the
winners survive across processes — and every candidate configuration it
can pick must be numerically correct (every block_n rung is swept on
the interpret path too, so this runs on CPU)."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, ops, ref
from repro.kernels.gf256_matmul import expand_coeff_bitplanes


def test_tuned_gf256_picks_candidate_and_caches():
    tuned = autotune.tuned_ragged_gf256(True)
    assert tuned.block_n in autotune.RAGGED_GF_TILE_CANDIDATES
    assert tuned.elapsed > 0
    assert autotune.tuned_ragged_gf256(True) is tuned  # process-lifetime cache
    assert "ragged_gf256/interpret" in autotune.report()


def test_tuned_xor_picks_candidate_and_caches():
    tuned = autotune.tuned_ragged_xor(True)
    assert tuned.block_n in autotune.RAGGED_XOR_TILE_CANDIDATES
    assert autotune.tuned_ragged_xor(True) is tuned
    assert "ragged_xor/interpret" in autotune.report()


def test_block_n_capped_to_payload_size():
    """Tile padding must never multiply kernel work: the tuned tile is
    capped to the next power of two of the actual byte length."""
    t = autotune.TunedKernel(block_n=32768, elapsed=0.0)
    assert t.block_n_for(1000) == 1024
    assert t.block_n_for(128) == 128
    assert t.block_n_for(50) == 128  # kernel minimum tile
    assert t.block_n_for(1 << 20) == 32768  # never above the tuned value


def test_tuned_ragged_kernels_pick_candidates():
    gf = autotune.tuned_ragged_gf256(True)
    assert gf.block_n in autotune.RAGGED_GF_TILE_CANDIDATES
    xor = autotune.tuned_ragged_xor(True)
    assert xor.block_n in autotune.RAGGED_XOR_TILE_CANDIDATES
    assert "ragged_gf256/interpret" in autotune.report()
    assert "ragged_xor/interpret" in autotune.report()


# ---------------------------------------------------------------------------
# cross-process persistence (the disk cache)
# ---------------------------------------------------------------------------

@pytest.fixture
def disk_cache(tmp_path, monkeypatch):
    """Isolated disk cache + empty in-process cache for each test."""
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    saved = dict(autotune._CACHE)
    autotune._CACHE.clear()
    yield path
    autotune._CACHE.clear()
    autotune._CACHE.update(saved)


def test_sweep_persists_winner_to_disk(disk_cache):
    tuned = autotune.tuned_ragged_xor(True)
    assert disk_cache.exists()
    doc = json.loads(disk_cache.read_text())
    entry = doc["entries"][autotune._disk_key("ragged_xor", True)]
    assert entry["block_n"] == tuned.block_n


def test_persisted_winner_loads_without_sweeping(disk_cache, monkeypatch):
    """A fresh process (cleared in-memory cache) must take the disk
    winner instead of re-running the measurement sweep."""
    autotune.tuned_ragged_xor(True)
    autotune._CACHE.clear()  # simulate a new process

    def boom(*a, **kw):  # the sweep must NOT run
        raise AssertionError("sweep ran despite a persisted winner")

    monkeypatch.setattr(autotune, "_best", boom)
    tuned = autotune.tuned_ragged_xor(True)
    assert tuned.block_n in autotune.RAGGED_XOR_TILE_CANDIDATES


def test_stale_disk_entry_is_ignored(disk_cache):
    """An entry whose block_n is no longer a candidate (retired config)
    must not be loaded — the sweep re-runs instead."""
    disk_cache.write_text(json.dumps({
        "schema": 1,
        "entries": {
            autotune._disk_key("ragged_xor", True): {
                "block_n": 12345, "elapsed": 0.001,
            }
        },
    }))
    tuned = autotune.tuned_ragged_xor(True)
    assert tuned.block_n in autotune.RAGGED_XOR_TILE_CANDIDATES


def test_clear_cache_clears_disk_too(disk_cache):
    autotune.tuned_ragged_xor(True)
    assert disk_cache.exists()
    autotune.clear_cache()
    assert not disk_cache.exists()
    assert autotune.report() == {}


def test_cache_disabled_via_env(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "off")
    assert autotune.cache_path() is None
    saved = dict(autotune._CACHE)
    autotune._CACHE.clear()
    try:
        autotune.tuned_ragged_xor(True)  # must not raise without a disk path
    finally:
        autotune._CACHE.clear()
        autotune._CACHE.update(saved)
    assert not (tmp_path / "autotune.json").exists()


def test_corrupt_disk_cache_is_nonfatal(disk_cache):
    disk_cache.write_text("{not json")
    tuned = autotune.tuned_ragged_xor(True)  # falls back to the sweep
    assert tuned.block_n in autotune.RAGGED_XOR_TILE_CANDIDATES


@pytest.mark.parametrize("block_n", autotune.RAGGED_GF_TILE_CANDIDATES)
def test_every_gf256_candidate_config_is_correct(block_n):
    """Whatever the sweep picks must match the reference bit-for-bit: a
    ragged launch at each candidate tile width, one coefficient row per
    tile, against the jnp oracle per tile."""
    rng = np.random.default_rng(block_n)
    c, k = 4, 6
    coefs = rng.integers(0, 256, size=(c, k), dtype=np.uint8)
    data = rng.integers(0, 256, size=(k, c, block_n), dtype=np.uint8)
    mc = np.stack([expand_coeff_bitplanes(coefs[i][None, :])[0] for i in range(c)], axis=1)
    got = ops.gf256_ragged(mc, data, interpret=True)
    for i in range(c):
        want = np.asarray(
            ref.gf256_matmul(jnp.asarray(coefs[i][None, :]), jnp.asarray(data[:, i]))
        )[0]
        np.testing.assert_array_equal(got[i], want)


def test_candidate_that_fails_to_compile_raises(disk_cache, monkeypatch):
    """A candidate the backend refuses is an error, never skipped: a
    sweep that quietly drops candidates could crown a config that was
    never the fastest, or none at all."""

    def refuse(*a, **kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(ops, "xor_ragged", refuse)
    with pytest.raises(RuntimeError, match="Mosaic"):
        autotune.tuned_ragged_xor(True)
    assert not disk_cache.exists()
