"""Pallas TPU kernels: descriptor-driven ragged decode megakernel.

The gateway's decode hot path is a WINDOW of reconstructions with mixed
shapes — horizontal RS decodes of varying target counts, vertical XOR
repairs, ragged byte lengths.  These kernels run a whole window in ONE
launch per kind and chunk: the host cuts every decode ROW (one output
row of one op) into fixed-width tiles, gathers the tiles into a flat
staging buffer, and the kernel's grid walks tiles, applying each tile's
own coefficient row.

Descriptor layout (built host-side by gateway/coalescer.py), source-
major so that one source's slabs for a block of tiles are contiguous:

  * ``data``  (K, C, TN) u8 — tile t's K source slabs.  A row of length
    L occupies ceil(L / TN) consecutive tiles; the tail tile is
    zero-padded past its valid length (zero bytes contribute zero to
    both GF(256) products and XOR, so no in-kernel masking is needed —
    the host slices the valid prefix back out).  Ops with fewer than K
    sources zero-pad the K axis (a zero row is the identity for both
    ops).
  * ``mc``    (K, C, 8) u8 — tile t's coefficient row, bit-plane
    expanded (gf256_matmul.expand_coeff_bitplanes); the GF kernel only.
    Replicating the planes per tile is the descriptor table: it is what
    lets tiles of DIFFERENT ops share one traced signature.
  * ``out``   (C, TN) u8 — tile t's output slab.

The launch tile count C is the jit shape key, so it is drawn from
exactly two rungs (``CHUNK_SMALL``, ``CHUNK_BIG``): a window with T
tiles issues T // CHUNK_BIG big launches plus ceil(rem / CHUNK_SMALL)
small ones, the last padded with null tiles.  Traced signatures per
kind are therefore <= 2 regardless of shape diversity, and padding is
bounded by CHUNK_SMALL - 1 tiles per window.

Kernel layout: gf256_matmul's uint32 word layout. The host views the
staged bytes as (K, C, TN/4) words, which costs nothing in numpy, and
byte-splats the plane constants (gf256_matmul.stage_words and
stage_planes); the GF body is gf256_matmul's byte-lane-safe word
product. Grid: (C / tile_block, K); step (j, k) XORs source k's slab
into output block j, which stays resident across k, so VMEM per step
is one (tile_block, TN) slab whatever K is.
Under the interpreter ``tile_block`` is the whole chunk; on TPU it is
capped so that slab stays within a VMEM budget, and it is always the
whole chunk or a multiple of 8 tiles, the only block heights Mosaic
accepts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret
from repro.kernels.gf256_matmul import accumulate_over_sources, gf_word_product

# Launch-size rungs, in tiles. Two rungs bound the traced signatures per
# kind at 2 while keeping null-tile padding under CHUNK_SMALL per window
# (a window with T tiles issues T // CHUNK_BIG big launches, then small
# ones for the remainder).
CHUNK_SMALL = 4
CHUNK_BIG = 32

# Default tile width in bytes (the autotuned sweep in kernels/autotune.py
# overrides this per backend; callers cap it to the longest row staged).
DEFAULT_TILE_N = 4096

# Per-grid-step VMEM budget for one source's (tile_block, TN) slab on a
# compiled backend; the interpreter takes the whole chunk per step.
_VMEM_TILE_BUDGET = 1 << 21


def chunk_sizes(num_tiles: int) -> list[int]:
    """Launch sizes covering ``num_tiles`` tiles from the two rungs:
    big chunks while they fit, then small ones (the last padded with
    null tiles). Total padding < CHUNK_SMALL."""
    assert num_tiles > 0, num_tiles
    chunks = [CHUNK_BIG] * (num_tiles // CHUNK_BIG)
    rem = num_tiles - CHUNK_BIG * len(chunks)
    chunks += [CHUNK_SMALL] * (-(-rem // CHUNK_SMALL))
    return chunks


def tile_block_for(c: int, tn: int, interpret: bool) -> int:
    """Tiles per grid step: the whole chunk under the interpreter (one
    step per source), VMEM-capped on a compiled backend — halved while
    one source slab exceeds the budget, but only down to a multiple of
    8, so the block is always the whole chunk or a multiple of 8.

    Every chunk rung at the autotune candidates' widest tile (64 KiB)
    fits the budget whole; the cap splits only wider tiles."""
    if interpret:
        return c
    tb = c
    while tb % 16 == 0 and tb * tn > _VMEM_TILE_BUDGET:
        tb //= 2
    return tb


def _ragged_gf_kernel(mc_ref, data_ref, out_ref):
    """mc_ref: (1, TB, 8) splatted plane constants of source k, one row
    per tile; data_ref: (1, TB, TN/4) words of source k; out_ref:
    (TB, TN/4). Every tile applies its own coefficient row, so mixed
    ops in one block cost nothing extra."""
    accumulate_over_sources(out_ref, gf_word_product(data_ref[0], mc_ref[0]))


def _ragged_xor_kernel(data_ref, out_ref):
    """data_ref: (1, TB, TN/4) words of source k -> out_ref (TB, TN/4):
    XOR over the K axis (zero-padded K rows are the XOR identity)."""
    accumulate_over_sources(out_ref, data_ref[0])


def _check(words: jnp.ndarray, tile_block: int) -> tuple[int, int, int]:
    kk, c, w = words.shape
    assert words.dtype == jnp.uint32, words.dtype
    assert c % tile_block == 0, (c, tile_block)
    return kk, c, w


def gf256_tiles_call(
    planes: jnp.ndarray, words: jnp.ndarray, tile_block: int, interpret: bool
) -> jnp.ndarray:
    """The GF(256) tile launch shared by the decode and encode entries:
    planes (K, C, 8) and words (K, C, W) uint32 -> (C, W) uint32."""
    kk, c, w = _check(words, tile_block)
    assert planes.shape == (kk, c, 8), (planes.shape, words.shape)
    return pl.pallas_call(
        _ragged_gf_kernel,
        out_shape=jax.ShapeDtypeStruct((c, w), jnp.uint32),
        grid=(c // tile_block, kk),
        in_specs=[
            pl.BlockSpec((1, tile_block, 8), lambda j, k: (k, j, 0)),
            pl.BlockSpec((1, tile_block, w), lambda j, k: (k, j, 0)),
        ],
        out_specs=pl.BlockSpec((tile_block, w), lambda j, k: (j, 0)),
        interpret=interpret,
    )(planes, words)


def xor_tiles_call(
    words: jnp.ndarray, tile_block: int, interpret: bool
) -> jnp.ndarray:
    """The XOR tile launch shared by the decode and encode entries:
    words (K, C, W) uint32 -> (C, W) uint32."""
    kk, c, w = _check(words, tile_block)
    return pl.pallas_call(
        _ragged_xor_kernel,
        out_shape=jax.ShapeDtypeStruct((c, w), jnp.uint32),
        grid=(c // tile_block, kk),
        in_specs=[pl.BlockSpec((1, tile_block, w), lambda j, k: (k, j, 0))],
        out_specs=pl.BlockSpec((tile_block, w), lambda j, k: (j, 0)),
        interpret=interpret,
    )(words)


@functools.partial(jax.jit, static_argnames=("tile_block", "interpret"))
def ragged_gf256_tiles(
    planes: jnp.ndarray,
    words: jnp.ndarray,
    *,
    tile_block: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One descriptor-driven launch over C tiles of mixed GF(256) ops.

    planes: (K, C, 8) per-tile splatted coefficient bit-planes
    (gf256_matmul.stage_planes); words: (K, C, TN/4) source tiles
    (gf256_matmul.stage_words) -> (C, TN/4) uint32. C % tile_block == 0."""
    return gf256_tiles_call(planes, words, tile_block, resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("tile_block", "interpret"))
def ragged_xor_tiles(
    words: jnp.ndarray,
    *,
    tile_block: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One descriptor-driven launch over C tiles of mixed XOR repairs:
    words (K, C, TN/4) -> (C, TN/4) uint32. C % tile_block == 0."""
    return xor_tiles_call(words, tile_block, resolve_interpret(interpret))
