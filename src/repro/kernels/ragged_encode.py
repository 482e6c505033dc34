"""Pallas TPU kernels: descriptor-driven ragged ENCODE megakernel.

The write-path mirror of kernels/ragged_decode.py (PR 5): a batching
window's PUT work is a mixed bag of GF(256) parity ENCODES (the
systematic RS parity rows of ``coding/rs.py`` — parities = P @ data)
and XOR-delta parity FOLDS (the single-parity-check vertical code of
``coding/spc.py`` — new_parity = stored ^ old_row ^ new_row, with any
number of folded contributions thanks to XOR associativity). Both are
the SAME tile algebra the decode megakernel runs — a GF(256) product
with per-tile coefficient bit-planes, and an XOR reduction over the K
source axis — so the kernel bodies are shared with ragged_decode and
only the jit entry points differ.

Why separate entry points at all: the coalescer's O(1)-signatures-per-
kind guarantee is *observable* (``jit_entries_by_kind``), and encode
traffic must not alias decode signatures — a PUT-heavy window growing
the encode K cap may never retrace the decode kernels, and the bench
gate "<= 2 live signatures per ENCODE kind" must be countable on its
own. Descriptor layout, chunk rungs (``CHUNK_SMALL``/``CHUNK_BIG``),
tile-width autotuning, and the zero-padding-is-identity staging
contract are all inherited from ragged_decode verbatim — see that
module's docstring for the full contract.

Host-side coefficient source: ``coding/rs.py``'s ``parity_matrix(n, k)``
rows feed the GF encode tiles ("EH" ops in gateway/coalescer.py);
the XOR fold ("EV") needs no coefficients.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve_interpret
from repro.kernels.ragged_decode import (  # noqa: F401  (re-exported contract)
    CHUNK_BIG,
    CHUNK_SMALL,
    DEFAULT_TILE_N,
    chunk_sizes,
    gf256_tiles_call,
    tile_block_for,
    xor_tiles_call,
)


@functools.partial(jax.jit, static_argnames=("tile_block", "interpret"))
def ragged_gf256_encode_tiles(
    planes: jnp.ndarray,
    words: jnp.ndarray,
    *,
    tile_block: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One descriptor-driven launch over C tiles of mixed GF(256) parity
    ENCODES: planes (K, C, 8) per-tile splatted generator-row bit-planes,
    words (K, C, TN/4) source-data tiles -> (C, TN/4) parity words.
    C % tile_block == 0; semantics identical to ragged_gf256_tiles, as a
    separately traced signature pool."""
    return gf256_tiles_call(planes, words, tile_block, resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("tile_block", "interpret"))
def ragged_xor_encode_tiles(
    words: jnp.ndarray,
    *,
    tile_block: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One descriptor-driven launch over C tiles of mixed XOR-delta
    parity folds: words (K, C, TN/4) -> (C, TN/4), XOR over the K axis
    (stored parity + any number of old/new row contributions; zero-
    padded K rows and tile tails are the XOR identity).
    C % tile_block == 0."""
    return xor_tiles_call(words, tile_block, resolve_interpret(interpret))
