"""Pallas TPU kernel: GF(2^8) coefficient-matrix x block-data multiply.

This is the compute hot spot of erasure coding: RS encode
(parity = P @ data), RS erasure decode (message = Inv @ survivors) and
repair (missing = Coef @ sources) are all `small coefficient matrix (M,K)
x large byte matrix (K, N)` products over GF(2^8).

TPU adaptation (DESIGN.md §3): the MXU cannot do field arithmetic and
per-byte 256-entry table gathers are VPU-hostile. We instead *bit-slice*
the data operand:

    gfmul(c, x) = XOR_{b=0..7} ((x >> b) & 1) * gfmul(c, 2^b)

The 8 constants gfmul(c, 2^b) per coefficient are precomputed host-side
into an (M, K, 8) tensor, so the kernel body is pure VPU work: shifts,
masks, byte selects, XOR accumulation — no gathers, no tables.

Word layout, shared with kernels/ragged_decode.py: the kernels run on
uint32 words holding 4 bytes each. The host views its byte buffers as
words (``stage_words``, free in numpy) and byte-splats each plane
constant (``stage_planes``: ``c * 0x01010101``); the device never
changes bitwidth (Mosaic refuses in-kernel bitcasts, and XLA's relayout
of uint8 arrays compiles slowly and, at 64 MiB blocks, does not fit).
Every op is byte-lane-safe: ``(x >> b) & 0x01010101`` takes bit b of
every byte and ``(bits << 8) - bits`` spreads each set bit to a 0xFF
byte mask. There is no uint8 multiply, which Mosaic cannot lower.
Sources are a grid axis: step (j, k) XORs source k's contribution into
output block j, which stays resident across k, so VMEM per step does
not grow with K.

Grid: (N / BN, K). BN defaults to 32768 bytes: a (1, BN/4) word tile
plus the (M, BN/4) accumulator stay well inside VMEM while amortizing
per-step grid/DMA overhead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.coding import gf256
from repro.kernels.backend import resolve_interpret

DEFAULT_BLOCK_N = 32768


def expand_coeff_bitplanes(coef: np.ndarray) -> np.ndarray:
    """(M, K) uint8 coefficient matrix -> (M, K, 8) bit-plane constants
    Mc[i, k, b] = gfmul(coef[i, k], 2^b). Host-side, tiny."""
    coef = np.asarray(coef, dtype=np.uint8)
    planes = np.stack(
        [gf256._MUL_NP[coef, 1 << b] for b in range(8)], axis=-1
    )  # (M, K, 8)
    return planes.astype(np.uint8)


BYTE_ONES = 0x01010101  # bit 0 of every byte of a uint32 word


def stage_words(data) -> np.ndarray:
    """(..., N) uint8 buffer -> (..., N / 4) uint32 words: a host view,
    no copy when ``data`` is already a contiguous numpy array."""
    return np.ascontiguousarray(data, dtype=np.uint8).view(np.uint32)


def stage_planes(mc) -> np.ndarray:
    """uint8 plane constants -> uint32 words holding each byte four
    times, as the word product needs them."""
    return np.asarray(mc, dtype=np.uint8).astype(np.uint32) * np.uint32(BYTE_ONES)


def gf_word_product(d: jnp.ndarray, planes: jnp.ndarray) -> jnp.ndarray:
    """GF(2^8) product of one source's data words ``d`` with splatted
    plane constants ``planes`` (..., 8): XOR over bits b of
    (byte mask of bit b of d) & planes[..., b]. Broadcasts ``d`` (R, W)
    against each plane column (M, 1)."""
    acc = None
    for b in range(8):
        bits = jnp.bitwise_and(
            jnp.right_shift(d, jnp.uint32(b)), jnp.uint32(BYTE_ONES)
        )
        mask = jnp.left_shift(bits, jnp.uint32(8)) - bits  # 0x00 / 0xFF bytes
        term = jnp.bitwise_and(mask, planes[:, b : b + 1])
        acc = term if acc is None else jnp.bitwise_xor(acc, term)
    return acc


def accumulate_over_sources(out_ref, part: jnp.ndarray) -> None:
    """Grid axis 1 walks the K sources; the output block stays resident
    across it, so source k's contribution XORs into it in place."""

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = part

    @pl.when(pl.program_id(1) > 0)
    def _():
        out_ref[...] = jnp.bitwise_xor(out_ref[...], part)


def _gf_matmul_kernel(mc_ref, data_ref, out_ref):
    """mc_ref: (1, M, 8) splatted plane constants of source k;
    data_ref: (1, 1, BN/4) words of source k; out_ref: (M, BN/4)."""
    accumulate_over_sources(out_ref, gf_word_product(data_ref[0], mc_ref[0]))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def gf256_matmul_planes(
    planes: jnp.ndarray,
    words: jnp.ndarray,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """C (M, N) = coefficient-matrix x data over GF(2^8), on words.

    planes: (K, M, 8) splatted bit-plane constants (``stage_planes`` of
    expand_coeff_bitplanes, source-major); words: (K, 1, N/4) data words
    (``stage_words``) -> (M, N/4) uint32. N must be a multiple of block_n
    (ops.py pads) and block_n of 4. interpret=None auto-detects the
    backend (kernels/backend.py).
    """
    interpret = resolve_interpret(interpret)
    kk, m, _ = planes.shape
    k2, _, w = words.shape
    assert kk == k2, (planes.shape, words.shape)
    assert block_n % 4 == 0 and w % (block_n // 4) == 0, (w, block_n)
    bw = block_n // 4
    return pl.pallas_call(
        _gf_matmul_kernel,
        out_shape=jax.ShapeDtypeStruct((m, w), jnp.uint32),
        grid=(w // bw, kk),
        in_specs=[
            pl.BlockSpec((1, m, 8), lambda j, k: (k, 0, 0)),
            pl.BlockSpec((1, 1, bw), lambda j, k: (k, 0, j)),
        ],
        out_specs=pl.BlockSpec((m, bw), lambda j, k: (0, j)),
        interpret=interpret,
    )(planes, words)
