# The paper's compute hot spots: RS encode/decode (GF(2^8) matmul) and
# vertical XOR parity — see DESIGN.md §3 for the TPU adaptation
# (bit-plane GF multiply on the VPU; no MXU mapping exists for field
# arithmetic). Two dataplane generations coexist:
#
#   * shape-bucketed stacked launches — gf256_matmul_batched /
#     xor_parity_batched: one launch per (kind, M, K, blocklen) bucket,
#     batch sizes padded up a power-of-two ladder;
#   * the ragged megakernel — gf256_ragged / xor_ragged
#     (kernels/ragged_decode.py): a whole mixed-shape window staged as
#     fixed-width tiles plus a per-tile descriptor table, decoded in ONE
#     launch per kind with <= 2 traced signatures regardless of shape
#     diversity.
#
# kernels/autotune.py measures block_n / tile width per device kind
# at first use and persists the winners across processes.
from repro.kernels import autotune, ops, ragged_decode, ref
from repro.kernels.ops import (
    gf256_matmul,
    gf256_matmul_batched,
    gf256_ragged,
    rs_decode,
    rs_encode,
    xor_parity,
    xor_parity_batched,
    xor_ragged,
)

__all__ = [
    "autotune",
    "ops",
    "ragged_decode",
    "ref",
    "gf256_matmul",
    "gf256_matmul_batched",
    "gf256_ragged",
    "rs_decode",
    "rs_encode",
    "xor_parity",
    "xor_parity_batched",
    "xor_ragged",
]
