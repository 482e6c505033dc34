# The paper's compute hot spots: RS encode/decode (GF(2^8) matmul) and
# vertical XOR parity — see DESIGN.md §3 for the TPU adaptation
# (bit-plane GF multiply on the VPU; no MXU mapping exists for field
# arithmetic). The gateway's dataplane is the ragged megakernel —
# gf256_ragged / xor_ragged (kernels/ragged_decode.py): a whole
# mixed-shape window staged as fixed-width tiles plus a per-tile
# descriptor table, decoded in ONE launch per kind and chunk with <= 2
# traced signatures regardless of shape diversity. gf256_matmul /
# xor_parity are the single-stripe entries (RS encode/decode, repair).
#
# kernels/autotune.py measures the ragged tile width per device kind
# at first use and persists the winners across processes.
from repro.kernels import autotune, ops, ragged_decode, ref
from repro.kernels.ops import (
    gf256_matmul,
    gf256_ragged,
    rs_decode,
    rs_encode,
    xor_parity,
    xor_ragged,
)

__all__ = [
    "autotune",
    "ops",
    "ragged_decode",
    "ref",
    "gf256_matmul",
    "gf256_ragged",
    "rs_decode",
    "rs_encode",
    "xor_parity",
    "xor_ragged",
]
