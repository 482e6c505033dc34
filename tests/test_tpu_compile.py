"""Compile-only checks for one TPU v5e chip, with no chip attached.

The TPU compiler is installed with jaxlib, so the main path's Pallas
kernels and the load-path encode compile here for a described v5e
topology. That catches what the interpreter cannot: unaligned blocks,
ops Mosaic cannot lower, VMEM overruns, programs too large for HBM.
Nothing runs, so nothing here says anything about results or times.

The topology is described inside a module-scoped fixture, never while
a module is imported: the TPU library admits one process at a time, and
every test worker imports this file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.coding import gf256, rs
from repro.core import product_code
from repro.core.product_code import CoreCode
from repro.kernels import ragged_decode, ragged_encode
from repro.kernels.ragged_decode import CHUNK_BIG, CHUNK_SMALL, tile_block_for

V5E_HBM_BYTES = 16 * 2**30
BLOCK_BYTES = 64 * 2**20  # the paper's HDFS block
WAREHOUSE_BLOCK_BYTES = 256 * 2**20  # Facebook's warehouse HDFS-RAID block


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with JAX's persistent cache off:
    a compile for a described chip is written to it but cannot be read
    back without the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, sharding, dtype=jnp.uint8):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


ENTRIES = {
    "decode_gf": (ragged_decode.ragged_gf256_tiles, True),
    "decode_xor": (ragged_decode.ragged_xor_tiles, False),
    "encode_gf": (ragged_encode.ragged_gf256_encode_tiles, True),
    "encode_xor": (ragged_encode.ragged_xor_encode_tiles, False),
}


def _compile_ragged(one_chip, entry, c, kk, tn) -> int:
    """Compile one ragged entry at the tile block ``tile_block_for``
    picks; returns that block."""
    fn, has_planes = ENTRIES[entry]
    tb = tile_block_for(c, tn, interpret=False)
    assert tb == c or tb % 8 == 0, tb
    # the host-staged operands: source-major uint32 words and planes
    args = [_spec((kk, c, tn // 4), one_chip, jnp.uint32)]
    if has_planes:
        args.insert(0, _spec((kk, c, 8), one_chip, jnp.uint32))
    lowered = fn.lower(*args, tile_block=tb, interpret=False)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    return tb


# Source counts: every entry at CORE's t = 3 and RS(9,6)'s k = 6; the
# XOR decode at HDFS-Xorbas's local groups of 5 and the GF(256) decode
# at its k = 10 global fallback.
RAGGED_CASES = [
    (entry, c, kk, tn)
    for entry in sorted(ENTRIES)
    for c in (CHUNK_SMALL, CHUNK_BIG)
    for kk in (3, 6)
    for tn in (4096, 65536)
] + [
    (entry, c, kk, tn)
    for entry, kk in (("decode_xor", 5), ("decode_gf", 10))
    for c in (CHUNK_SMALL, CHUNK_BIG)
    for tn in (4096, 65536)
]


@pytest.mark.parametrize(
    "entry, c, kk, tn", RAGGED_CASES, ids=["-".join(map(str, case)) for case in RAGGED_CASES]
)
def test_ragged_entry_compiles_for_v5e(one_chip, entry, c, kk, tn):
    _compile_ragged(one_chip, entry, c, kk, tn)


@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_split_tile_block_compiles_for_v5e(one_chip, entry):
    """A tile twice the widest autotune candidate makes the VMEM cap
    split a big chunk; the split block must still lower."""
    tb = _compile_ragged(one_chip, entry, CHUNK_BIG, 6, 2 * 65536)
    assert tb < CHUNK_BIG, tb


def _hbm_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )


def _assert_blocks_out(compiled, nbytes: int) -> None:
    """The results are ``nbytes`` of (q,) blocks: no tiled padding, only
    the tuple's small table of pointers beside them."""
    out = compiled.memory_analysis().output_size_in_bytes
    assert nbytes <= out <= nbytes + 4096, (out, nbytes)


def test_load_encode_of_one_core_group_fits_v5e_hbm(one_chip):
    """``load_objects`` encodes one CoreCode(9,6,3) group at a time, the
    blocks the code computes only: at 64 MiB blocks that program must
    compile and fit in v5e's HBM."""
    code = CoreCode(9, 6, 3)
    pgen = _spec((code.m, code.k), one_chip)
    rows = [[_spec((BLOCK_BYTES,), one_chip)] * code.k] * code.t  # each block as it lies
    compiled = product_code._parity_and_xor_row.lower(pgen, rows).compile()
    computed = code.t * code.m + code.n  # parity columns, the XOR row
    _assert_blocks_out(compiled, computed * BLOCK_BYTES)
    assert _hbm_bytes(compiled) < V5E_HBM_BYTES, compiled.memory_analysis()


def test_load_encode_of_one_warehouse_stripe_fits_v5e_hbm(one_chip):
    """The RS(14,10) stripe of the warehouse configuration at 256 MiB
    blocks: its 4 parities from 10 data blocks, in v5e's HBM."""
    blocks = [_spec((WAREHOUSE_BLOCK_BYTES,), one_chip)] * 10
    compiled = jax.jit(rs.make_rs(14, 10).encode_parity).lower(blocks).compile()
    _assert_blocks_out(compiled, 4 * WAREHOUSE_BLOCK_BYTES)
    assert _hbm_bytes(compiled) < V5E_HBM_BYTES / 2, compiled.memory_analysis()


@pytest.mark.parametrize("kk, q, rebuilt", [(6, BLOCK_BYTES, 1), (10, WAREHOUSE_BLOCK_BYTES, 2)])
def test_repair_step_holds_its_sources_and_results_only(one_chip, kk, q, rebuilt):
    """BlockFixer's GF(256) step at RS(9,6)'s 64 MiB and the warehouse
    RS(14,10)'s 256 MiB (two blocks rebuilt at once, as after a
    corruption): the sources go in as blocks, never stacked, and the
    program holds little beyond them and its results."""
    from repro.storage import repair

    coeffs = _spec((rebuilt, kk), one_chip)
    blocks = [_spec((q,), one_chip)] * kk
    compiled = repair._gf_matmul_jit.lower(coeffs, blocks).compile()
    _assert_blocks_out(compiled, rebuilt * q)
    mem = compiled.memory_analysis()
    # a few slices per block, whatever the block size
    assert mem.temp_size_in_bytes <= 8 * (kk + rebuilt) * gf256.MATMUL_SLICE, mem
    assert _hbm_bytes(compiled) < V5E_HBM_BYTES / 2, mem


def test_reference_matmul_temporaries_do_not_grow_with_block(one_chip):
    """``gf256.matmul`` (load, PUT verify, audits, BlockFixer) walks the
    block in fixed slices: its temporaries at 64 MiB blocks stay within
    a few slices' worth, as they do at 1 MiB."""
    code = CoreCode(9, 6, 3)
    m = code.n - code.k
    bound = 4 * m * code.k * gf256.MATMUL_SLICE
    for q in (2**20, BLOCK_BYTES):
        a = _spec((m, code.k), one_chip)
        b = _spec((code.t, code.k, q), one_chip)
        mem = gf256.matmul.lower(a, b).compile().memory_analysis()
        assert mem.temp_size_in_bytes <= bound, (q, mem)
