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

from repro.coding import gf256
from repro.core.product_code import CoreCode, CoreCodec
from repro.kernels import ragged_decode, ragged_encode
from repro.kernels.ragged_decode import CHUNK_BIG, CHUNK_SMALL, tile_block_for

V5E_HBM_BYTES = 16 * 2**30
BLOCK_BYTES = 64 * 2**20  # the paper's HDFS block


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


@pytest.mark.parametrize("tn", [4096, 65536])
@pytest.mark.parametrize("kk", [3, 6])
@pytest.mark.parametrize("c", [CHUNK_SMALL, CHUNK_BIG])
@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_ragged_entry_compiles_for_v5e(one_chip, entry, c, kk, tn):
    _compile_ragged(one_chip, entry, c, kk, tn)


@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_split_tile_block_compiles_for_v5e(one_chip, entry):
    """A tile twice the widest autotune candidate makes the VMEM cap
    split a big chunk; the split block must still lower."""
    tb = _compile_ragged(one_chip, entry, CHUNK_BIG, 6, 2 * 65536)
    assert tb < CHUNK_BIG, tb


def test_load_encode_of_one_core_group_fits_v5e_hbm(one_chip):
    """``load_objects`` encodes one CoreCode(9,6,3) group at a time: at
    64 MiB blocks that program must compile and fit in v5e's HBM."""
    code = CoreCode(9, 6, 3)
    objects = _spec((code.t, code.k, BLOCK_BYTES), one_chip)
    compiled = jax.jit(CoreCodec(code).encode).lower(objects).compile()
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    assert mem.output_size_in_bytes == code.rows * code.n * BLOCK_BYTES
    assert total < V5E_HBM_BYTES, mem


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
