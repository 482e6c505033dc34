"""The roofline metrics find their kernels in the trace by module-name
fragments (each metric file's ``MODULES``). Lower each kernel they read
and check its compiled module's name holds its metric's fragment, so a
rename fails here instead of leaving a metric reading nothing on the
chip."""

from __future__ import annotations

import importlib.util

import jax
import jax.numpy as jnp
import pytest

import harness


def modules_of(metric: str) -> tuple:
    path = harness.HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.MODULES


def module_name(compiled) -> str:
    """``HloModule <name>, ...`` -> ``<name>``."""
    first = compiled.as_text().split("\n", 1)[0]
    return first.split()[1].rstrip(",")


def u8(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint8)


def u32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32)


def lowered(kernel: str):
    from repro.kernels import ragged_decode
    from repro.storage import repair

    if kernel == "ragged_xor_tiles":
        return ragged_decode.ragged_xor_tiles.lower(u32(3, 8, 128), tile_block=8, interpret=True)
    if kernel == "ragged_gf256_tiles":
        return ragged_decode.ragged_gf256_tiles.lower(
            u32(6, 8, 8), u32(6, 8, 128), tile_block=8, interpret=True
        )
    if kernel == "_xor_jit":
        return repair._xor_jit.lower(u8(3, 4096))
    return repair._gf_matmul_jit.lower(u8(1, 6), u8(6, 4096))


@pytest.mark.parametrize(
    "metric, kernel",
    [
        ("xor_decode_hbm_roofline", "ragged_xor_tiles"),
        ("gf256_decode_hbm_roofline", "ragged_gf256_tiles"),
        ("repair_codec_hbm_roofline", "_xor_jit"),
        ("repair_codec_hbm_roofline", "_gf_matmul_jit"),
    ],
)
def test_compiled_module_carries_the_metrics_fragment(metric, kernel):
    name = module_name(lowered(kernel).compile())
    # the metric's reader matches a module holding any of its fragments
    assert [f for f in modules_of(metric) if f in name] == [kernel], name
