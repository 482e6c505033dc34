"""distributed_xor_repair: butterfly XOR across mesh shards == oracle.
Runs in a subprocess with 8 fake devices (the main session keeps 1)."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.core.distributed import distributed_xor_repair

for t, q in [(8, 4096), (5, 1000), (3, 257)]:
    n_axis = 8
    mesh = make_mesh((n_axis,), ("data",))
    rng = np.random.default_rng(t)
    blocks = rng.integers(0, 256, (t, q), dtype=np.uint8)
    want = np.bitwise_xor.reduce(blocks, axis=0)
    with jax.set_mesh(mesh):
        got = np.asarray(jax.jit(
            lambda b: distributed_xor_repair(b, mesh, "data")
        )(jnp.asarray(blocks)))
    assert np.array_equal(got, want), (t, q)
print("DISTRIBUTED_XOR_OK")
"""


def test_distributed_xor_repair_subprocess():
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
        cwd=REPO, timeout=600, capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "DISTRIBUTED_XOR_OK" in r.stdout


def test_critical_path_model():
    from repro.core.distributed import xor_repair_critical_path

    bfly, cent = xor_repair_critical_path(5, 64 << 20, 50e9, 12e6)
    assert bfly < cent / 100  # mesh repair crushes 2013-Ethernet repair
    b2, c2 = xor_repair_critical_path(5, 4 << 20, 50e9, 50e9)
    assert b2 == pytest.approx(3 * (4 << 20) / 50e9)
    assert c2 == pytest.approx(5 * (4 << 20) / 50e9)
