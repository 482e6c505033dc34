"""chip_smoke.py's phases on the CPU at a small block width (Pallas
kernels interpreted): the same trace, checks and audits the script runs
on the chip at 64 MiB blocks, so a change that breaks the smoke's path
fails here first. Without a TPU the script itself must refuse to run."""

from __future__ import annotations

import importlib.util
import pathlib

import pytest

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_pass_at_small_blocks(chip_smoke):
    out = chip_smoke.run(seed=0, block_bytes=16384)
    assert out["blocks_stored"] == 72
    assert out["gets"] == out["gets_verified"] == chip_smoke.NUM_GETS + 2
    assert all(out["ops_by_kind"][k] > 0 for k in ("V", "H", "EH", "EV"))
    assert all(out["launches_by_kind"][k] > 0 for k in ("V", "H", "EH", "EV"))
    assert out["blocks_repaired"] > 0
    assert out["durability"]["blocks_lost"] == 0
    assert out["parity"]["stale_blocks"] == 0


def test_smoke_refuses_without_a_tpu(chip_smoke, monkeypatch, capsys):
    monkeypatch.setattr("sys.argv", ["chip_smoke.py"])
    assert chip_smoke.main() == 1
    captured = capsys.readouterr()
    assert "no TPU found" in captured.err
    assert '"ok"' not in captured.out
