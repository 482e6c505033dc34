"""BENCHMARK.json against the benchmark's contract, every file it names
present, and the command's refusal to run without a TPU."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import harness

ROOT = harness.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert all(not w.startswith("/") and ".." not in w for w in BENCH["command"])
    assert 1 <= BENCH["run_seconds"] <= 51


def test_entries():
    cfgs = {c["name"] for c in BENCH["configs"]}
    used = {w["config"] for w in BENCH["workloads"]}
    assert cfgs == used
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/chip/") and (ROOT / c["file"]).is_file()
        assert all(NAME.match(r) for r in c["reduced"])
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (harness.HERE / "traffic" / f"{w['traffic']}.json").is_file()
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(BENCH["workloads"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0 < m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file()
    every = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + BENCH["per_layer"]
    names = [e["name"] for e in every]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    assert all(UNIT.match(m["unit"]) for m in BENCH["end_to_end"] + BENCH["per_layer"])


@pytest.mark.parametrize("where", ["checkout", "bare"])
def test_command_refuses_without_a_tpu(tmp_path, where):
    """No TPU: exit non-zero and print no result, in the checkout and in
    a directory holding only BENCHMARK.json and the benchmark's files."""
    root = ROOT
    if where == "bare":
        root = tmp_path / "bare"
        shutil.copytree(
            ROOT / "benchmarks" / "chip", root / "benchmarks" / "chip",
            ignore=shutil.ignore_patterns("__pycache__"),
        )
        shutil.copy(ROOT / "BENCHMARK.json", root)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", "core.degraded_read",
         "--seed", str(2**31 + 9), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
