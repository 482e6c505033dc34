"""Test set-up for the chip benchmark's own tests: the program's sources
and this directory on the path, and the kernels' autotune winners kept
in a throwaway file, never in the checkout's cache."""

from __future__ import annotations

import os
import pathlib
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
for _p in (HERE.parents[1] / "src", HERE):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

if "REPRO_AUTOTUNE_CACHE" not in os.environ:
    _AUTOTUNE_TMP = tempfile.TemporaryDirectory(prefix="bench-autotune-")
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(_AUTOTUNE_TMP.name, "autotune.json")
