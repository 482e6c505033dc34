"""Shared pytest configuration.

Optional-dependency guard: some test extras cannot always be installed
(no network in some environments), so modules that use them are skipped
at collection time instead of erroring the whole run. The scan is
content-based, keyed on the table below, so new test modules using an
optional dependency are covered automatically.

Autotune-cache isolation: kernels/autotune.py persists sweep winners to
a disk cache in the checkout by default. A test run must neither read ambient
cached state (a stale winner would silently skip the sweep
paths the tests exercise) nor write to the checkout's cache, so the
whole suite is pointed at a throwaway path unless the caller already
pinned one.
"""

from __future__ import annotations

import importlib.util
import os
import pathlib
import tempfile

if "REPRO_AUTOTUNE_CACHE" not in os.environ:
    # module-level reference keeps the directory alive for the whole
    # run; TemporaryDirectory's finalizer removes it at interpreter exit
    _AUTOTUNE_TMP = tempfile.TemporaryDirectory(prefix="repro-autotune-")
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(
        _AUTOTUNE_TMP.name, "autotune.json"
    )

# package name -> import markers that identify a module using it
OPTIONAL_DEPS = {
    "hypothesis": ("import hypothesis", "from hypothesis"),
}

collect_ignore: list[str] = []

_here = pathlib.Path(__file__).parent
for _pkg, _markers in OPTIONAL_DEPS.items():
    if importlib.util.find_spec(_pkg) is not None:
        continue
    for _path in sorted(_here.glob("test_*.py")):
        text = _path.read_text(encoding="utf-8", errors="ignore")
        if any(m in text for m in _markers):
            collect_ignore.append(_path.name)
