"""Suite-wide conftest.

* Ensures ``src/`` is importable even when pytest is invoked without
  PYTHONPATH=src (pyproject's ``pythonpath`` handles the normal case; this
  covers direct ``pytest tests/...`` invocations from other cwds).
* Arms the recompile-counter tripwire (``repro.analysis.sanitizers``) when
  ``REPRO_RECOMPILE_TRIPWIRE=1``: any test marked ``no_recompile`` fails if
  it triggers an XLA executable compile — the serve warmup invariant,
  generalized to any test.  CI's ``lint-static`` job runs one pytest leg
  with the flag set.
"""
from __future__ import annotations

import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_ROOT, "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "no_recompile: with REPRO_RECOMPILE_TRIPWIRE=1, fail this test if "
        "it triggers any XLA executable compile")


@pytest.fixture(autouse=True)
def _recompile_tripwire(request):
    if (os.environ.get("REPRO_RECOMPILE_TRIPWIRE") != "1"
            or request.node.get_closest_marker("no_recompile") is None):
        yield
        return
    from repro.analysis.sanitizers import CompileCounter
    with CompileCounter() as counter:
        yield
    if counter.count:
        pytest.fail(
            f"no_recompile test compiled {counter.count} executable(s): "
            f"{counter.names}")
