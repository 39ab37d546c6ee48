import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH, os.path.join(BENCH, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    """Runs here compile for the CPU; keep them out of the benchmark's
    compilation cache."""
    import harness
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
