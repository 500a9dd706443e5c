"""The benchmark's tests import ``bench`` from the repository root."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture
def no_compile_cache(monkeypatch):
    """Runs of the harness in a test keep JAX's persistent cache off."""
    import repro.runtime
    monkeypatch.setattr(repro.runtime, "use_compilation_cache",
                        lambda root: "off")
