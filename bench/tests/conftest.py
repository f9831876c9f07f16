"""The benchmark's own tests: `python -m pytest bench/tests`."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def no_persistent_compile_cache(monkeypatch):
    """CPU compiles stay out of the checkout's chip compile cache."""
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "off")
