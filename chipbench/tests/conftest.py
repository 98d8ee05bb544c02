import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src"), str(ROOT / "chipbench" / "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    """Keep the harness's compile cache out of the checkout."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    return tmp_path


@pytest.fixture(autouse=True)
def tiny_sizes(monkeypatch):
    """The tiny model serves a few tokens per request and runs a window of
    a second or two: compare fewer tokens, trace half a second."""
    from chipbench import harness as H
    monkeypatch.setattr(H, "REFERENCE_MIN_TOKENS", 8)
    monkeypatch.setattr(H, "TRACE_SECONDS", 0.5)
